"""On-device replay plane (apex_tpu/ondevice).

The load-bearing pins:

* :class:`DeviceFramePool` is BIT-identical to a host-orchestrated
  :class:`FramePoolReplay` across dispatch boundaries — every tree
  field, the PRNG key chain, the sampled indices and batches (there is
  only one implementation; the pin keeps it that way).
* ``FramePoolReplay.add(valid=...)``: True is bit-identical to the
  unmasked call, False is a bit-exact no-op on every state field — the
  contract the fused loop's fixed chunk-slot grid ingests through.
* The fused step's scan composition is pure dispatch amortization:
  ``steps_per_dispatch=N`` once == ``steps_per_dispatch=1`` N times,
  bit-identical train state, replay state, and key chains at fixed
  seeds.
* The snapshot path round-trips through the PR 8 checkpoint machinery
  and refuses a shape-shifting restore.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from apex_tpu.config import (ActorConfig, ApexConfig,  # noqa: E402
                             EnvConfig, LearnerConfig, ReplayConfig)
from apex_tpu.ondevice.fused import (FusedApexTrainer,  # noqa: E402
                                     acting_priorities)
from apex_tpu.ondevice.replay import DeviceFramePool  # noqa: E402
from apex_tpu.replay.frame_pool import FramePoolReplay  # noqa: E402

REPLAY_FIELDS = ("frames", "action", "reward", "discount", "obs_ids",
                 "next_ids", "frame_epoch", "sum_tree", "min_tree",
                 "pos", "f_epoch", "size", "max_priority")


def _assert_states_equal(a, b, context=""):
    for f in REPLAY_FIELDS:
        # parity assertion, not a hot loop: the drain-per-iteration IS
        # the test
        x = np.asarray(jax.device_get(getattr(a, f)))  # apexlint: disable=J006
        y = np.asarray(jax.device_get(getattr(b, f)))  # apexlint: disable=J006
        assert np.array_equal(x, y), f"{f} diverged {context}"


def _spec(capacity=64, frame_capacity=128):
    return FramePoolReplay(capacity=capacity, frame_shape=(5,),
                           frame_stack=2, frame_capacity=frame_capacity)


def _chunk(rng, kf=10, k=8):
    nf = int(rng.integers(2, kf + 1))
    nt = int(rng.integers(1, k + 1))
    return dict(
        frames=jnp.asarray(rng.integers(0, 255, (kf, 5), dtype=np.uint8)),
        n_frames=jnp.int32(nf), n_trans=jnp.int32(nt),
        action=jnp.asarray(rng.integers(0, 3, (k,)), jnp.int32),
        reward=jnp.asarray(rng.normal(size=k), jnp.float32),
        discount=jnp.asarray(rng.random(k), jnp.float32),
        obs_ref=jnp.asarray(rng.integers(0, nf, (k, 2)), jnp.int32),
        next_ref=jnp.asarray(rng.integers(0, nf, (k, 2)), jnp.int32))


# -- DeviceFramePool vs host-orchestrated FramePoolReplay ------------------

def test_device_pool_bit_parity_vs_host_pool():
    """Same chunks, same key chain -> identical tree fields, sampled
    indices, batches, and IS weights across three add/sample/update
    rounds (the 'dispatch boundary' is every host round-trip)."""
    spec = _spec()
    rng = np.random.default_rng(7)
    pool = DeviceFramePool(spec, seed=11)

    # the host twin, driven exactly as the concurrent trainer drives it
    h_state = spec.init()
    h_key = jax.random.key(11)
    h_add = jax.jit(spec.add)
    h_sample = jax.jit(spec.sample, static_argnums=(2,))
    h_update = jax.jit(spec.update_priorities)

    for round_i in range(3):
        for _ in range(4):
            ch = _chunk(rng)
            pr = jnp.asarray(rng.random(8), jnp.float32)
            pool.add(ch, pr)
            h_state = h_add(h_state, ch, pr)
        batch, weights, idx = pool.sample(16, 0.5)
        h_key, k = jax.random.split(h_key)
        hb, hw, hi = h_sample(h_state, k, 16, jnp.float32(0.5))
        assert np.array_equal(np.asarray(idx), np.asarray(hi)), round_i
        assert np.array_equal(np.asarray(weights), np.asarray(hw))
        for key in ("obs", "action", "reward", "next_obs", "discount"):
            assert np.array_equal(np.asarray(batch[key]),
                                  np.asarray(hb[key])), (round_i, key)
        new_pr = jnp.asarray(rng.random(16), jnp.float32)
        pool.update_priorities(idx, new_pr)
        h_state = h_update(h_state, hi, new_pr)
        _assert_states_equal(pool.state, h_state,
                             f"after round {round_i}")
    # the key chains stayed in lockstep too
    assert np.array_equal(np.asarray(jax.random.key_data(pool.key)),
                          np.asarray(jax.random.key_data(h_key)))


def test_masked_add_true_is_plain_false_is_identity():
    spec = _spec()
    rng = np.random.default_rng(3)
    st = spec.init()
    # warm two chunks in so trees/cursors are nontrivial
    for _ in range(2):
        st = spec.add(st, _chunk(rng), jnp.asarray(rng.random(8),
                                                   jnp.float32))
    ch = _chunk(rng)
    pr = jnp.asarray(rng.random(8), jnp.float32)
    masked = jax.jit(lambda s, c, p, v: spec.add(s, c, p, valid=v))
    plain = spec.add(st, ch, pr)
    _assert_states_equal(plain, masked(st, ch, pr, jnp.bool_(True)),
                         "valid=True vs unmasked")
    _assert_states_equal(st, masked(st, ch, pr, jnp.bool_(False)),
                         "valid=False vs identity")


def test_snapshot_roundtrip_and_spec_pin(tmp_path):
    spec = _spec()
    rng = np.random.default_rng(5)
    pool = DeviceFramePool(spec, seed=2)
    for _ in range(3):
        pool.add(_chunk(rng), jnp.asarray(rng.random(8), jnp.float32))
    pool.sample(8, 0.4)
    path = os.path.join(tmp_path, "pool.msgpack")
    pool.snapshot(path)

    other = DeviceFramePool(spec, seed=99)       # different chain on disk
    other.restore(path)
    _assert_states_equal(pool.state, other.state, "after restore")
    assert other.ingested == pool.ingested
    # the restored chain continues identically
    b1, w1, i1 = pool.sample(8, 0.4)
    b2, w2, i2 = other.sample(8, 0.4)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    assert np.array_equal(np.asarray(b1["obs"]), np.asarray(b2["obs"]))

    # a shape-shifting restore refuses loudly
    with pytest.raises(ValueError, match="different pool spec"):
        DeviceFramePool(_spec(capacity=32, frame_capacity=64)).restore(
            path)


# -- the fused step --------------------------------------------------------

def _cfg(warmup=32, capacity=512, n_envs=2, send=8):
    return ApexConfig(
        env=EnvConfig(env_id="ApexCatchSmall-v0", frame_stack=2,
                      clip_rewards=False, episodic_life=False),
        replay=ReplayConfig(capacity=capacity, warmup=warmup,
                            beta_anneal=2000),
        learner=LearnerConfig(batch_size=16, compute_dtype="float32",
                              target_update_interval=50,
                              publish_interval=5),
        actor=ActorConfig(n_actors=1, n_envs_per_actor=n_envs,
                          send_interval=send))


def _run_fused(steps_per_dispatch, dispatches):
    t = FusedApexTrainer(_cfg(), steps_per_dispatch=steps_per_dispatch,
                         rollout_len=8)
    for _ in range(dispatches):
        t.train_state, t.replay_state, t.key, info = t.fused.dispatch(
            t.train_state, t.replay_state, t.key)
    return t


def test_fused_vs_serial_train_state_parity():
    """steps_per_dispatch=3 x 2 dispatches == steps_per_dispatch=1 x 6
    dispatches: bit-identical params/opt/step, replay state, and both
    key chains — the scan composition is pure latency amortization."""
    a = _run_fused(3, 2)
    b = _run_fused(1, 6)
    pa = jax.tree.leaves(jax.device_get(
        (a.train_state.params, a.train_state.opt_state)))
    pb = jax.tree.leaves(jax.device_get(
        (b.train_state.params, b.train_state.opt_state)))
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(pa, pb))
    assert int(a.train_state.step) == int(b.train_state.step) > 0
    _assert_states_equal(a.replay_state, b.replay_state,
                         "fused vs serial")
    assert np.array_equal(
        np.asarray(jax.random.key_data(a.key)),
        np.asarray(jax.random.key_data(b.key)))
    assert np.array_equal(
        np.asarray(jax.random.key_data(a.fused.engine.key)),
        np.asarray(jax.random.key_data(b.fused.engine.key)))
    assert int(a.fused.ingested_dev) == int(b.fused.ingested_dev)
    assert a.fused.train_steps == b.fused.train_steps > 0
    assert a.fused.prio_writebacks == b.fused.prio_writebacks > 0


def test_acting_priorities_match_host_epilogue_within_one_ulp():
    """The device priorities follow the numpy epilogue formula; XLA's
    FMA contraction rounds the multiply-add once where numpy rounds
    twice, so the envelope is <= 1 ulp (module-docstring contract:
    self-consistency inside the fused plane, not host bit-parity)."""
    from apex_tpu.models.dueling import DuelingDQN
    from apex_tpu.ops.losses import make_optimizer
    from apex_tpu.training.anakin import make_anakin_engine
    from apex_tpu.training.apex import dqn_env_specs
    from apex_tpu.training.state import create_train_state

    cfg = _cfg(n_envs=3)
    spec, fs, fd, stack = dqn_env_specs(cfg)
    model = DuelingDQN(**spec)
    stacked = fs[:-1] + (stack * fs[-1],)
    ts = create_train_state(model, make_optimizer(), jax.random.key(0),
                            np.zeros((1,) + stacked, fd))
    eng = make_anakin_engine(cfg, rollout_len=16)
    eng.key, k = jax.random.split(eng.key)
    _, _, out = eng._jit(ts.params, eng.epsilons, eng.carry,
                         eng.carry_frames, k)
    dev = np.asarray(jax.device_get(jax.jit(acting_priorities)(out)))
    got = jax.device_get(out)
    # the engine keeps the two numbers of each Q row the priority reads
    target = got["reward"] + got["discount"] * got["qn_max"]
    host = (np.abs(target - got["q_taken"]).astype(np.float32)
            + np.float32(1e-6))
    assert np.allclose(dev, host, rtol=2e-7, atol=0), \
        np.abs(dev - host).max()


def test_fused_trainer_trains_and_reports():
    t = FusedApexTrainer(_cfg(), steps_per_dispatch=2, rollout_len=8)
    t.train(total_steps=4, max_seconds=120.0)
    assert t.steps_rate.total >= 4
    summary = t.fleet_summary()
    ond = summary["metrics"]["ondevice"]
    assert ond["dispatches"] > 0 and ond["chunks"] > 0
    assert ond["train_steps"] > 0 and ond["prio_writebacks"] >= 1
    assert ond["transitions"] > 0
    # the fused plane beat into the registry
    idents = {p["identity"] for p in summary["peers"]}
    assert "fused-0" in idents


def test_fused_checkpoint_roundtrip(tmp_path):
    """The on-device replay state host-spills through the PR 8
    checkpoint machinery: restore imposes the donated pool bit-exactly
    and re-seeds the device warm/anneal counter."""
    t = FusedApexTrainer(_cfg(), steps_per_dispatch=2, rollout_len=8,
                         checkpoint_dir=str(tmp_path))
    for _ in range(3):
        t.train_state, t.replay_state, t.key, _ = t.fused.dispatch(
            t.train_state, t.replay_state, t.key)
    t.ingested = t.fused.transitions
    path = t.save_checkpoint()
    assert os.path.exists(path)

    t2 = FusedApexTrainer(_cfg(), steps_per_dispatch=2, rollout_len=8,
                          checkpoint_dir=str(tmp_path))
    t2.restore()
    _assert_states_equal(t.replay_state, t2.replay_state,
                         "after checkpoint restore")
    assert int(t2.fused.ingested_dev) == min(
        t.ingested, int(t.fused._ing_cap))
    assert np.array_equal(np.asarray(jax.random.key_data(t.key)),
                          np.asarray(jax.random.key_data(t2.key)))
    # the restored trainer keeps dispatching
    t2.train_state, t2.replay_state, t2.key, info = t2.fused.dispatch(
        t2.train_state, t2.replay_state, t2.key)
    assert info["transitions"] > 0


def test_fused_refusals_name_their_knobs():
    cfg = _cfg()
    # dp>1 is no longer refused wholesale (PR 17) — the honest capability
    # errors left are divisibility, naming BOTH knobs each
    with pytest.raises(ValueError) as ei:
        FusedApexTrainer(cfg.replace(
            learner=dataclasses.replace(cfg.learner, mesh_shape=(2,)),
            actor=dataclasses.replace(cfg.actor, n_envs_per_actor=3)))
    assert "--n-envs-per-actor" in str(ei.value)
    assert "--mesh-dp" in str(ei.value)
    with pytest.raises(ValueError) as ei:
        FusedApexTrainer(cfg.replace(learner=dataclasses.replace(
            cfg.learner, mesh_shape=(4,), batch_size=18)))
    assert "batch_size" in str(ei.value)
    assert "mesh" in str(ei.value)
    # a mesh wider than the host still refuses with the device count
    with pytest.raises(ValueError, match="devices"):
        FusedApexTrainer(cfg.replace(learner=dataclasses.replace(
            cfg.learner, mesh_shape=(1024,))))
    # non-jittable env ids refuse in make_jax_env before any pool spawn
    with pytest.raises(ValueError, match="ApexCartPole"):
        FusedApexTrainer(cfg.replace(env=dataclasses.replace(
            cfg.env, env_id="ApexCartPole-v0", frame_stack=1)))
    with pytest.raises(ValueError, match="steps_per_dispatch"):
        FusedApexTrainer(cfg, steps_per_dispatch=0)


def test_cli_env_twins(monkeypatch):
    from apex_tpu.runtime.cli import build_parser
    monkeypatch.setenv("APEX_ROLLOUT", "fused")
    monkeypatch.setenv("APEX_STEPS_PER_DISPATCH", "7")
    args = build_parser().parse_args([])
    assert args.rollout == "fused"
    assert args.steps_per_dispatch == 7


def test_fused_bench_lane_direction_classes():
    """The part-1f ondevice_fused lane's leaves classify higher-better
    in the obs.slo --check differ (the regression gate direction)."""
    from apex_tpu.obs.slo import _direction
    assert _direction("ondevice_fused.toy.frames_per_sec") > 0
    assert _direction("ondevice_fused.toy.train_steps_per_sec") > 0
    assert _direction("ondevice_fused.pixel.transitions_per_sec") > 0
    # the PR 17 fused_dp lane's leaves ride the same classifier
    assert _direction("fused_dp.dp1.frames_per_sec") > 0
    assert _direction("fused_dp.dpN.frames_per_sec") > 0
    assert _direction("fused_dp.dpN.train_steps_per_sec") > 0
