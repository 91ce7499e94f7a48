"""On-device Anakin rollouts (training/anakin.py).

The load-bearing pin is CHUNK BIT-COMPATIBILITY: the fused scan's sealed
chunks must be byte-identical to what the host
:class:`~apex_tpu.replay.frame_chunks.FrameChunkBuilder` emits for the same
trajectory — same chunk boundaries, frame carry, refs, padding, priorities
— and must ingest into :class:`~apex_tpu.replay.frame_pool.FramePoolReplay`
to the same state.  The host side replays the engine's exact key chain
through the numpy builder (the jax envs stepped eagerly), so any drift in
the scan port's state machine shows up as an array mismatch.
"""

from __future__ import annotations

import numpy as np
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from apex_tpu.actors.pool import (EpisodeStat,  # noqa: E402
                                  drain_builder_chunks)
from apex_tpu.config import (ActorConfig, ApexConfig,  # noqa: E402
                             EnvConfig, LearnerConfig, ReplayConfig)
from apex_tpu.envs.registry import make_jax_env  # noqa: E402
from apex_tpu.models import make_q_network  # noqa: E402
from apex_tpu.models.dueling import make_policy_fn  # noqa: E402
from apex_tpu.ops.losses import make_optimizer  # noqa: E402
from apex_tpu.replay.frame_chunks import FrameChunkBuilder  # noqa: E402
from apex_tpu.training import anakin  # noqa: E402
from apex_tpu.training.anakin import (AnakinPool,  # noqa: E402
                                      make_anakin_engine)
from apex_tpu.training.apex import ApexTrainer, dqn_env_specs  # noqa: E402
from apex_tpu.training.state import create_train_state  # noqa: E402

CHUNK_KEYS = ("frames", "n_frames", "n_trans", "action", "reward",
              "discount", "obs_ref", "next_ref")


def _cfg(env_id="ApexCatchSmall-v0", stack=2, n_envs=3, send=16,
         torso="dueling"):
    return ApexConfig(
        env=EnvConfig(env_id=env_id, frame_stack=stack,
                      clip_rewards=False, episodic_life=False),
        replay=ReplayConfig(capacity=1024, warmup=128),
        learner=LearnerConfig(batch_size=32, ingest_chunk=32,
                              compute_dtype="float32",
                              target_update_interval=100, torso=torso),
        actor=ActorConfig(n_actors=1, n_envs_per_actor=n_envs,
                          send_interval=send))


def _params(cfg):
    model_spec, frame_shape, frame_dtype, frame_stack = dqn_env_specs(cfg)
    model = make_q_network(model_spec)
    stacked = frame_shape[:-1] + (frame_stack * frame_shape[-1],)
    ts = create_train_state(model, make_optimizer(), jax.random.key(0),
                            np.zeros((1,) + stacked, frame_dtype))
    return model, model_spec, frame_shape, frame_dtype, ts.params


def _host_replay(cfg, engine, params, model, dispatches):
    """Replay the engine's exact key chain through the numpy builder:
    eager jax env steps + the standalone jitted policy feeding per-slot
    FrameChunkBuilders — the ground truth the scan port must match."""
    _, frame_shape, frame_dtype, frame_stack = dqn_env_specs(cfg)
    env = make_jax_env(cfg.env.env_id, cfg.env)
    policy = jax.jit(make_policy_fn(model))
    B, T = engine.B, engine.T
    builders = [FrameChunkBuilder(
        engine.n, cfg.learner.gamma, engine.S, frame_shape,
        chunk_transitions=engine.K, frame_dtype=frame_dtype)
        for _ in range(B)]
    # the engine consumed key(seed) -> (chain, init) at construction
    chain, init_key = jax.random.split(
        jax.random.key(cfg.env.seed + 1000))
    states, obs0 = jax.vmap(env.reset)(engine.reset_keys(init_key))
    obs0 = np.asarray(obs0)
    for b in range(B):
        builders[b].begin_episode(obs0[b])
    vstep = jax.jit(jax.vmap(lambda s, a, k: env.step(s, a, k)))
    eps = engine.epsilons
    per_dispatch, stats = [], []
    for _d in range(dispatches):
        chain, kd = jax.random.split(chain)
        for sk in jax.random.split(kd, T):
            stack = np.stack([bl.current_stack() for bl in builders])
            a, q = policy(params, stack, eps,
                          jax.random.fold_in(sk, anakin.T_POLICY))
            # apexlint: disable=J008 -- parity replay harness, not a hot loop: eager materialization keeps the ground-truth trace obvious
            a, q = np.asarray(a), np.asarray(q)
            # apexlint: disable=J004 -- replaying the engine's documented tag discipline: T_POLICY vs T_ENV folds are disjoint
            keys = jax.vmap(jax.random.fold_in, (None, 0))(
                jax.random.fold_in(sk, anakin.T_ENV),
                np.arange(B, dtype=np.uint32))
            states, obs, rew, done, ff = vstep(states, jnp.asarray(a),
                                               keys)
            obs, rew, done, ff = map(np.asarray, (obs, rew, done, ff))
            for b in range(B):
                builders[b].add_step(int(a[b]), float(rew[b]), q[b],
                                     ff[b], bool(done[b]), False)
                if done[b]:
                    stats.append((b, float(rew[b])))
                    builders[b].begin_episode(obs[b])
        host = []
        for b in range(B):
            host.extend(drain_builder_chunks(builders[b]))
        per_dispatch.append(host)
    return per_dispatch, stats


def test_chunk_bit_compat_with_host_builder():
    """Three dispatches (carry state survives dispatch boundaries): every
    sealed chunk byte-equals the host builder's, priorities included."""
    cfg = _cfg()
    model, _spec, _shape, _dtype, params = _params(cfg)
    engine = make_anakin_engine(cfg, rollout_len=40)
    host_stream, _ = _host_replay(cfg, engine, params, model,
                                  dispatches=3)
    compared = 0
    for host in host_stream:
        msgs, _stats = engine.rollout(params)
        assert len(host) == len(msgs)
        for h, e in zip(host, msgs):
            np.testing.assert_array_equal(h["priorities"],
                                          e["priorities"])
            assert h["n_trans"] == e["n_trans"]
            for k in CHUNK_KEYS:
                np.testing.assert_array_equal(
                    np.asarray(h["payload"][k]),
                    np.asarray(e["payload"][k]), err_msg=k)
            compared += 1
    assert compared >= 8       # several chunks incl. cross-dispatch carry


def test_chunk_bit_compat_on_token_contexts():
    """The same pin on ``ApexTokens-v0`` under the toy GLM torso: 1-D byte
    frames, frame stack 1, a 64-wide Q row of which the carry keeps two
    numbers, episodes that end by truncation."""
    cfg = _cfg(env_id="ApexTokens-v0", stack=1, n_envs=2, send=16,
               torso="glm47_flash_tiny")
    model, spec, frame_shape, _dtype, params = _params(cfg)
    assert spec["torso"] == "glm47_flash_tiny" and frame_shape == (32,)
    engine = make_anakin_engine(cfg, rollout_len=40)
    assert engine.carry.q.shape == (2, engine.M, 16, 2)
    assert engine.carry.w_q.shape == (2, engine.n + 1, 2)
    host_stream, stats = _host_replay(cfg, engine, params, model,
                                      dispatches=2)
    compared = 0
    for host in host_stream:
        msgs, _stats = engine.rollout(params)
        assert len(host) == len(msgs)
        for h, e in zip(host, msgs):
            np.testing.assert_array_equal(h["priorities"],
                                          e["priorities"])
            for k in CHUNK_KEYS:
                np.testing.assert_array_equal(
                    np.asarray(h["payload"][k]),
                    np.asarray(e["payload"][k]), err_msg=k)
            compared += 1
    assert compared >= 6 and stats          # an episode ended (64 steps)


def _live_bytes() -> int:
    """Live device bytes, each buffer once (benchmark/tests/
    test_harness.py counts them the same way)."""
    return sum({x.unsafe_buffer_pointer(): x.nbytes
                for x in jax.live_arrays()}.values())


def test_a_publish_holds_one_snapshot():
    """``AnakinPool.publish_params`` snapshots the learner's tree as the
    policy multiplies it, into the buffers of the snapshot it replaces:
    however many publishes, one snapshot's bytes are live, the learner's
    own tree is untouched, and the engine acts on the newest."""
    import gc

    from apex_tpu.models import acting_params

    import dataclasses

    cfg = _cfg(env_id="ApexTokens-v0", stack=1, n_envs=2, send=16,
               torso="glm47_flash_tiny")
    cfg = dataclasses.replace(cfg, learner=dataclasses.replace(
        cfg.learner, compute_dtype="bfloat16"))
    model, _spec, _shape, _dtype, params = _params(cfg)
    pool = AnakinPool(cfg, make_anakin_engine(cfg, rollout_len=8))
    want = acting_params(model, params)
    snap_bytes = sum(x.nbytes for x in jax.tree.leaves(want))
    assert snap_bytes < 0.6 * sum(x.nbytes for x in jax.tree.leaves(params))
    del want
    gc.collect()
    before = _live_bytes()
    pool.publish_params(1, params)
    jax.block_until_ready(pool._params)
    assert _live_bytes() - before == snap_bytes
    first = jax.tree.leaves(pool._params)
    bumped = jax.tree.map(lambda x: x + 1.0, params)
    held = _live_bytes()
    for version in (2, 3, 4):
        pool.publish_params(version, bumped)
    jax.block_until_ready(pool._params)
    assert _live_bytes() == held                # still one snapshot
    assert all(x.is_deleted() for x in first)   # its buffers moved on
    for got, live in zip(jax.tree.leaves(pool._params),
                         jax.tree.leaves(bumped)):
        assert not live.is_deleted()
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(live.astype(got.dtype), np.float32))
    assert len(pool.poll_chunks(1)) == 1


def test_chunk_ingest_parity_into_frame_pool():
    """The replay-path pin: on-device chunks ingested into FramePoolReplay
    produce the SAME state (frames ring, id tables, trees, cursors) as the
    host-built chunks — they flow into the existing path unchanged."""
    from apex_tpu.replay.frame_pool import FramePoolReplay

    cfg = _cfg(n_envs=2, send=16)
    model, _spec, frame_shape, frame_dtype, params = _params(cfg)
    engine = make_anakin_engine(cfg, rollout_len=48)
    host_stream, _ = _host_replay(cfg, engine, params, model,
                                  dispatches=1)
    msgs, _ = engine.rollout(params)
    host = host_stream[0]
    pool = FramePoolReplay(capacity=256, frame_shape=frame_shape,
                           frame_stack=engine.S,
                           frame_dtype=np.dtype(frame_dtype).name)
    add = jax.jit(pool.add)

    def ingest(stream):
        state = pool.init()
        for m in stream:
            state = add(state, jax.tree.map(jnp.asarray, m["payload"]),
                        jnp.asarray(m["priorities"]))
        return state

    sa, sb = ingest(host), ingest(msgs)
    for field in ("frames", "action", "reward", "discount", "obs_ids",
                  "next_ids", "frame_epoch", "sum_tree", "min_tree",
                  "pos", "f_epoch", "size", "max_priority"):
        np.testing.assert_array_equal(
            np.asarray(getattr(sa, field)), np.asarray(getattr(sb, field)),
            err_msg=field)


def test_engine_episode_stats_match_env():
    cfg = _cfg(n_envs=2)
    model, _spec, _shape, _dtype, params = _params(cfg)
    engine = make_anakin_engine(cfg, rollout_len=60)
    _, host_stats = _host_replay(cfg, engine, params, model, dispatches=1)
    _msgs, stats = engine.rollout(params)
    assert len(stats) == len(host_stats) and len(stats) >= 2
    assert all(isinstance(s, EpisodeStat) for s in stats)
    # CatchSmall: 3 balls of +-1 -> integer returns in [-3, 3], 18 steps
    assert all(abs(s.reward) <= 3 and s.length == 18 for s in stats)


def test_rally_engine_runs():
    cfg = _cfg(env_id="ApexRallySmall-v0", stack=2, n_envs=2)
    _model, _spec, _shape, _dtype, params = _params(cfg)
    engine = make_anakin_engine(cfg, rollout_len=32)
    msgs, _ = engine.rollout(params)
    msgs2, _ = engine.rollout(params)
    total = sum(m["n_trans"] for m in msgs + msgs2)
    assert total >= engine.B * 32        # every step eventually emits


def test_anakin_pool_trains_apex_trainer():
    """The co-located training mode end to end: AnakinPool as the
    trainer's chunk source — steps taken, transitions ingested, on-device
    counters live in fleet_summary, heartbeat peer visible."""
    cfg = _cfg(n_envs=4, send=32)
    pool = AnakinPool(cfg, make_anakin_engine(cfg))
    trainer = ApexTrainer(cfg, pool=pool, publish_min_seconds=0.2,
                          train_ratio=0.5)
    trainer.train(total_steps=6, max_seconds=90, log_every=10 ** 9)
    assert trainer.steps_rate.total >= 6
    assert trainer.ingested >= cfg.replay.warmup
    summary = trainer.fleet_summary()
    ond = summary["metrics"]["ondevice"]
    assert ond["chunks"] > 0 and ond["frames"] > 0
    assert ond["dispatches"] > 0 and ond["transitions"] > 0
    peers = {p["identity"]: p["role"] for p in summary["peers"]}
    assert peers.get("ondevice-0") == "rollout"


def test_anakin_pool_device_params_and_backpressure():
    cfg = _cfg(n_envs=2)
    pool = AnakinPool(cfg, make_anakin_engine(cfg, rollout_len=16))
    assert pool.accepts_device_params
    # no params yet: polling produces nothing (no dispatch without a
    # policy), so the replay-ratio gate pauses collection for free
    assert pool.poll_chunks(4) == []
    _model, _spec, _shape, _dtype, params = _params(cfg)
    pool.publish_params(1, params)
    got = pool.poll_chunks(1)
    assert len(got) == 1 and "payload" in got[0]
    # the dispatch produced one chunk per env slot: the second drains the
    # pending buffer WITHOUT a fresh dispatch
    d0 = pool.engine.dispatches
    rest = pool.poll_chunks(1)
    assert len(rest) == 1 and pool.engine.dispatches == d0
    stats = pool.poll_stats()
    assert any(getattr(s, "role", "") == "rollout" for s in stats)


def test_make_anakin_engine_guards():
    cfg = _cfg(env_id="ApexCartPole-v0", stack=1)
    with pytest.raises(ValueError, match="ApexCartPole-v0"):
        make_anakin_engine(cfg)


def test_loadgen_slot_bands_match_worker_slots():
    """A loadgen process's ladder band equals the host vector worker's for
    the same actor id — the fleet exploration spectrum is topology-
    independent."""
    from apex_tpu.actors.vector import worker_slots

    cfg = ApexConfig(
        env=EnvConfig(env_id="ApexCatchSmall-v0", frame_stack=2,
                      clip_rewards=False, episodic_life=False),
        actor=ActorConfig(n_actors=3, n_envs_per_actor=4))
    for band in range(3):
        eng = make_anakin_engine(cfg, n_envs=4, slot_band=band,
                                 total_slots=12)
        slot_ids, _seeds, eps = worker_slots(cfg, band)
        assert eng.slot_ids == slot_ids
        np.testing.assert_allclose(eng.epsilons,
                                   np.asarray(eps, np.float32))


def test_run_loadgen_ships_chunks_through_sender(monkeypatch):
    """Loadgen plumbing with the transport faked out: params arrive, the
    engine dispatches, chunks + heartbeats ship through the sender."""
    import threading

    from apex_tpu.config import RoleIdentity
    from apex_tpu.runtime import roles, transport

    cfg = _cfg(n_envs=2, send=16)
    _model, _spec, _shape, _dtype, params = _params(cfg)
    host_params = jax.device_get(params)

    class FakeSub:
        def __init__(self, comms):
            pass

        def wait_first(self, stop_event):
            return (1, host_params)

        def poll(self, ms):
            return None

        def close(self):
            pass

    sent = {"chunks": [], "stats": []}

    class FakeSender:
        chunks_sent = 0
        acks_received = 0

        def __init__(self, comms, name):
            pass

        def send_chunk(self, msg, stop_event, **kw):
            sent["chunks"].append(msg)
            return True

        def send_stat(self, stat):
            sent["stats"].append(stat)

        def close(self):
            pass

    monkeypatch.setattr(transport, "ParamSubscriber", FakeSub)
    monkeypatch.setattr(transport, "ChunkSender", FakeSender)
    stop = threading.Event()
    out = roles.run_loadgen(cfg, RoleIdentity(role="loadgen", actor_id=0,
                                              n_actors=1),
                            stop_event=stop, max_seconds=8.0,
                            rollout_len=24)
    assert out["dispatches"] >= 1 and out["chunks"] >= 1
    assert sent["chunks"] and all("payload" in m for m in sent["chunks"])
    assert out["frames"] == out["dispatches"] * 24 * 2


def test_outbox_overflow_bound_documented():
    """M sizing: transitions per dispatch <= leftover window + T + n, so
    seals can never exceed the sealed-slot budget for the toy envs; the
    host-side check would fire loudly rather than corrupt."""
    cfg = _cfg(n_envs=2, send=16)
    engine = make_anakin_engine(cfg, rollout_len=64)
    assert engine.M >= (64 + engine.n + engine.K - 1) // engine.K + 3 - 1
    _model, _spec, _shape, _dtype, params = _params(cfg)
    for _ in range(3):
        msgs, _ = engine.rollout(params)     # would raise on overflow
        assert all(m["n_trans"] >= 1 for m in msgs)
