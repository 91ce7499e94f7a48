"""Fused plane x dp mesh (PR 17): the three dp=1 refusals are gone.

The load-bearing pins:

* **Fused dp=N == serial dp=N, bit-identical.**  On an emulated
  ``device_count=4`` CPU mesh (subprocess pytest — the PR 3 pattern),
  ``steps_per_dispatch=3 x 2`` dispatches equal ``steps_per_dispatch=1
  x 6``: params, opt_state, all per-shard replay trees (leading shard
  axis intact), the engine key chain, the sample key chain, and the
  device ingest counter.  The dp speedup claim rests on proven
  identical work.
* **Replay-service batches train under dp>1** — the batch shards over
  the mesh, the update pmeans, and every shard write-back routes with
  the idx alignment unchanged (the PR 7 guard is gone).
* **Tenant partitions ride the same path** — a tenant-qualified learner
  (APEX_TENANT) trains on service batches at dp=2 (the PR 13 guard fell
  transitively with the service guard).
* **Live train_ratio** (the PR 15 carried knob): the device budget
  throttles fused train steps to ``ingested * ratio / batch`` at every
  dp width, and the no-ratio program is untouched.
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402

from apex_tpu.config import (ActorConfig, ApexConfig,  # noqa: E402
                             EnvConfig, LearnerConfig, ReplayConfig,
                             small_test_config)
from apex_tpu.ondevice.fused import FusedApexTrainer  # noqa: E402

REPLAY_FIELDS = ("frames", "action", "reward", "discount", "obs_ids",
                 "next_ids", "frame_epoch", "sum_tree", "min_tree",
                 "pos", "f_epoch", "size", "max_priority")

_INNER_ENV = "APEX_FUSED_DP_INNER"


def _cfg(dp=4, n_envs=4, warmup=32):
    return ApexConfig(
        env=EnvConfig(env_id="ApexCatchSmall-v0", frame_stack=2,
                      clip_rewards=False, episodic_life=False),
        replay=ReplayConfig(capacity=512, warmup=warmup,
                            beta_anneal=2000),
        learner=LearnerConfig(batch_size=16, compute_dtype="float32",
                              target_update_interval=50,
                              publish_interval=5, mesh_shape=(dp,)),
        actor=ActorConfig(n_actors=1, n_envs_per_actor=n_envs,
                          send_interval=8))


def _run_fused_dp(steps_per_dispatch, dispatches, dp=4, train_ratio=None):
    t = FusedApexTrainer(_cfg(dp=dp), rollout_len=8,
                         steps_per_dispatch=steps_per_dispatch,
                         train_ratio=train_ratio)
    for _ in range(dispatches):
        t.train_state, t.replay_state, t.key, info = t.fused.dispatch(
            t.train_state, t.replay_state, t.key)
    return t


# -- fused dp=N vs serial dp=N (acceptance pin, subprocess) -----------------

@pytest.mark.skipif(os.environ.get(_INNER_ENV) != "1",
                    reason="spawned by test_fused_dp4_vs_serial_bit_"
                           "parity on a 4-device mesh")
def test_fused_dp4_parity_inner():
    """Inside the subprocess pytest: fused dp=4 scan composition is pure
    dispatch amortization — same macro body, same pre-split fan-out key
    chains — so 3x2 and 1x6 give bit-identical everything."""
    assert jax.device_count() == 4

    a = _run_fused_dp(3, 2)
    b = _run_fused_dp(1, 6)

    pa = jax.tree.leaves(jax.device_get(
        (a.train_state.params, a.train_state.opt_state)))
    pb = jax.tree.leaves(jax.device_get(
        (b.train_state.params, b.train_state.opt_state)))
    assert pa and all(np.array_equal(np.asarray(x), np.asarray(y))
                      for x, y in zip(pa, pb))
    assert int(a.train_state.step) == int(b.train_state.step) > 0

    # per-shard replay trees: leading axis = the 4 pool partitions
    ra = jax.device_get(a.replay_state)
    rb = jax.device_get(b.replay_state)
    for name in REPLAY_FIELDS:
        va = np.asarray(getattr(ra, name))
        vb = np.asarray(getattr(rb, name))
        assert va.shape[0] == 4, f"replay field {name} lost its shard axis"
        assert np.array_equal(va, vb), f"replay field {name} diverged"
    # every chip's partition actually ingested
    assert (np.asarray(jax.device_get(a.replay_state.size)) > 0).all()

    # both host key chains advanced with the serial split discipline
    assert np.array_equal(
        np.asarray(jax.random.key_data(a.key)),
        np.asarray(jax.random.key_data(b.key)))
    assert np.array_equal(
        np.asarray(jax.random.key_data(a.fused.engine.key)),
        np.asarray(jax.random.key_data(b.fused.engine.key)))
    assert int(a.fused.ingested_dev) == int(b.fused.ingested_dev) > 0
    assert a.fused.train_steps == b.fused.train_steps > 0
    assert a.fused.prio_writebacks == b.fused.prio_writebacks > 0


def test_fused_dp4_vs_serial_bit_parity():
    """Acceptance pin, tier-1-safe: spawn the inner parity test in a
    fresh pytest on a CPU backend forced to exactly 4 devices (the
    emulation geometry the issue names)."""
    env = dict(os.environ)
    env[_INNER_ENV] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("PYTEST_CURRENT_TEST", None)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.abspath(__file__), "-q",
         "-k", "test_fused_dp4_parity_inner", "-p", "no:cacheprovider"],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    # rc 0 = collected AND passed (empty collection exits 5, failure 1)
    assert proc.returncode == 0, \
        f"inner fused dp=4 parity pytest failed:\n" \
        f"{proc.stdout}\n{proc.stderr}"


# -- live train_ratio (device budget) ---------------------------------------

def test_fused_train_ratio_throttles_on_device():
    """ratio=0.2 with batch=16 (well under this geometry's structural
    rate): steps stay within one step of ``ingested * ratio / batch``
    (the budget gate closes the moment consumption catches the accrual),
    and the unthrottled twin trains strictly more."""
    throttled = _run_fused_dp(2, 8, dp=2, train_ratio=0.2)
    free = _run_fused_dp(2, 8, dp=2)
    ing = throttled.fused.transitions
    cap = ing * 0.2 / 16
    assert throttled.fused.train_steps <= cap + 1
    assert throttled.fused.train_steps > 0
    assert free.fused.train_steps > throttled.fused.train_steps
    # the budget ledger is exact f32 arithmetic off the psum'd ingest
    assert float(throttled.fused.budget_dev) == pytest.approx(
        ing * 0.2 - throttled.fused.train_steps * 16)
    # no-ratio runs never touch the budget scalar
    assert float(free.fused.budget_dev) == 0.0


def test_fused_dp_counters_and_summary_shards():
    t = _run_fused_dp(2, 3, dp=2)
    c = t.fused.counters()
    assert c["dp"] == 2
    assert c["train_steps"] > 0 and c["prio_writebacks"] > 0
    sizes = np.asarray(jax.device_get(t.replay_state.size)).reshape(-1)
    assert sizes.shape == (2,) and (sizes > 0).all()


# -- replay service under dp>1 (PR 7 guard removal) -------------------------

class _StubPool:
    """No-chunk pool: the trainer must train on SERVICE batches alone."""

    procs: list = []

    def start(self):
        pass

    def cleanup(self):
        pass

    def poll_chunks(self, n, timeout=0.0):
        if timeout:
            time.sleep(min(timeout, 0.005))
        return []

    def poll_stats(self):
        return []

    def publish_params(self, version, params):
        pass


class _StubClient:
    """Serves pre-fabricated batches with the client's interface; records
    the write-backs the trainer routes back."""

    def __init__(self, batches):
        self._lock = threading.Lock()
        self._batches = list(batches)
        self.n_shards = 2
        self.batches = 0
        self.prio = []                   # (shard, seq) routed back
        self.rejected = self.prio_sent = self.prio_dropped = 0
        self.learner_epoch = 0

    def poll_batch(self, timeout=0.0):
        with self._lock:
            if not self._batches:
                return None
            self.batches += 1
            return self._batches.pop(0)

    def push_priorities(self, shard, seq, idx, priorities):
        assert np.asarray(priorities).dtype == np.float32
        assert np.asarray(priorities).shape == np.asarray(idx).shape
        with self._lock:
            self.prio.append((int(shard), int(seq)))
            self.prio_sent += 1
        return True

    def ingested_total(self):
        return 4096                      # "the shard fleet is warm"

    def shard_status(self):
        return []

    def close(self):
        pass


BATCH = 16


def _service_batches(cfg, count):
    from apex_tpu.training.apex import dqn_env_specs
    _, frame_shape, frame_dtype, frame_stack = dqn_env_specs(cfg)
    stacked = frame_shape[:-1] + (frame_stack * frame_shape[-1],)
    rng = np.random.default_rng(0)

    def obs():
        if np.dtype(frame_dtype) == np.uint8:
            return rng.integers(0, 255, (BATCH,) + stacked, np.uint8)
        return rng.normal(size=(BATCH,) + stacked).astype(frame_dtype)

    return [{
        "batch": {
            "obs": obs(),
            "action": rng.integers(0, 2, BATCH).astype(np.int32),
            "reward": rng.normal(size=BATCH).astype(np.float32),
            "next_obs": obs(),
            "discount": np.full(BATCH, 0.97, np.float32),
        },
        "weights": np.ones(BATCH, np.float32),
        "idx": rng.integers(0, 256, BATCH).astype(np.int32),
        "seq": i // 2, "shard": i % 2, "ingested": 2048,
    } for i in range(count)]


def _service_cfg():
    cfg = small_test_config(capacity=256, batch_size=BATCH)
    return cfg.replace(learner=dataclasses.replace(
        cfg.learner, mesh_shape=(2,)))


def test_service_batches_train_on_dp2_mesh():
    """The PR 7 refusal is gone: a dp=2 learner trains on shard-served
    batches through the shard_map'd batch-train (pmean'd update,
    priorities reassembled in sample order) and routes every write-back
    to its owning shard."""
    from apex_tpu.training.apex import ApexTrainer

    cfg = _service_cfg()
    client = _StubClient(_service_batches(cfg, 4))
    trainer = ApexTrainer(cfg, pool=_StubPool(), respawn_workers=False)
    assert trainer.n_dp == 2
    trainer.replay_client = client
    p_before = np.asarray(jax.device_get(
        jax.tree.leaves(trainer.train_state.params)[0])).copy()
    trainer.train(total_steps=4, max_seconds=120, log_every=10 ** 9)

    assert trainer.service_steps == 4
    assert sorted(client.prio) == [(0, 0), (0, 1), (1, 0), (1, 1)]
    p_after = np.asarray(jax.device_get(
        jax.tree.leaves(trainer.train_state.params)[0]))
    assert not np.array_equal(p_before, p_after)
    svc = trainer.fleet_summary()["metrics"]["replay_service"]
    assert svc["service_steps"] == 4 and svc["batches_pulled"] == 4


def test_tenant_partition_trains_on_dp2_mesh(monkeypatch):
    """The PR 13 refusal fell with the service guard: a tenant-qualified
    learner (APEX_TENANT) pulls its partition's batches and trains on
    the dp=2 mesh like any other service learner."""
    from apex_tpu.tenancy import namespace
    from apex_tpu.training.apex import ApexTrainer

    monkeypatch.setenv("APEX_TENANT", "rally")
    assert namespace.current_tenant() == "rally"
    cfg = _service_cfg()
    client = _StubClient(_service_batches(cfg, 2))
    trainer = ApexTrainer(cfg, pool=_StubPool(), respawn_workers=False)
    trainer.replay_client = client
    trainer.train(total_steps=2, max_seconds=120, log_every=10 ** 9)
    assert trainer.service_steps == 2
    assert client.prio_sent == 2


def test_batch_train_priorities_are_per_chip_blocks():
    """idx-alignment pin: the dp=2 shard_map'd batch-train reassembles
    ``[batch]`` as contiguous per-chip blocks, and each block equals the
    single-chip update body run on that half alone (priorities blend a
    per-BATCH max — ``mixed_max_priorities`` — so the per-chip
    normalizer is the established ShardedLearner semantics, not a
    global one)."""
    from apex_tpu.training.apex import ApexTrainer

    item = _service_batches(_service_cfg(), 1)[0]
    cfg = _service_cfg()
    tr = ApexTrainer(cfg, pool=_StubPool(), respawn_workers=False)
    fn = tr._make_batch_train()
    ts, prios, metrics = fn(tr.train_state, item["batch"],
                            item["weights"])
    p2 = np.asarray(jax.device_get(prios))
    assert p2.shape == (BATCH,)
    assert np.isfinite(float(metrics["loss"]))

    # reference: the plain update body on each contiguous half
    half = BATCH // 2
    ref = ApexTrainer(small_test_config(capacity=256, batch_size=BATCH),
                      pool=_StubPool(), respawn_workers=False)
    step = jax.jit(ref.core.update_from_batch)
    for c in range(2):
        sl = slice(c * half, (c + 1) * half)
        hb = {k: v[sl] for k, v in item["batch"].items()}
        _, p_half, _ = step(ref.train_state, hb, item["weights"][sl])
        np.testing.assert_allclose(p2[sl], np.asarray(p_half),
                                   rtol=1e-5)
