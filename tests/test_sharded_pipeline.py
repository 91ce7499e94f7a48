"""Sharded (dp>1) ingest pipeline: per-shard group-merge bit-parity, the
key-prefetcher chain contract, group-granular staging mechanics, and the
acceptance pin — the dp=4 loop's train state AND per-shard replay state
equal the plain fold of its own dispatch sequence over the same chunk
stream, in a subprocess-spawned pytest on a
``--xla_force_host_platform_device_count=4`` CPU mesh
(``apex_tpu/training/ingest_pipeline.py`` sharded mode)."""

import copy
import dataclasses
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from apex_tpu.actors.pool import drain_builder_chunks
from apex_tpu.config import small_test_config
from apex_tpu.parallel.aggregate import ChunkAggregator, stack_chunk_messages
from apex_tpu.replay.frame_chunks import FrameChunkBuilder
from apex_tpu.replay.frame_pool import FramePoolReplay
from apex_tpu.training.ingest_pipeline import (IngestPipeline, KeyPrefetcher,
                                               PipelineState,
                                               merge_group_messages)

# -- fixtures ---------------------------------------------------------------

K = 16          # transitions per worker chunk


def _cartpole_chunk_messages(n_chunks: int, seed: int = 0) -> list[dict]:
    """Chunks matching small_test_config's ApexCartPole spec — the exact
    payloads actor workers ship (same builder as tests/test_ingest_pipeline)."""
    rng = np.random.default_rng(seed)
    builder = FrameChunkBuilder(3, 0.99, 1, (4,), chunk_transitions=K,
                                frame_dtype=np.float32)
    msgs: list[dict] = []
    while len(msgs) < n_chunks:
        builder.begin_episode(rng.normal(size=4).astype(np.float32))
        ep_len = int(rng.integers(4, 40))
        for t in range(ep_len):
            builder.add_step(int(rng.integers(0, 2)), float(rng.normal()),
                             rng.normal(size=2).astype(np.float32),
                             rng.normal(size=4).astype(np.float32),
                             terminated=t == ep_len - 1, truncated=False)
        msgs.extend(drain_builder_chunks(builder))
    return msgs[:n_chunks]


def _group(msgs: list[dict]) -> dict:
    """One round-robin group message, exactly as ChunkAggregator stacks it."""
    payload, prios, n_trans = stack_chunk_messages(msgs)
    return {"payload": payload, "priorities": prios, "n_trans": n_trans}


class ScriptedPool:
    """Deterministic in-process chunk source with the pool interface."""

    def __init__(self, msgs):
        self._msgs = list(msgs)
        self.procs = []
        self.polled = 0
        self.published = []

    def start(self):
        pass

    def cleanup(self):
        pass

    def publish_params(self, version, params):
        self.published.append(version)

    def poll_stats(self):
        return []

    def poll_chunks(self, max_chunks, timeout=0.0):
        out = []
        while self._msgs and len(out) < max_chunks:
            out.append(self._msgs.pop(0))
        self.polled += len(out)
        return out


def _mini_sharded(n_dp: int):
    """A ShardedLearner stand-in exposing only what the pipeline's
    sharded mode touches host-side (n_dp; shard_put stays unused on the
    CPU backend, where put_device defaults off)."""
    from apex_tpu.parallel.learner import ShardedLearner
    from apex_tpu.parallel.mesh import make_mesh

    sl = ShardedLearner.__new__(ShardedLearner)
    object.__setattr__(sl, "core", None)
    object.__setattr__(sl, "mesh", make_mesh(dp=n_dp,
                                             devices=jax.devices()[:n_dp]))
    return sl


# -- per-shard group-merge bit-parity ---------------------------------------

@pytest.mark.parametrize("n_dp,m", [(2, 2), (4, 3), (4, 8)])
def test_merge_group_messages_bit_identical_per_shard(n_dp, m):
    """add(group_merge(g1..gm)) == add(g1); ...; add(gm) on EVERY state
    field of EVERY shard — through the real frame pool, so ref rebasing
    and epoch_off carry exactly as the single-shard merge contract."""
    msgs = _cartpole_chunk_messages(n_dp * m, seed=n_dp * 10 + m)
    groups = [_group(msgs[i * n_dp:(i + 1) * n_dp]) for i in range(m)]
    pool = FramePoolReplay(capacity=256, frame_shape=(4,), frame_stack=1,
                           frame_capacity=512, frame_dtype="float32")

    merged = merge_group_messages(copy.deepcopy(groups), n_dp)
    assert merged["n_trans"] == sum(g["n_trans"] for g in groups)

    for s in range(n_dp):
        seq = pool.init()
        for g in groups:
            seq = pool.add(
                seq, jax.tree.map(lambda x: x[s], g["payload"]),
                np.asarray(g["priorities"][s], np.float32))
        one = pool.add(
            pool.init(), jax.tree.map(lambda x: x[s], merged["payload"]),
            np.asarray(merged["priorities"][s], np.float32))
        for name in ("frames", "action", "reward", "discount", "obs_ids",
                     "next_ids", "frame_epoch", "sum_tree", "min_tree",
                     "pos", "f_epoch", "size", "max_priority"):
            va = np.asarray(getattr(seq, name))
            vb = np.asarray(getattr(one, name))
            assert np.array_equal(va, vb), \
                f"shard {s} state field {name} diverged"


def test_merge_group_messages_single_group_passthrough():
    g = _group(_cartpole_chunk_messages(4))
    assert merge_group_messages([g], 4) is g


# -- key prefetcher: the chain contract -------------------------------------

def test_key_prefetcher_matches_serial_split_chain():
    """take() i must yield EXACTLY device_keys(k_i) of the serial chain
    ``chain, k_i = split(chain)``, plus the chain state the inline split
    would have left behind — pipelined dispatch keys and post-train
    ``self.key`` both reduce to the serial sequence."""
    sl = _mini_sharded(4)
    seed = jax.random.key(42)
    pre = KeyPrefetcher(sl, seed, depth=3)
    pre.refill()

    chain = seed
    for i in range(7):              # crosses a refill boundary
        placed, after = pre.take()
        chain, k = jax.random.split(chain)
        np.testing.assert_array_equal(np.asarray(placed),
                                      np.asarray(sl.device_keys(k)))
        np.testing.assert_array_equal(np.asarray(jax.random.key_data(after)),
                                      np.asarray(jax.random.key_data(chain)))
        if i == 3:
            pre.refill()


# -- sharded staging mechanics ----------------------------------------------

def test_sharded_pipeline_groups_merge_and_preserve_order():
    """Through a real ChunkAggregator: ingest-only groups merge
    group-granular (dp axis intact, per-shard widths pow2-quantized),
    stream order is preserved, and totals balance."""
    n_dp = 4
    msgs = _cartpole_chunk_messages(n_dp * 8, seed=3)
    total = sum(int(m["n_trans"]) for m in msgs)
    pool = ChunkAggregator(ScriptedPool(msgs), n_dp)
    pipe = IngestPipeline(
        pool, depth=2, merge_max=4,
        state_fn=lambda: PipelineState(train_eligible=False),
        capacity=1 << 20, frame_capacity=1 << 20,
        sharded=_mini_sharded(n_dp))
    assert pipe.scan_steps == 1      # no scan stacking on the sharded plan
    pipe.start()
    try:
        slots = []
        for _ in range(40):
            slot = pipe.poll_slot(timeout=0.5)
            if slot is None:
                break
            slots.append(slot)
    finally:
        pipe.stop()
    assert sum(s.n_trans for s in slots) == total
    assert any(s.kind == "merged" for s in slots)
    for s in slots:
        # every slot keeps the dp axis in front, whatever its width
        assert np.asarray(s.payload["action"]).shape[0] == n_dp
        assert np.asarray(s.prios).shape[0] == n_dp
    # order: the concatenated per-shard action stream must equal the
    # source chunks round-robin-assigned in poll order
    for shard in range(n_dp):
        got = np.concatenate([
            np.asarray(s.payload["action"])[shard].reshape(-1)
            for s in slots])
        want = np.concatenate([
            np.asarray(m["payload"]["action"])
            for i, m in enumerate(_cartpole_chunk_messages(n_dp * 8, seed=3))
            if i % n_dp == shard])
        np.testing.assert_array_equal(got[:want.size], want)


def test_sharded_pipeline_behind_pauses_draining():
    n_dp = 4
    raw = ScriptedPool(_cartpole_chunk_messages(n_dp * 4, seed=5))
    pipe = IngestPipeline(
        ChunkAggregator(raw, n_dp), depth=2,
        state_fn=lambda: PipelineState(behind=True, train_eligible=False),
        sharded=_mini_sharded(n_dp))
    pipe.start()
    try:
        time.sleep(0.3)
        assert raw.polled == 0, "behind-learner must pause draining"
    finally:
        pipe.stop()


# -- the acceptance pin: dp=4, the fold of the loop's own dispatches ---------

_INNER_ENV = "APEX_DP_PARITY_INNER"


def _dp_trainer(msgs):
    from apex_tpu.training.apex import ApexTrainer

    cfg = small_test_config(capacity=256, batch_size=16, n_actors=1)
    cfg = cfg.replace(
        replay=dataclasses.replace(cfg.replay, warmup=256),
        learner=dataclasses.replace(cfg.learner, mesh_shape=(4,),
                                    target_update_interval=20))
    return ApexTrainer(cfg, pool=ScriptedPool(copy.deepcopy(msgs)),
                       publish_min_seconds=10.0, respawn_workers=False)


@pytest.mark.skipif(os.environ.get(_INNER_ENV) != "1",
                    reason="spawned by test_dp4_loop_state_is_the_fold_of_"
                           "its_own_dispatches on a 4-device mesh")
def test_dp4_parity_inner():
    """Runs inside the subprocess pytest: whatever sequence of dispatches
    the dp=4 loop chose for a deterministic chunk stream, its train state,
    per-shard replay state, counters AND post-train key chain are the
    plain fold of the same sharded programs over that sequence
    (``tests/test_ingest_pipeline.fold_reference``: round-robin groups one
    by one, raw chain keys).  The stream crosses the warmup boundary
    (merged round-robin groups), continues through staged trainable groups,
    and ends in replay-only catch-up steps (prefetched keys past the
    data)."""
    from tests.test_ingest_pipeline import (assert_same_learner,
                                            fold_reference, train_recorded)
    assert jax.device_count() == 4

    msgs = _cartpole_chunk_messages(80)      # 20 groups of 4 x 16 trans
    n = 30                                   # > post-warm group count
    loop = _dp_trainer(msgs)
    sequence = train_recorded(loop, total_steps=n, max_seconds=300)

    assert loop.steps_rate.total == n and loop.ingested == 80 * K
    assert {"ingest", "fused", "train"} <= {kind for kind, _n in sequence}
    # the run staged slots and merged its warmup groups
    stats = loop._pipeline_last_stats
    assert stats is not None and stats["slots"] > 0
    assert stats["merged_chunks"] >= 2, \
        "warmup fill never exercised the sharded merged-group path"

    reference = _dp_trainer(msgs)
    fold_reference(reference, sequence)
    assert_same_learner(loop, reference, replay_shards=4)


def test_dp4_loop_state_is_the_fold_of_its_own_dispatches():
    """Acceptance pin, tier-1-safe: spawn the inner parity test in a
    fresh pytest on a CPU backend forced to exactly 4 devices — the
    sharded plan under the precise emulation geometry the issue names
    (XLA_FLAGS=--xla_force_host_platform_device_count=4)."""
    env = dict(os.environ)
    env[_INNER_ENV] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("PYTEST_CURRENT_TEST", None)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.abspath(__file__), "-q",
         "-k", "test_dp4_parity_inner", "-p", "no:cacheprovider"],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    # rc 0 = at least one test collected AND none failed (an empty
    # collection exits 5, a failure 1) — the inner run passed
    assert proc.returncode == 0, \
        f"inner dp=4 parity pytest failed:\n{proc.stdout}\n{proc.stderr}"
