"""Async ingest pipeline: merge bit-parity, end-to-end loop bit-parity,
order preservation, and bounded-ring backpressure
(``apex_tpu/training/ingest_pipeline.py``)."""

import copy
import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from apex_tpu.actors.pool import drain_builder_chunks
from apex_tpu.config import small_test_config
from apex_tpu.replay.frame_chunks import FrameChunkBuilder
from apex_tpu.replay.frame_pool import FramePoolReplay
from apex_tpu.training.ingest_pipeline import (IngestPipeline, PipelineState,
                                               is_frame_chunk,
                                               merge_chunk_messages)

# -- chunk stream fixtures --------------------------------------------------

FRAME_SHAPE = (3,)
STACK = 2
K = 8          # transitions per chunk
N_STEPS = 2


def _random_chunk_messages(seed: int, n_chunks: int,
                           frame_shape=FRAME_SHAPE, stack=STACK,
                           k=K, extra_shapes=None) -> list[dict]:
    """Drive a real FrameChunkBuilder through random episodes until it has
    emitted ``n_chunks`` fixed-shape chunks — the exact payloads actor
    workers ship."""
    rng = np.random.default_rng(seed)
    builder = FrameChunkBuilder(N_STEPS, 0.9, stack, frame_shape,
                                chunk_transitions=k, frame_margin=4,
                                frame_dtype=np.uint8,
                                extra_shapes=extra_shapes)
    msgs: list[dict] = []
    while len(msgs) < n_chunks:
        builder.begin_episode(rng.integers(0, 255, frame_shape))
        ep_len = int(rng.integers(1, 3 * k))
        for t in range(ep_len):
            extras = None
            if extra_shapes:
                extras = {name: rng.normal(size=shape).astype(np.float32)
                          for name, shape in extra_shapes.items()}
            builder.add_step(int(rng.integers(0, 4)),
                             float(rng.normal()),
                             rng.normal(size=4).astype(np.float32),
                             rng.integers(0, 255, frame_shape),
                             terminated=t == ep_len - 1, truncated=False,
                             extras=extras)
        msgs.extend(drain_builder_chunks(builder))
    return msgs[:n_chunks]


def _pool_spec(extra_spec=()):
    return FramePoolReplay(capacity=64, frame_shape=FRAME_SHAPE,
                           frame_stack=STACK, frame_capacity=128,
                           frame_dtype="uint8", extra_spec=extra_spec)


def _assert_states_identical(a, b):
    for name in ("frames", "action", "reward", "discount", "obs_ids",
                 "next_ids", "frame_epoch", "sum_tree", "min_tree",
                 "pos", "f_epoch", "size", "max_priority"):
        va, vb = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        assert np.array_equal(va, vb), f"state field {name} diverged"
    for key in a.extras:
        assert np.array_equal(np.asarray(a.extras[key]),
                              np.asarray(b.extras[key])), \
            f"extras[{key}] diverged"


# -- merge bit-parity (the property the whole pipeline rests on) ------------

@pytest.mark.parametrize("m", [2, 3, 5, 8])
def test_merged_ingest_bit_identical_to_sequential(m):
    """add(merge(c1..cm)) == add(c1); ...; add(cm) on EVERY state field:
    frames, id tables, trees, per-transition frame epochs, cursors."""
    msgs = _random_chunk_messages(seed=m, n_chunks=m)
    pool = _pool_spec()

    seq = pool.init()
    for msg in msgs:
        seq = pool.add(seq, msg["payload"],
                       np.asarray(msg["priorities"], np.float32))

    merged = merge_chunk_messages(copy.deepcopy(msgs))
    assert merged["n_trans"] == sum(int(x["n_trans"]) for x in msgs)
    one = pool.add(pool.init(), merged["payload"],
                   np.asarray(merged["priorities"], np.float32))

    _assert_states_identical(seq, one)


def test_merged_ingest_bit_identical_with_extras_and_wraparound():
    """Extras sidecars merge per-name, and parity survives the frame ring
    wrapping (chunks straddling the f_capacity boundary)."""
    extra_shapes = {"a_mu": (5,)}
    msgs = _random_chunk_messages(seed=7, n_chunks=30,
                                  extra_shapes=extra_shapes)
    pool = _pool_spec(extra_spec=(("a_mu", (5,)),))

    seq = pool.init()
    one = pool.init()
    # interleave merged widths over a long stream so cursors wrap
    i = 0
    widths = [3, 1, 4, 2, 5]
    w = 0
    while i < len(msgs):
        take = msgs[i:i + widths[w % len(widths)]]
        w += 1
        i += len(take)
        for msg in take:
            seq = pool.add(seq, msg["payload"],
                           np.asarray(msg["priorities"], np.float32))
        merged = merge_chunk_messages(copy.deepcopy(take))
        one = pool.add(one, merged["payload"],
                       np.asarray(merged["priorities"], np.float32))
    assert int(seq.f_epoch) > pool.f_capacity, "stream too short to wrap"
    _assert_states_identical(seq, one)


def test_merge_is_schema_gated():
    assert is_frame_chunk(_random_chunk_messages(1, 1)[0]["payload"])
    assert not is_frame_chunk({"obs": 1, "action": 2})
    assert not is_frame_chunk([1, 2])
    with pytest.raises(ValueError, match="uniform"):
        a = _random_chunk_messages(1, 1)[0]
        b = _random_chunk_messages(2, 1, k=4)[0]
        merge_chunk_messages([a, b])


# -- pipeline mechanics: scripted pool --------------------------------------

class ScriptedPool:
    """Deterministic in-process chunk source with the pool interface the
    trainer drives; counts polls so backpressure is observable."""

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


def test_pipeline_backpressures_when_behind_and_bounds_the_ring():
    """The replay-ratio floor pauses draining entirely; without the floor
    the bounded ring caps how much the pipeline will buffer ahead of the
    learner — it never drains the pool unboundedly."""
    msgs = _random_chunk_messages(seed=3, n_chunks=64)
    pool = ScriptedPool(msgs)
    state = {"behind": True}
    pipe = IngestPipeline(
        pool, depth=2, scan_steps=1, merge_max=4,
        state_fn=lambda: PipelineState(behind=state["behind"],
                                       train_eligible=False),
        capacity=1 << 20, frame_capacity=1 << 20)
    pipe.start()
    try:
        time.sleep(0.3)
        assert pool.polled == 0, "behind-learner must pause draining"

        state["behind"] = False          # floor released, but no consumer:
        time.sleep(0.5)                  # the depth-2 ring must backpressure
        # at most: depth slots of merge_max chunks + one group in flight
        bound = (2 + 1) * 4
        assert 0 < pool.polled <= bound, \
            f"ring buffered {pool.polled} chunks > bound {bound}"
        assert len(msgs) - pool.polled > 0, "pool fully drained: unbounded"

        # draining the ring lets staging make progress — order preserved
        seen = []
        for _ in range(100):
            slot = pipe.poll_slot(timeout=0.2)
            if slot is None:
                break
            seen.append(slot)
        assert sum(s.n_trans for s in seen) \
            == sum(int(m["n_trans"]) for m in msgs)
    finally:
        pipe.stop()


def test_pipeline_publish_rides_staging_thread():
    pool = ScriptedPool([])
    pipe = IngestPipeline(pool, state_fn=lambda: PipelineState())
    pipe.start()
    try:
        pipe.publish(3, {"w": jax.numpy.ones(4)})
        deadline = time.monotonic() + 2.0
        while not pool.published and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pool.published == [3]
    finally:
        pipe.stop()


def test_pipeline_staging_error_surfaces_to_consumer():
    class ExplodingPool(ScriptedPool):
        def poll_chunks(self, max_chunks, timeout=0.0):
            raise RuntimeError("decode blew up")

    pipe = IngestPipeline(ExplodingPool([]),
                          state_fn=lambda: PipelineState())
    pipe.start()
    try:
        with pytest.raises(RuntimeError, match="staging thread died"):
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline:
                pipe.poll_slot(timeout=0.05)
    finally:
        pipe.stop()


# -- the hand-off: a dry pipeline says so at once, a held chunk is waited for --

class TransportPool(ScriptedPool):
    """A host-fed pool's transport: ``timeout=0`` answers at once, a timed
    poll waits for a delivery as long as it was given (``ActorPool``'s
    chunk queue).  Notes, for each timed wait, whether the pipeline was
    idle when it began."""

    def __init__(self, msgs=()):
        super().__init__([])
        self._arrived = threading.Event()
        self._todo = list(msgs)
        self.pipe = None
        self.idle_at_timed_wait = []

    def deliver(self, n=1):
        self._msgs.extend(self._todo[:n])
        del self._todo[:n]
        self._arrived.set()

    def poll_chunks(self, max_chunks, timeout=0.0):
        if timeout and not self._msgs:
            self.idle_at_timed_wait.append(self.pipe._idle.is_set())
            self._arrived.wait(timeout)
        self._arrived.clear()
        return super().poll_chunks(max_chunks)


class ProducingPool(ScriptedPool):
    """``AnakinPool``'s stand-in: the poll itself produces the chunk and
    blocks meanwhile, whatever ``timeout`` says (there a rollout on the
    device, here a semaphore the test releases)."""

    def __init__(self, msgs):
        super().__init__(msgs)
        self.go = threading.Semaphore(0)
        self.producing = threading.Event()

    def poll_chunks(self, max_chunks, timeout=0.0):
        self.producing.set()
        try:
            if not self.go.acquire(timeout=30.0):
                return []
        finally:
            self.producing.clear()
        return super().poll_chunks(1)


def _transport_pipeline(pool, state_fn=PipelineState):
    pipe = IngestPipeline(pool, state_fn=state_fn)
    pool.pipe = pipe
    return pipe


def _wait_until(predicate, seconds=5.0):
    deadline = time.monotonic() + seconds
    while not predicate() and time.monotonic() < deadline:
        time.sleep(0.001)
    assert predicate()


def test_dry_pipeline_answers_none_without_a_timed_get():
    """Nothing staged, nothing in hand, nothing sent: ``poll_slot(0)`` is
    told so at once — the ring is only ever tried without a wait."""
    pool = TransportPool()
    pipe = _transport_pipeline(pool)
    gets = []
    ring_get = pipe._ring.get

    def get(block=True, timeout=None):
        gets.append((block, timeout))
        return ring_get(block, timeout)
    pipe._ring.get = get
    pipe.start()
    try:
        _wait_until(lambda: len(pool.idle_at_timed_wait) >= 2)
        for _ in range(50):
            assert pipe.poll_slot(timeout=0) is None
        assert gets and all(block is False for block, _t in gets)
        assert pipe.stats["dry_polls"] == 50
        assert pipe.stats["waited_polls"] == 0
        assert pipe.stats["poll_wait_s"] == 0.0
    finally:
        pipe.stop()


def test_idle_through_the_transport_wait_and_clear_while_a_chunk_is_held():
    """``_idle`` is set through every timed wait on the empty transport
    and clear from a chunk's arrival (staged, put) until the thread waits
    again."""
    pool = TransportPool(_random_chunk_messages(seed=5, n_chunks=2))
    pipe = _transport_pipeline(pool)
    held = []
    stage, ring_put = pipe._stage, pipe._ring.put

    def staged(x):
        held.append(("stage", pipe._idle.is_set()))
        return stage(x)

    def put(slot, timeout=None):
        held.append(("put", pipe._idle.is_set()))
        return ring_put(slot, timeout=timeout)
    pipe._stage, pipe._ring.put = staged, put
    pipe.start()
    try:
        for _ in range(2):
            waits = len(pool.idle_at_timed_wait)
            _wait_until(lambda: len(pool.idle_at_timed_wait) > waits)
            pool.deliver()                  # arrives inside a timed wait
            slot = pipe.poll_slot(timeout=5.0)
            assert slot is not None and slot.kind == "single"
            waits = len(pool.idle_at_timed_wait)
            _wait_until(lambda: len(pool.idle_at_timed_wait) > waits)
            assert pipe.poll_slot(timeout=0) is None
        assert pool.idle_at_timed_wait and all(pool.idle_at_timed_wait)
        assert {what for what, _idle in held} == {"stage", "put"}
        assert not any(idle for _what, idle in held)
    finally:
        pipe.stop()


@pytest.mark.parametrize("after_pause", [False, True],
                         ids=["after_a_put", "after_a_behind_pause"])
def test_a_producing_poll_keeps_the_flag_the_last_pass_left(after_pause):
    """A pool that produces inside its poll is polled with ``_idle``
    untouched.  After a slot was put and taken the loop is NOT told "dry"
    while the next poll produces: it waits, and gets the slot on the put.
    After a ``behind`` pause the flag is set, and the loop trains alone
    meanwhile (the on-device cell's replay ratio rests on both)."""
    pool = ProducingPool(_random_chunk_messages(seed=6, n_chunks=2))
    state = {"behind": False}
    pipe = IngestPipeline(
        pool, state_fn=lambda: PipelineState(behind=state["behind"]))
    pipe.start()
    try:
        pool.go.release()
        assert pipe.poll_slot(timeout=5.0) is not None
        if after_pause:
            state["behind"] = True
            pool.go.release()       # the poll already under way comes home
            assert pipe.poll_slot(timeout=5.0) is not None
            _wait_until(pipe._idle.is_set)
            state["behind"] = False
            pool._msgs = _random_chunk_messages(seed=8, n_chunks=1)
        _wait_until(pool.producing.is_set)

        if after_pause:
            before = pipe.stats["dry_polls"]
            assert pipe.poll_slot(timeout=0) is None
            assert pipe.stats["dry_polls"] == before + 1
            pool.go.release()
            assert pipe.poll_slot(timeout=5.0) is not None
            return
        got = []
        waiter = threading.Thread(
            target=lambda: got.append(pipe.poll_slot(timeout=0)))
        waiter.start()
        waiter.join(timeout=0.3)
        assert waiter.is_alive() and not got, \
            "told dry while the poll produced"
        pool.go.release()
        waiter.join(timeout=5.0)
        assert not waiter.is_alive()
        assert got and got[0] is not None
        assert pipe.stats["waited_polls"] >= 1
        assert pipe.stats["poll_wait_s"] > 0.0
        assert pipe.stats["dry_polls"] == 0
    finally:
        pool.go.release()
        pipe.stop()


def test_a_timeout_on_a_dry_pipeline_runs_to_its_deadline():
    pool = TransportPool()
    pipe = _transport_pipeline(pool)
    pipe.start()
    try:
        t0 = time.monotonic()
        assert pipe.poll_slot(timeout=0.2) is None
        took = time.monotonic() - t0
        assert 0.2 <= took < 2.0
        assert pipe.stats["waited_polls"] == 1
        assert pipe.stats["dry_polls"] == 0
        assert 0.1 < pipe.stats["poll_wait_s"] <= took
    finally:
        pipe.stop()


@pytest.mark.parametrize("timeout", [0, 30.0], ids=["nowait", "waiting"])
def test_a_staging_error_raises_from_poll_slot(timeout):
    """Dead before the call, ``poll_slot(0)`` raises where it would have
    said None; dying under a caller that waits wakes it."""
    fuse = threading.Event()

    class ExplodingPool(TransportPool):
        def poll_chunks(self, max_chunks, timeout=0.0):
            if fuse.is_set():
                raise RuntimeError("decode blew up")
            return super().poll_chunks(max_chunks, timeout)

    pool = ExplodingPool()
    pipe = _transport_pipeline(pool)
    pipe.start()
    try:
        if timeout:
            threading.Timer(0.1, fuse.set).start()
        else:
            fuse.set()
            _wait_until(lambda: pipe._error is not None)
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="staging thread died"):
            pipe.poll_slot(timeout=timeout)
        assert time.monotonic() - t0 < 10.0
    finally:
        pipe.stop()


# -- end-to-end: the loop's state is the plain fold of its own dispatches -----

def train_recorded(trainer, **train_kw) -> list:
    """``trainer.train(**train_kw)`` with its three programs wrapped; what
    it dispatched, ``[("ingest", 64), ("fused", 16), ("train", 0), ...]``:
    each dispatch's program and the transitions it consumed
    (``trainer.ingested`` from one dispatch to the next)."""
    log = []
    for kind in ("ingest", "fused", "train"):
        fn = getattr(trainer, "_" + kind)

        def program(*operands, _fn=fn, _kind=kind):
            log.append((_kind, trainer.ingested))
            return _fn(*operands)
        program.__name__ = fn.__name__      # _dispatch names its span by it
        setattr(trainer, "_" + kind, program)
    trainer.train(log_every=10 ** 9, **train_kw)
    marks = [at for _kind, at in log[1:]] + [trainer.ingested]
    return [(kind, after - at) for (kind, at), after in zip(log, marks)]


def fold_reference(trainer, sequence: list) -> None:
    """The plain reference: on a trainer built from the same seed over the
    same scripted messages (never ``train()``-ed: no staging thread, no
    merge, no key block), walk its pool's messages in order as ``sequence``
    says.  An ``ingest`` of n transitions folds its messages one by one
    through ``_ingest``; a ``fused`` takes one message (one round-robin
    group at dp>1) and a ``train`` none, each with the next key of the
    eager chain ``key, k = split(key)`` and ``_beta`` of what was ingested
    before it."""
    t = trainer

    def message(n_most: int) -> tuple:
        (msg,) = t.pool.poll_chunks(1)
        assert 0 < int(msg["n_trans"]) <= n_most
        return (msg["payload"], np.asarray(msg["priorities"], np.float32),
                int(msg["n_trans"]))

    for kind, n in sequence:
        if kind == "ingest":
            while n:
                payload, prios, took = message(n)
                t.replay_state = t._ingest(t.replay_state, payload, prios)
                t.ingested += took
                n -= took
            continue
        t.key, k = jax.random.split(t.key)
        beta = jax.numpy.float32(t._beta())
        if kind == "fused":
            payload, prios, took = message(n)
            assert took == n
            t.train_state, t.replay_state, _ = t._fused(
                t.train_state, t.replay_state, payload, prios, k, beta)
            t.ingested += took
        else:
            assert kind == "train" and n == 0
            t.train_state, t.replay_state, _ = t._train(
                t.train_state, t.replay_state, k, beta)
        t.steps_rate.tick()


def assert_same_learner(a, b, replay_shards: int | None = None) -> None:
    """Train state (params, target, optimizer, step), every replay field,
    the counters and the key chain of two trainers, bit for bit."""
    flat_a = jax.tree_util.tree_leaves_with_path(
        jax.device_get(a.train_state))
    flat_b = dict(jax.tree_util.tree_leaves_with_path(
        jax.device_get(b.train_state)))
    assert flat_a and len(flat_a) == len(flat_b)
    for path, leaf in flat_a:
        assert np.array_equal(np.asarray(leaf), np.asarray(flat_b[path])), \
            f"train state diverged at {jax.tree_util.keystr(path)}"
    if replay_shards is not None:
        assert np.asarray(a.replay_state.pos).shape[0] == replay_shards
    _assert_states_identical(a.replay_state, b.replay_state)
    assert a.ingested == b.ingested
    assert a.steps_rate.total == b.steps_rate.total
    assert np.array_equal(np.asarray(jax.random.key_data(a.key)),
                          np.asarray(jax.random.key_data(b.key)))


class PacedPool(ScriptedPool):
    """A fleet slower than the learner: a chunk no sooner than 20 ms after
    the one before, a timed poll waiting as long as it was given."""

    every = 0.02

    def __init__(self, msgs):
        super().__init__(msgs)
        self._last = 0.0

    def poll_chunks(self, max_chunks, timeout=0.0):
        due = self._last + self.every - time.monotonic()
        if due > 0:
            if due > timeout:
                time.sleep(timeout)
                return []
            time.sleep(due)
        out = super().poll_chunks(1)
        if out:
            self._last = time.monotonic()
        return out


def _parity_trainer(msgs, train_ratio=None, pool_cls=ScriptedPool):
    from apex_tpu.training.apex import ApexTrainer

    cfg = small_test_config(capacity=256, batch_size=16, n_actors=1)
    cfg = cfg.replace(
        replay=dataclasses.replace(cfg.replay, warmup=64),
        learner=dataclasses.replace(cfg.learner,
                                    target_update_interval=20))
    return ApexTrainer(cfg, pool=pool_cls(copy.deepcopy(msgs)),
                       publish_min_seconds=10.0, respawn_workers=False,
                       train_ratio=train_ratio)


def _cartpole_chunk_messages(n_chunks: int) -> list[dict]:
    """Chunks matching small_test_config's ApexCartPole spec: (4,) float32
    frames, stack 1 — what ApexTrainer's replay expects."""
    rng = np.random.default_rng(0)
    builder = FrameChunkBuilder(3, 0.99, 1, (4,), chunk_transitions=16,
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


@pytest.mark.parametrize("train_ratio,total_steps", [(None, 40), (0.5, 10)],
                         ids=["uncapped", "ratio_capped"])
def test_loop_state_is_the_fold_of_its_own_dispatches(train_ratio,
                                                      total_steps):
    """The acceptance pin: whatever sequence of dispatches the loop chose
    for a deterministic chunk stream, its state is the plain fold of the
    same programs over that sequence — merged ingests equal sequential
    ones, keys are the eager chain, beta follows ingestion, order is kept.
    The stream crosses the warmup boundary (merged warmup ingest, staged
    fused singles); uncapped it ends in replay-only steps, and under a
    replay-ratio cap (12 steps for 384 transitions) chunks the cap turns
    away after warmup are absorbed ingest-only."""
    msgs = _cartpole_chunk_messages(24)      # 24 * 16 = 384 transitions
    loop = _parity_trainer(msgs, train_ratio)
    sequence = train_recorded(loop, total_steps=total_steps, max_seconds=120)

    assert loop.steps_rate.total == total_steps
    kinds = [kind for kind, _n in sequence]
    assert {"ingest", "fused"} <= set(kinds)
    if train_ratio is None:
        assert loop.ingested == 384 and "train" in kinds    # replay-only tail
    else:
        assert "ingest" in kinds[kinds.index("fused"):], \
            "the cap never turned a warm chunk away"
    # the run staged slots and merged its warmup fill
    stats = loop._pipeline_last_stats
    assert stats is not None and stats["slots"] > 0
    assert stats["merged_chunks"] >= 2, \
        "warmup fill never exercised the merged-ingest path"
    assert max(n for kind, n in sequence if kind == "ingest") > 16

    reference = _parity_trainer(msgs, train_ratio)
    fold_reference(reference, sequence)
    assert_same_learner(loop, reference)


def test_loop_trains_alone_between_the_chunks_of_a_slow_fleet():
    """A chunk every 20 ms and a warm loop that needs a tenth of that a
    pass: told "dry" at once, it trains alone between one chunk and the
    next (``f t t .. f``), and its state is still the plain fold of the
    sequence it chose."""
    msgs = _cartpole_chunk_messages(24)
    loop = _parity_trainer(msgs, pool_cls=PacedPool)
    sequence = train_recorded(loop, total_steps=150, max_seconds=120)

    assert loop.steps_rate.total == 150
    kinds = [kind for kind, _n in sequence]
    fused = [i for i, kind in enumerate(kinds) if kind == "fused"]
    assert len(fused) >= 2
    assert "train" in kinds[fused[0]:fused[-1]], \
        "never trained alone between two chunks"
    stats = loop._pipeline_last_stats
    assert stats["dry_polls"] > 0

    reference = _parity_trainer(msgs)
    fold_reference(reference, sequence)
    assert_same_learner(loop, reference)
