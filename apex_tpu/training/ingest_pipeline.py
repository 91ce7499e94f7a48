"""Async ingest pipeline: overlap host decode, H2D staging, and compute.

A learner loop (:meth:`ConcurrentTrainer.train`) that does all of this on
ONE thread, in sequence, per step — poll the chunk queue (pickle / shm
decode), stack arrays with host numpy, hand host buffers to the jitted
step (whose H2D copy runs synchronously inside the dispatch), then poll
again — never overlaps host decode, H2D transfer, and device compute: the
exact decoupling failure Ape-X exists to avoid (Horgan et al. 2018), and
the standard fix is double-buffered staging (Stooke & Abbeel 2018,
PAPERS.md "Accelerated Methods for Deep RL").

This module runs a single background STAGING thread that:

* drains ``pool.poll_chunks`` (the decode cost — mp.Queue pickle or shm
  copy — moves off the hot loop with it);
* groups chunks by what the trainer will do with them, predicted from the
  live counters (``state_fn``):

  - train-eligible chunks -> a ``lax.scan`` stack of j chunks (one
    dispatch, j bit-identical fused steps), a shortfall of j <
    scan_steps chunks quantized to a power of two;
  - ingest-only chunks (warmup fill, replay-ratio cap) -> ONE merged
    payload via :func:`merge_chunk_messages` — m dispatches and m H2D
    copies become one, bit-identically (see below);

* ``jax.device_put``\\ s each staged slot so the next dispatch's data is
  already in HBM while the current fused step runs, into a bounded
  depth-``depth`` ring (default 2: classic double buffering).

Ordering / backpressure / numerics contract:

* Chunks enter slots strictly in poll order and the ring is FIFO — the
  replay sees the transition stream the pool delivered.
* The ring is BOUNDED and the staging thread polls nothing while it is
  full (or while the replay-ratio floor says the learner is behind), so
  the bounded worker chunk queue backpressures the actor fleet exactly as
  before; the pipeline can hold at most ``depth`` slots plus one group in
  flight.
* Merging is numerics-free: :func:`merge_chunk_messages` rebases the
  chunk-relative ``obs_ref``/``next_ref`` tables with cumulative frame
  offsets and carries per-transition ``epoch_off`` so one merged
  :meth:`FramePoolReplay.add` writes the SAME cells, priorities, and
  epochs as ingesting the chunks one by one — exploiting the
  duplicate-pad-write invariant (pads repeat the last real row, so they
  remain deterministic no-ops after merging).  Bit-parity is pinned in
  ``tests/test_ingest_pipeline.py``.

Param publishes also ride the staging thread: the trainer hands over an
on-device param copy and the thread performs the blocking
``jax.device_get`` + serialization that used to drain the whole device
pipeline from inside the hot loop (apexlint J006 now guards against that
pattern coming back).

Sharded (dp>1) plan: the same staging stage drives the multi-chip
learner.  ``ChunkAggregator`` already assembles whole ROUND-ROBIN groups
(``n_dp`` worker chunks stacked on a leading dp axis — chunk i of a group
lands on chip i), so each polled message is one group and the pipeline
stages group-granular slots:

* train-eligible groups stage as ``"single"`` slots whose payload is
  ``device_put`` with a ``NamedSharding`` over the dp axis (H2D lands
  each shard's slice on its chip ahead of the dispatch);
* ingest-only groups merge PER SHARD via :func:`merge_group_messages`:
  shard s's m chunks compact exactly as the single-shard merge does
  (frame refs rebased by cumulative real-frame offsets, ``epoch_off``
  carried), then the n_dp merged payloads restack on the dp axis —
  shards are independent replays, so bit-parity reduces to the
  single-shard merge contract per shard;
* per-chip PRNG keys are PRE-SPLIT and PRE-PLACED by a
  :class:`KeyPrefetcher` that owns the trainer's dispatch key chain: a
  raw chain key pays a split and a sharded ``device_put`` inside every
  dispatch (``ShardedLearner.device_keys``); the prefetcher generates the
  exact same chain ahead of time on the staging side.
"""

from __future__ import annotations

import queue as queue_lib
import threading
import time
from collections import deque
from dataclasses import dataclass

import jax
import numpy as np

from apex_tpu.obs import spans as obs_spans
from apex_tpu.obs.trace import get_ring

#: payload keys that identify a self-contained frame chunk
#: (replay/frame_chunks.py contract) — the only payload schema
#: merge_chunk_messages understands.  Everything else (stacked AQL
#: batches, R2D2 sequence messages) stages as single slots.
FRAME_CHUNK_KEYS = frozenset((
    "frames", "n_frames", "n_trans", "action", "reward", "discount",
    "obs_ref", "next_ref"))


def is_frame_chunk(payload) -> bool:
    return isinstance(payload, dict) and FRAME_CHUNK_KEYS <= payload.keys()


def merge_chunk_messages(msgs: list[dict]) -> dict:
    """Merge m frame-chunk messages into ONE ingest message.

    Real rows from every chunk are compacted front-to-back (frames and
    transitions separately), ``obs_ref``/``next_ref`` are rebased by each
    chunk's cumulative REAL frame offset, and ``epoch_off`` records that
    same offset per transition so the pool stamps sequential-identical
    frame epochs.  The tail pads by repeating the last real row —
    priorities included — preserving the duplicate-pad-write invariant.
    Output shapes are fixed per m (``[m*K]`` / ``[m*Kf, D]``), so each
    distinct merge width compiles exactly one ingest program.

    Bit-parity contract: ``add(merge(c1..cm))`` == ``add(c1); ...;
    add(cm)`` on every :class:`FramePoolState` field (frames, id tables,
    trees, epochs, cursors) — tests/test_ingest_pipeline.py.
    """
    if len(msgs) == 1:
        return msgs[0]
    payloads = [m["payload"] for m in msgs]
    k = payloads[0]["action"].shape[0]
    kf, d = payloads[0]["frames"].shape
    stack = payloads[0]["obs_ref"].shape[1]
    for p in payloads[1:]:
        if (p["action"].shape[0] != k or p["frames"].shape != (kf, d)
                or p["obs_ref"].shape[1] != stack):
            raise ValueError("merge_chunk_messages needs uniform chunk "
                             "shapes (one builder config per pool)")
    m = len(msgs)
    n_tr = [int(p["n_trans"]) for p in payloads]
    n_fr = [int(p["n_frames"]) for p in payloads]
    tot_tr, tot_fr = sum(n_tr), sum(n_fr)
    out_k, out_kf = m * k, m * kf
    # cumulative REAL frame offset of each source chunk — the ref rebase
    # and the per-transition epoch offsets both come from this
    cum_fr = np.concatenate(([0], np.cumsum(n_fr)[:-1])).astype(np.int64)

    frames = np.empty((out_kf, d), payloads[0]["frames"].dtype)
    off = 0
    for p, nf in zip(payloads, n_fr):
        frames[off:off + nf] = p["frames"][:nf]
        off += nf
    frames[tot_fr:] = frames[tot_fr - 1]

    def cat(rows: list[np.ndarray], dtype) -> np.ndarray:
        arr = np.concatenate(rows).astype(dtype, copy=False)
        out = np.empty((out_k,) + arr.shape[1:], dtype)
        out[:tot_tr] = arr
        out[tot_tr:] = arr[tot_tr - 1]
        return out

    payload = dict(
        frames=frames,
        n_frames=np.int32(tot_fr),
        n_trans=np.int32(tot_tr),
        action=cat([p["action"][:nt] for p, nt in zip(payloads, n_tr)],
                   np.int32),
        reward=cat([p["reward"][:nt] for p, nt in zip(payloads, n_tr)],
                   np.float32),
        discount=cat([p["discount"][:nt] for p, nt in zip(payloads, n_tr)],
                     np.float32),
        obs_ref=cat([p["obs_ref"][:nt] + c
                     for p, nt, c in zip(payloads, n_tr, cum_fr)], np.int32),
        next_ref=cat([p["next_ref"][:nt] + c
                      for p, nt, c in zip(payloads, n_tr, cum_fr)], np.int32),
        epoch_off=cat([np.full(nt, c)
                       for nt, c in zip(n_tr, cum_fr)], np.int32),
    )
    if "extras" in payloads[0]:
        payload["extras"] = {
            name: cat([p["extras"][name][:nt]
                       for p, nt in zip(payloads, n_tr)], np.float32)
            for name in payloads[0]["extras"]}
    prios = cat([np.asarray(msg["priorities"])[:nt]
                 for msg, nt in zip(msgs, n_tr)], np.float32)
    out = {"payload": payload, "priorities": prios, "n_trans": tot_tr}
    # lineage spans ride MESSAGE metadata, never the payload — the
    # bit-parity contract above compares payloads field for field and
    # must keep holding with stamping on (tests re-pin it)
    spans = obs_spans.merge_spans(msgs)
    if spans:
        out[obs_spans.SPAN_KEY] = spans
    return out


def merge_group_messages(msgs: list[dict], n_dp: int) -> dict:
    """Merge m stacked round-robin GROUP messages into ONE sharded ingest
    message.

    Each input message carries ``n_dp`` chunks on a leading dp axis
    (``ChunkAggregator``'s stacking).  Shard s receives chunk s of every
    group, in group order — exactly the stream it would ingest group by
    group — so its m chunks merge with :func:`merge_chunk_messages`
    (refs rebased, ``epoch_off`` carried) and the n_dp merged payloads
    restack on the dp axis.  Shards own independent replays, so the
    sharded bit-parity contract ``add(merge(g1..gm)) == add(g1); ...;
    add(gm)`` holds per shard by the single-shard merge contract
    (tests/test_sharded_pipeline.py pins it through the real pool).
    """
    if len(msgs) == 1:
        return msgs[0]
    per_shard = []
    for s in range(n_dp):
        shard_msgs = [
            {"payload": jax.tree.map(lambda x: x[s], m["payload"]),
             "priorities": np.asarray(m["priorities"])[s],
             "n_trans": int(np.asarray(m["payload"]["n_trans"])[s])}
            for m in msgs]
        per_shard.append(merge_chunk_messages(shard_msgs))
    payload = jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]),
        *[p["payload"] for p in per_shard])
    prios = np.stack([np.asarray(p["priorities"], np.float32)
                      for p in per_shard])
    out = {"payload": payload, "priorities": prios,
           "n_trans": sum(int(p["n_trans"]) for p in per_shard)}
    spans = obs_spans.merge_spans(msgs)    # metadata, not payload (above)
    if spans:
        out[obs_spans.SPAN_KEY] = spans
    return out


class KeyPrefetcher:
    """Pre-split, pre-placed per-chip PRNG keys for the sharded plan.

    Owns the trainer's dispatch key chain while a sharded pipeline is
    live, and only then: it is seeded with the trainer's ``self.key`` when
    ``train()`` builds the pipeline, every ``take()`` is assigned back to
    ``self.key``, and when the pipeline stops the trainer's own
    ``_dispatch_key`` goes on from that ``self.key`` (:class:`KeyBlocks`,
    the single-shard plan's way, follows whatever ``self.key`` is and
    holds no chain of its own) — one chain, one owner at a time.

    Entry i is ``(device_keys(k_i), chain_{i+1})`` where ``chain_{i+1},
    k_i = split(chain_i)`` — the EXACT per-dispatch sequence an eager
    ``self.key, k = split(self.key)`` followed by
    ``ShardedLearner.device_keys(k)`` produces.  The consumer pops
    entries in dispatch order and assigns the returned chain state back
    to its ``self.key``, so a run consumes the eager chain's keys for its
    dispatch count AND leaves the trainer's key where the eager chain
    stands (checkpoints taken mid-train stay exact).

    The staging thread refills between polls; an empty queue (startup,
    key-hungry burst) generates synchronously under the same lock, so
    the chain never forks.
    """

    def __init__(self, sharded, key, depth: int = 4):
        self._sharded = sharded
        self._chain = key
        self.depth = max(1, int(depth))
        self._lock = threading.Lock()
        self._queue: deque = deque()

    def _gen(self) -> None:
        self._chain, k = jax.random.split(self._chain)
        self._queue.append((self._sharded.device_keys(k), self._chain))

    def refill(self) -> None:
        """Top the queue up to ``depth`` (staging-thread side)."""
        with self._lock:
            while len(self._queue) < self.depth:
                self._gen()

    def take(self):
        """``(placed_per_chip_keys, chain_state_after)`` for the next
        dispatch, generating inline if the prefetch ran dry."""
        with self._lock:
            if not self._queue:
                self._gen()
            return self._queue.popleft()


#: dispatch keys one launch of the key-chain program makes
KEY_BLOCK = 64


@jax.jit
def _split_block(chain):
    """``KEY_BLOCK`` sequential ``chain, k = split(chain)`` in ONE program:
    ``[(k_i, chain_{i+1})]``, every key a scalar array of its own, so
    that handing a pair out launches nothing.  Bit-identical to the eager
    chain."""
    def step(chain, _):
        chain, k = jax.random.split(chain)
        return chain, (k, chain)
    _, (ks, chains) = jax.lax.scan(step, chain, None, length=KEY_BLOCK)
    return [(ks[i], chains[i]) for i in range(KEY_BLOCK)]


class KeyBlocks:
    """The single-shard plan's dispatch keys, a block per launch.

    The trainer's ``self.key`` stays the one chain: ``take(chain)`` is
    given it and answers ``(k, chain_after)`` with the
    :class:`KeyPrefetcher` contract (the caller assigns ``chain_after``
    back), popped off a block of :data:`KEY_BLOCK` pairs that
    :func:`_split_block` made.  A block is held by IDENTITY: it serves
    only while the chain passed in is the very object handed out last.
    A chain assigned from outside (checkpoint restore, ``evaluate()``) or
    a block run dry gets a block made from the chain passed in, so the
    keys are the eager chain's at every count whatever else touches
    ``self.key``.  Loop thread only: the one pass in :data:`KEY_BLOCK`
    that runs dry pays the launch and its ``2 * KEY_BLOCK`` output
    buffers (about 0.13 ms of host time each on the chip, PERF.md, PR 27).
    """

    def __init__(self):
        self._pairs: list = []          # the block in hand, next pair last
        self._chain = None              # the chain object handed out last
        self.refills = 0                # blocks made
        self.served = 0                 # keys handed out

    def take(self, chain):
        """``(k, chain_after)`` going on from ``chain``."""
        if chain is not self._chain or not self._pairs:
            self._pairs = _split_block(chain)[::-1]
            self.refills += 1
        k, self._chain = self._pairs.pop()
        self.served += 1
        return k, self._chain


@dataclass
class PipelineState:
    """Trainer-counter snapshot the staging thread groups by.  ``behind``
    mirrors the replay-ratio floor (pause draining so the bounded queue
    backpressures the fleet); ``train_eligible`` predicts whether the
    NEXT chunk will be trained on or absorbed ingest-only — computed from
    the monotone :meth:`IngestPipeline.polled_total` (plus
    :meth:`IngestPipeline.staged_train_steps` on the budget side) so the
    prediction sees exactly what the consume-time warm/budget gate will
    see when that chunk reaches the front of the queue."""

    behind: bool = False
    train_eligible: bool = True
    #: replay-service mode only: may the staging thread pull another
    #: pre-sampled batch?  The ratio budget alone (warmup is enforced
    #: shard-side; pulling IS training, so the floor never gates it).
    pull_eligible: bool = True


@dataclass
class StagedSlot:
    """One ready-on-device unit of ingest work, in stream order.

    kind:
      ``"single"`` — one chunk, the fused-step shape;
      ``"scan"``   — j chunks stacked on a leading axis for the
                     lax.scan dispatch (``n_per`` holds per-chunk
                     transition counts for the per-step beta stack);
      ``"merged"`` — m chunks compacted into one ingest payload.
    """

    kind: str
    payload: object
    prios: object
    n_trans: int
    n_per: tuple[int, ...] = ()
    chunks: int = 1
    #: replay-service ``"batch"`` slots (payload = staged sample batch,
    #: prios = staged IS weights): the sampled tree rows (host numpy —
    #: they round-trip to the owning shard with the new priorities), the
    #: owning shard/sequence ids, and the shard-split update key for
    #: families whose update consumes one (AQL NoisyNet)
    idx: object = None
    shard: int = -1
    seq: int = -1
    update_key: object = None
    #: train steps this slot was STAGED to take (scan j / eligible single
    #: 1 / ingest-only 0) — folded into the budget prediction so chunks
    #: behind an unconsumed trainable slot see the step count they will
    #: actually meet at the front of the queue
    planned_steps: int = 0
    #: lineage spans of the slot's source chunks (obs plane metadata —
    #: the trainer joins them into frame-age / param-lag at consume)
    spans: tuple = ()


def _pow2_floor(n: int) -> int:
    return 1 << (max(1, n).bit_length() - 1)


class IngestPipeline:
    """The background staging stage (module docstring).

    Construction does not start the thread; drive it with
    :meth:`start` / :meth:`stop`.  Single producer (the staging thread),
    single consumer (the trainer loop) — FIFO order is structural.
    """

    def __init__(self, pool, *, depth: int = 2, scan_steps: int = 1,
                 merge_max: int = 8, state_fn=None,
                 capacity: int | None = None,
                 frame_capacity: int | None = None,
                 poll_timeout: float = 0.01,
                 put_device: bool | None = None,
                 sharded=None, key=None, key_prefetch: int = 4,
                 replay_client=None):
        self.pool = pool
        # replay-service mode (apex_tpu/replay_service): the staging
        # thread ALSO pulls pre-sampled batches round-robin from the
        # shard fleet and ships priority write-backs back to the owning
        # shard — the client's sockets are driven by this thread alone
        # (RemotePool's migrate-then-use thread-affinity contract)
        self.client = replay_client
        self._wb_lock = threading.Lock()
        self._wb_q: deque = deque()
        self.depth = max(1, int(depth))
        # dp>1 (``sharded`` = the ShardedLearner): every polled message is
        # one whole round-robin group; the scan stack doesn't apply (the
        # sharded plan has no multi-step program) — group merging is the
        # ingest-only coalescing dimension instead
        self.sharded = sharded
        self.scan_steps = 1 if sharded is not None else max(1,
                                                            int(scan_steps))
        self.merge_max = max(1, int(merge_max))
        self.keys = (KeyPrefetcher(sharded, key, depth=key_prefetch)
                     if sharded is not None and key is not None else None)
        self.state_fn = state_fn or PipelineState
        self.capacity = capacity
        self.frame_capacity = frame_capacity
        self.poll_timeout = poll_timeout
        if put_device is None:
            # pre-staging into device memory only pays when there IS a
            # transfer to hide; on the CPU backend an explicit per-slot
            # device_put costs more than the jit call's own zero-distance
            # ingestion of numpy operands (measured ~150us/leaf)
            put_device = jax.default_backend() != "cpu"
        if not put_device:
            self._stage = lambda x: x
        elif sharded is not None:
            # group slots carry the dp axis in front: place each shard's
            # slice on its chip (NamedSharding over dp) so the sharded
            # dispatch finds its operands already in local HBM
            self._stage = sharded.shard_put
        else:
            self._stage = jax.device_put
        self.put_device = put_device
        self._ring: queue_lib.Queue = queue_lib.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        # clear while the staging thread HOLDS a chunk (polled, being
        # merged, staged or put): poll_slot then waits for the slot that
        # is on its way rather than let the trainer burn a replay-only
        # step on data that is milliseconds away.  Set while it waits on
        # an empty transport: "ring empty + staging idle" is dry, and
        # poll_slot says so at once.  A pool whose poll PRODUCES (the
        # on-device rollout) is polled with the flag as the last pass left
        # it: clear after a put, so the loop waits for the rollout's
        # chunks; set after a ``behind`` pause.  ``_wake`` guards the flag
        # and is notified on every put, so poll_slot sleeps on the event
        # that ends its wait, never on a quantum.
        self._idle = threading.Event()
        self._idle.set()
        self._wake = threading.Condition()
        self._error: BaseException | None = None
        self._pub_lock = threading.Lock()
        self._pub: tuple | None = None
        self._ahead_lock = threading.Lock()
        self._staged_ahead = 0          # transitions polled but not consumed
        self._polled_total = 0          # transitions EVER polled (monotone)
        self._staged_steps = 0          # planned train steps not yet consumed
        self.stats = {"slots": 0, "scan_slots": 0, "merged_slots": 0,
                      "merged_chunks": 0, "publishes": 0,
                      "batch_slots": 0, "writebacks": 0,
                      # poll_slot, on the trainer's thread: answers of None
                      # without a wait; calls that waited, and their seconds
                      "dry_polls": 0, "waited_polls": 0, "poll_wait_s": 0.0}
        # obs plane: staging-thread activity lands on its own track of
        # the learner's trace ring (host clocks only — J006/J010 clean)
        self.ring = get_ring()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="apex-ingest-staging")

    # -- trainer side ------------------------------------------------------

    def start(self) -> None:
        self._thread.start()

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=timeout)

    def staged_ahead(self) -> int:
        """Transitions the pipeline holds (staged or in flight) that the
        trainer has not consumed yet — observability only."""
        return self._staged_ahead

    def polled_total(self) -> int:
        """Transitions EVER polled off the pool — monotone, so the
        warm/budget prediction in ``state_fn`` is race-free: when the
        staging thread asks about the NEXT chunk, this is exactly the
        transition count preceding it in the (order-preserved) stream,
        i.e. the ``ingested`` the consume-time warm gate will see.
        (``ingested + staged_ahead`` is the same quantity only between
        consumptions — mid-consume it undercounts and a train-eligible
        chunk could get merged into an ingest-only payload.)"""
        return self._polled_total

    def staged_train_steps(self) -> int:
        """Train steps staged but not yet consumed: the budget prediction
        adds these to the live step counter, else every chunk behind one
        pending fused step looks budget-eligible and the ingest-only
        stream degrades to unmerged singles."""
        return self._staged_steps

    def publish(self, version: int, params) -> None:
        """Latest-wins async param publish: the staging thread performs
        the blocking device_get + pool serialization.  ``params`` must be
        a tree the hot loop will NOT donate later — the trainer hands an
        on-device ``jnp.copy`` for exactly that reason."""
        with self._pub_lock:
            self._pub = (version, params)

    def write_back(self, shard: int, seq: int, idx, priorities) -> None:
        """Hand one consumed batch's TD priorities to the staging thread,
        which performs the blocking ``device_get`` and ships them to the
        owning shard — the write-back's host sync never lands on the hot
        loop (the same discipline as param publishes)."""
        with self._wb_lock:
            self._wb_q.append((shard, seq, idx, priorities))

    def poll_slot(self, timeout: float = 0.0) -> StagedSlot | None:
        """Next ready slot in stream order, or None when the pipeline is
        dry: the ring is empty, the staging thread holds no chunk (it
        waits on an empty transport, or pauses ``behind``) and ``timeout``
        has run out.  Nothing staged and nothing on its way is answered
        at once, for ``timeout=0`` with no wait at all; only while a slot
        is on its way, or a caller's ``timeout`` still runs, does the
        call wait, and then on the put or on the staging thread going
        idle (``_wake``)."""
        deadline = time.monotonic() + timeout
        waited = 0.0
        try:
            while True:
                try:
                    slot = self._ring.get_nowait()
                    break
                except queue_lib.Empty:
                    pass
                if self._error is not None:
                    raise RuntimeError(
                        "ingest pipeline staging thread died"
                    ) from self._error
                if self._stop.is_set():
                    return None
                t0 = time.monotonic()
                with self._wake:
                    if not self._ring.empty():
                        continue
                    # a held chunk ends in a put, which notifies; the cap
                    # only bounds how late a stop() from another thread
                    # is seen
                    wait = deadline - t0 if self._idle.is_set() else 0.1
                    if wait <= 0:
                        if not waited:
                            self.stats["dry_polls"] += 1
                        return None
                    self._wake.wait(wait)
                waited += time.monotonic() - t0
        finally:
            if waited:
                self.stats["waited_polls"] += 1
                self.stats["poll_wait_s"] += waited
        with self._ahead_lock:
            self._staged_ahead -= slot.n_trans
            self._staged_steps -= slot.planned_steps
        return slot

    def _set_idle(self) -> None:
        with self._wake:
            if not self._idle.is_set():
                self._idle.set()
                self._wake.notify_all()

    # -- staging thread ----------------------------------------------------

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                self._serve_publish()
                if self.keys is not None:
                    # keep the per-chip key prefetch full: each entry
                    # buys one dispatch a host split + sharded put it no
                    # longer pays on the hot loop
                    self.keys.refill()
                # NOTE: no ring-full pre-check — the blocking _put IS the
                # backpressure (bound: depth slots + one group in flight),
                # and a condition-variable wakeup hands the consumer the
                # next slot immediately where a sleep-poll would add a
                # millisecond quantum per slot
                st = self.state_fn()
                if self.client is not None:
                    # replay-service mode: ship pending write-backs, then
                    # prefer a pre-sampled batch; the chunk path below
                    # stays live as the direct-ingest FALLBACK (actors
                    # reroute to the learner when their shard wedges)
                    self._serve_writebacks()
                    if st.pull_eligible:
                        item = self.client.poll_batch(timeout=0)
                        if item is not None:
                            self._idle.clear()
                            with self.ring.span("stage", "ingest-staging",
                                                {"kind": "batch"}):
                                slot = self._build_batch_slot(item)
                            self._put(slot)
                            continue
                if st.behind:
                    # replay-ratio floor: pause draining so the bounded
                    # worker queue backpressures the actor fleet
                    self._set_idle()
                    time.sleep(0.002)
                    continue
                # what is there is taken without a wait, and only a timed
                # wait on an empty transport is idle.  ``_idle`` is not
                # touched before this poll: a pool that produces inside it
                # (AnakinPool launches a rollout, whatever ``timeout``)
                # keeps the flag the last pass left
                msgs = self._poll(1, timeout=0)
                if not msgs:
                    self._set_idle()
                    msgs = self._poll(1, timeout=self.poll_timeout)
                    if not msgs:
                        continue
                self._idle.clear()
                # the slot's kind is known once it is built: the ring
                # event gets it, the annotation opened without it
                with self.ring.span("stage", "ingest-staging") as span:
                    slot = self._build_slot(msgs[0], st)
                    span.note(kind=slot.kind)
                self._put(slot)
        except BaseException as exc:      # surface to poll_slot, loudly
            self._error = exc
            with self._wake:             # whatever the flag read before
                self._idle.set()
                self._wake.notify_all()
            return
        # clean stop: the shards are waiting on the final write-backs
        # (strict ordering) — flush what the trainer queued before stop()
        if self.client is not None:
            self._serve_writebacks()

    def _poll(self, n: int, timeout: float = 0.0) -> list:
        msgs = self.pool.poll_chunks(n, timeout=timeout)
        if msgs:
            n_trans = sum(int(m["n_trans"]) for m in msgs)
            with self._ahead_lock:
                self._staged_ahead += n_trans
                self._polled_total += n_trans
            for m in msgs:
                # first-wins: the socket receiver's decode stamp (truer)
                # survives; mp-queue chunks get their recv time here
                obs_spans.stamp(m, "recv")
        return msgs

    def _build_slot(self, first: dict, st: PipelineState) -> StagedSlot:
        """Group ``first`` with immediately-available successors into one
        staged slot, honoring stream order and the predicted consume
        mode."""
        if st.train_eligible and self.scan_steps > 1:
            return self._build_scan_slot(first)
        if not st.train_eligible:
            cap = self._merge_cap(first["payload"])
            if cap > 1:
                return self._build_merged_slot(first)
        return self._single_slot(first,
                                 planned=1 if st.train_eligible else 0)

    def _merge(self, msgs: list[dict]) -> dict:
        if self.sharded is not None:
            return merge_group_messages(msgs, self.sharded.n_dp)
        return merge_chunk_messages(msgs)

    def _build_scan_slot(self, first: dict) -> StagedSlot:
        from apex_tpu.parallel.aggregate import stack_chunk_messages
        msgs = [first] + self._poll(self.scan_steps - 1, timeout=0)
        # quantize to powers of two so scan-shortfall widths compile
        # O(log K) programs, not one per j; leftovers become singles in
        # order (never reordered past the stack)
        j = _pow2_floor(len(msgs))
        take, rest = msgs[:j], msgs[j:]
        if j == 1:
            slot = self._single_slot(take[0])
        else:
            payload, prios, n_new = stack_chunk_messages(take)
            spans = obs_spans.merge_spans(take)     # scan stack = merge hop
            obs_spans.stamp_spans(spans, "stage")
            slot = StagedSlot(
                kind="scan", payload=self._stage(payload),
                prios=self._stage(prios), n_trans=n_new,
                n_per=tuple(int(m["n_trans"]) for m in take), chunks=j,
                planned_steps=j, spans=tuple(spans))
            with self._ahead_lock:
                self._staged_steps += j
            self.stats["scan_slots"] += 1
            self.stats["slots"] += 1
        for msg in rest:                 # order-preserving spillover
            self._put(slot)
            slot = self._single_slot(msg, planned=1)
        return slot

    def _build_merged_slot(self, first: dict) -> StagedSlot:
        cap = self._merge_cap(first["payload"])
        msgs = [first]
        # extend only while the NEXT chunk is still predicted ingest-only:
        # polled_total already counts everything in msgs, so state_fn sees
        # the effective warm/budget position of the chunk about to join —
        # a merge group never straddles the warmup (or budget) boundary
        while len(msgs) < cap:
            st = self.state_fn()
            if st.train_eligible:
                break
            more = self._poll(1, timeout=0)
            if not more:
                break
            msgs.extend(more)
        # quantize merge widths to powers of two (like the scan widths):
        # every distinct ingest shape is one XLA compile, and arbitrary
        # widths would scatter compiles across the whole run — O(log
        # merge_max) programs total instead.  Spillover stays in order.
        slot = None
        while msgs:
            j = _pow2_floor(min(len(msgs), cap))
            take, msgs = msgs[:j], msgs[j:]
            if slot is not None:
                self._put(slot)
            if j == 1:
                slot = self._single_slot(take[0], planned=0)
                continue
            merged = self._merge(take)
            self.stats["merged_slots"] += 1
            self.stats["merged_chunks"] += j
            self.stats["slots"] += 1
            spans = obs_spans.spans_of(merged)      # merge hop stamped there
            obs_spans.stamp_spans(spans, "stage")
            slot = StagedSlot(
                kind="merged", payload=self._stage(merged["payload"]),
                prios=self._stage(np.asarray(merged["priorities"],
                                             np.float32)),
                n_trans=int(merged["n_trans"]), chunks=j,
                spans=tuple(spans))
        return slot

    def _build_batch_slot(self, item: dict) -> StagedSlot:
        """Stage one pre-sampled shard batch: the sample payload and IS
        weights go on device ahead of the dispatch; the tree rows stay
        host-side (they only round-trip back to the shard with the new
        priorities)."""
        spans = obs_spans.spans_of(item)
        obs_spans.stamp_spans(spans, "stage")
        with self._ahead_lock:
            self._staged_steps += 1
        self.stats["batch_slots"] += 1
        self.stats["slots"] += 1
        return StagedSlot(
            kind="batch",
            payload=self._stage(item["batch"]),
            prios=self._stage(np.asarray(item["weights"], np.float32)),
            n_trans=0, planned_steps=1, spans=tuple(spans),
            idx=np.asarray(item["idx"]),
            shard=int(item.get("shard", 0)), seq=int(item["seq"]),
            update_key=item.get("update_key"))

    def _serve_writebacks(self) -> None:
        while True:
            with self._wb_lock:
                if not self._wb_q:
                    return
                shard, seq, idx, prios = self._wb_q.popleft()
            with self.ring.span("prio_writeback", "ingest-staging",
                                {"shard": shard}):
                self.client.push_priorities(
                    shard, seq, np.asarray(idx),
                    np.asarray(jax.device_get(prios), np.float32))
            self.stats["writebacks"] += 1

    def _single_slot(self, msg: dict, planned: int = 1) -> StagedSlot:
        self.stats["slots"] += 1
        if planned:
            with self._ahead_lock:
                self._staged_steps += planned
        spans = obs_spans.spans_of(msg)
        obs_spans.stamp_spans(spans, "stage")
        return StagedSlot(
            kind="single", payload=self._stage(msg["payload"]),
            prios=self._stage(np.asarray(msg["priorities"], np.float32)),
            n_trans=int(msg["n_trans"]), planned_steps=planned,
            spans=tuple(spans))

    def _merge_cap(self, payload) -> int:
        """Max chunks (dp>1: groups) mergeable with ``payload`` as the
        first member: the payload must be a frame chunk and the merged
        shapes must still fit the pool's validation bounds (m*K <=
        capacity keeps the transition scatter duplicate-free; m*Kf <=
        frame_capacity keeps the ring write in bounds).  Sharded group
        payloads carry the dp axis in front, and the bounds are
        PER-SHARD (capacity/frame_capacity describe one chip's shard),
        so the per-shard chunk shapes at axis 1 are what must fit."""
        if not is_frame_chunk(payload):
            return 1
        ax = 1 if self.sharded is not None else 0
        cap = self.merge_max
        if self.capacity is not None:
            cap = min(cap,
                      self.capacity // max(1, payload["action"].shape[ax]))
        if self.frame_capacity is not None:
            cap = min(cap, self.frame_capacity
                      // max(1, payload["frames"].shape[ax]))
        return max(1, cap)

    def _put(self, slot: StagedSlot) -> None:
        while not self._stop.is_set():
            try:
                self._ring.put(slot, timeout=0.1)
                with self._wake:
                    self._wake.notify_all()
                return
            except queue_lib.Full:
                # param publishes (and shard write-backs — a strict shard
                # is wedged until its priorities land) must not starve
                # behind a full ring
                self._serve_publish()
                if self.client is not None:
                    self._serve_writebacks()
                continue

    def _serve_publish(self) -> None:
        with self._pub_lock:
            req, self._pub = self._pub, None
        if req is None:
            return
        version, params = req
        with self.ring.span("publish", "ingest-staging",
                            {"version": version}):
            if getattr(self.pool, "accepts_device_params", False):
                # co-located on-device rollouts (training/anakin.py): the
                # pool consumes the device copy directly — params never
                # leave the device; the pool device_gets internally only
                # when an inner socket fleet needs wire params (still on
                # THIS thread)
                self.pool.publish_params(version, params)
            else:
                self.pool.publish_params(version, jax.device_get(params))
        self.stats["publishes"] += 1
