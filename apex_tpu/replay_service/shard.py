"""One replay shard's compute: ingest -> prioritized sample -> write-back.

This is the deterministic half of the replay service (no sockets — the
wire lives in :mod:`apex_tpu.replay_service.service`).  A shard owns ONE
:class:`~apex_tpu.replay.frame_pool.FramePoolReplay` segment tree and runs
the exact three programs the in-learner path runs, just as separate
dispatches instead of one fused one:

* ``add``      — the same ingest program ``LearnerCore.jit_ingest`` compiles
  (donated state, duplicate-pad-write invariant intact);
* ``sample``   — the same stratified PER sample the fused step embeds,
  driven by the shard's OWN PRNG key chain (``chain, k = split(chain)``
  per batch — the split sequence the trainer's ``self.key`` would have
  produced for the same dispatch count);
* ``update_priorities`` — the learner's TD priorities written back to the
  tree rows the batch was sampled from.

Bit-parity contract (the reason this class exists instead of an ad-hoc
loop in the server): with ``strict_order=True`` and one shard, the
sequence ``ingest(c1); b1=next_batch(); write_back(b1); ingest(c2); ...``
produces bit-identical replay state, sampled batches, and key-chain
position to the in-learner sequence ``fused_step(c1); fused_step(c2);
...`` — same tree, same beta schedule (beta is computed from the
PRE-ingest transition count, exactly like the trainer's ``_beta()`` call
before each fused dispatch), same keys.  tests/test_replay_service.py pins
params + every replay-state field + the key chain.

Ordering modes:

* ``strict_order=True`` (default): batch j+1 is sampled only after batch
  j's write-back has been applied, and the next ingest DEFERS behind an
  outstanding write-back (``can_ingest``) — because a wrapped ring can
  overwrite a just-sampled row, ingest and write-back do not commute
  bitwise.  The cost is one learner round-trip of latency per batch; the
  win is a replay plane that is deterministic and provably equivalent to
  the single-process path.
* ``strict_order=False``: the reference's semantics (``replay.py:104-146``
  applies priority updates whenever they arrive) — pre-sample up to
  ``presample_depth`` batches ahead, ingest never waits, write-backs land
  out of band.  Throughput mode for large fleets.

Families whose update consumes a PRNG key (``AQLCore.update_needs_key``)
get the trainer half of the split shipped WITH the batch: the shard
splits its per-batch key into (sample, update) halves like
``AQLCore.train_step`` does and sends the update half as raw key data —
one chain, two consumers, no fork.
"""

from __future__ import annotations

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.obs import spans as obs_spans
from apex_tpu.serving import fence

#: most source-chunk lineage spans carried onto one sampled batch (the
#: batch mixes many chunks; the freshest few keep frame-age measurable)
MAX_BATCH_SPANS = 8


class ReplayShardCore:
    """State + jitted programs of one replay shard (module docstring).

    ``warmup`` is PER SHARD (drivers divide the global warmup by the
    shard count); ``beta_anneal``/``n_shards`` let the shard estimate the
    GLOBAL ingest count for the trainer's beta schedule (shard-local
    ingested x n_shards — exact at N=1, an unbiased estimate under the
    uniform chunk hash otherwise).
    """

    def __init__(self, replay, key, *, batch_size: int, warmup: int,
                 beta: float = 0.4, beta_anneal: int = 500_000,
                 n_shards: int = 1, strict_order: bool = True,
                 presample_depth: int = 2, update_needs_key: bool = False,
                 example_item=None, quota: int = 0):
        self.replay = replay
        self.state = replay.init(example_item)
        self.key = key
        self.batch_size = int(batch_size)
        self.warmup = int(warmup)
        self.beta0 = float(beta)
        self.beta_anneal = int(beta_anneal)
        self.n_shards = max(1, int(n_shards))
        self.strict_order = bool(strict_order)
        self.presample_depth = max(1, int(presample_depth))
        self.update_needs_key = bool(update_needs_key)
        # per-tenant replay quota (PR 13): max RESIDENT transitions this
        # partition may hold (0 = unlimited — the single-tenant default,
        # bit-identical behavior).  The server refuses ingest into a
        # full partition (acked + counted: quota_dropped) so one tenant
        # can never evict another's experience from the shared shard.
        self.quota = max(0, int(quota))
        self.quota_dropped = 0
        # the three programs the fused step decomposes into
        self._add = jax.jit(replay.add, donate_argnums=(0,))
        self._sample = jax.jit(replay.sample, static_argnums=(2,))
        self._wb = jax.jit(replay.update_priorities, donate_argnums=(0,))
        # counters
        self.ingested = 0               # transitions resident (cumulative)
        self.chunks = 0
        self.sampled = 0                # batches ever sampled (chain length)
        self.wb_applied = 0             # write-backs applied
        self.dup_wb = 0                 # duplicate/late write-backs dropped
        # learner-epoch fencing (PR 8): the highest epoch any pull or
        # write-back has carried (0 = unstamped legacy traffic, fencing
        # off).  Write-backs from an OLDER epoch are a restarted
        # learner's predecessor talking — rejected and counted, never
        # applied (they would corrupt priorities the new learner already
        # owns); a NEWER epoch's first pull forgives the old epoch's
        # outstanding batches (that learner is gone with its write-backs)
        self.learner_epoch = 0
        self.stale_wb = 0               # stale-epoch write-backs rejected
        self.epoch_forgiven = 0         # batches forgiven on epoch bumps
        self.restored = 0               # transitions resident at restore
        self._outbox: deque[dict] = deque()
        self._pending_spans: deque = deque(maxlen=MAX_BATCH_SPANS)

    # -- gating --------------------------------------------------------------

    @property
    def warm(self) -> bool:
        return self.ingested >= self.warmup

    def outstanding(self) -> int:
        """Batches sampled whose priorities have not come back yet."""
        return self.sampled - self.wb_applied

    def resident(self) -> int:
        """Transitions currently resident (the ring overwrites past
        capacity, so residency saturates there)."""
        return min(self.ingested, self.replay.capacity)

    def over_quota(self) -> bool:
        """True when the partition is at its tenant quota — the server
        drops (acks + counts) further ingest instead of letting this
        tenant grow past its admission record."""
        return self.quota > 0 and self.resident() >= self.quota

    def can_ingest(self) -> bool:
        """Strict mode defers ingest behind an outstanding write-back: a
        wrapped ring can overwrite a sampled row, so ingest and write-back
        do not commute bitwise (module docstring).  Loose mode never
        waits."""
        if not self.strict_order:
            return True
        return self.outstanding() == 0

    def _can_sample(self) -> bool:
        if not self.warm:
            return False
        if self.strict_order:
            return self.outstanding() == 0 and not self._outbox
        # outstanding() already counts outbox batches (sampled, priorities
        # not back) — it IS the batches-in-flight-beyond-this-tree measure
        # the depth bounds
        return self.outstanding() < self.presample_depth

    def beta(self, ingested: int | None = None) -> float:
        """The trainer's ``_beta`` schedule on the estimated GLOBAL
        ingest count (shard-local x n_shards; exact at N=1)."""
        n = (self.ingested if ingested is None else ingested) * self.n_shards
        frac = min(1.0, n / max(1, self.beta_anneal))
        return self.beta0 + (1.0 - self.beta0) * frac

    # -- ingest ----------------------------------------------------------------

    def ingest_msg(self, msg: dict) -> None:
        """Ingest one chunk message (``{"payload", "priorities",
        "n_trans"}``).  Pre-ingest warm/beta are captured FIRST — the
        in-learner loop computes both before the fused dispatch, and the
        lockstep sample after this ingest must see the same values."""
        warm_pre = self.warm
        beta_pre = self.beta()
        payload = msg["payload"]
        prios = jnp.asarray(np.asarray(msg["priorities"], np.float32))
        self.state = self._add(self.state, payload, prios)
        self.ingested += int(msg["n_trans"])
        self.chunks += 1
        spans = obs_spans.spans_of(msg)
        if spans:
            self._pending_spans.extend(spans)
        if warm_pre and self._can_sample():
            # lockstep pre-sample: one batch per warm ingest, with the
            # pre-ingest beta — exactly the fused step's sample half
            self._outbox.append(self._sample_batch(beta_pre))

    # -- sampling ----------------------------------------------------------------

    def _sample_batch(self, beta: float) -> dict:
        self.key, k = jax.random.split(self.key)
        if self.update_needs_key:
            # AQLCore.train_step splits the dispatch key into
            # (sample, update): ship the update half as raw key data so
            # the learner consumes the same chain without forking it
            k_sample, k_update = jax.random.split(k)
            update_key = np.asarray(jax.random.key_data(k_update))
        else:
            k_sample, update_key = k, None
        batch, weights, idx = self._sample(self.state, k_sample,
                                           self.batch_size,
                                           jnp.float32(beta))
        seq = self.sampled
        self.sampled += 1
        out = {
            "kind": "batch",
            "seq": seq,
            "batch": jax.device_get(batch),
            "weights": np.asarray(weights),
            "idx": np.asarray(idx),
            "ingested": self.ingested,
            "sampled": self.sampled,
        }
        if update_key is not None:
            out["update_key"] = update_key
        spans = list(self._pending_spans)
        self._pending_spans.clear()
        if spans:
            obs_spans.stamp_spans(spans, "shard_sample")
            out[obs_spans.SPAN_KEY] = spans
        return out

    def next_batch(self) -> dict | None:
        """The next pre-sampled batch, or an on-demand sample (the
        train-only-step equivalent: the learner is pulling faster than
        chunks arrive), or None when the shard cannot serve one yet
        (cold, or strict mode waiting on a write-back)."""
        if self._outbox:
            return self._outbox.popleft()
        if self._can_sample():
            return self._sample_batch(self.beta())
        return None

    # -- write-back --------------------------------------------------------------

    def note_epoch(self, epoch: int) -> int:
        """Pull-side half of the epoch fence: a pull stamped with a NEWER
        learner epoch proves a restart — the old learner's outstanding
        write-backs will never arrive, so they are forgiven immediately
        (counted) instead of wedging the strict gate until the silence
        timeout.  Returns the number forgiven."""
        if not fence.newer_epoch(epoch, self.learner_epoch):
            return 0
        forgiven = 0
        if self.learner_epoch and self.outstanding() > 0:
            forgiven = self.forgive_outstanding()
            self.epoch_forgiven += forgiven
        self.learner_epoch = epoch
        return forgiven

    def write_back(self, seq: int, idx, priorities, epoch: int = 0) -> bool:
        """Apply one batch's TD priorities to the tree rows it was
        sampled from.  Duplicates (a retried pull training the same data
        twice) are counted and dropped — the zmq DEALER preserves order,
        so ``seq`` regressions only mean retransmits.  A write-back
        stamped with a STALE learner epoch (a restarted learner's
        predecessor) is rejected and counted — applying it would corrupt
        priorities on rows the new learner's stream now owns."""
        if epoch and self.learner_epoch \
                and fence.stale_epoch(epoch, self.learner_epoch):
            self.stale_wb += 1
            return False
        if fence.newer_epoch(epoch, self.learner_epoch):
            self.learner_epoch = epoch
        if seq < self.wb_applied:
            self.dup_wb += 1
            return False
        self.state = self._wb(self.state, jnp.asarray(idx),
                              jnp.asarray(np.asarray(priorities,
                                                     np.float32)))
        self.wb_applied = seq + 1
        return True

    def forgive_outstanding(self) -> int:
        """Abandon write-backs that will never come (a learner that died
        between pull and write-back): the strict gate must not wedge the
        shard — and its actors' credit windows — forever.  The server
        calls this after ``dead_after_s`` of write-back silence; a late
        write-back for a forgiven batch lands as a counted duplicate.
        Returns the number forgiven."""
        n = self.outstanding()
        self.wb_applied = self.sampled
        return n

    # -- durability (PR 8: shard checkpoint/restore) -----------------------------

    #: spec fields a snapshot pins — a restore into a differently-shaped
    #: shard would corrupt silently, so mismatches start cold instead
    _SNAP_PINS = ("batch_size", "warmup", "n_shards", "strict_order",
                  "update_needs_key")

    def quiescent(self) -> bool:
        """True when a snapshot taken now is self-consistent: no batch in
        flight to the learner and none pre-sampled but unserved (their
        write-backs/serves would be lost with the process, breaking the
        strict lockstep a restore resumes).  Loose mode snapshots
        anywhere — restore forgives the in-flight tail."""
        if not self.strict_order:
            return True
        return self.outstanding() == 0 and not self._outbox

    def snapshot_meta(self) -> dict:
        meta = {p: getattr(self, p) for p in self._SNAP_PINS}
        meta.update(
            capacity=self.replay.capacity,
            ingested=self.ingested, chunks=self.chunks,
            sampled=self.sampled, wb_applied=self.wb_applied,
            dup_wb=self.dup_wb, stale_wb=self.stale_wb,
            epoch_forgiven=self.epoch_forgiven,
            learner_epoch=self.learner_epoch)
        return meta

    def save_snapshot(self, path: str) -> str:
        """Atomically persist the whole shard — segment trees + frame
        pool (one FramePoolState pytree), PRNG chain, counters — with the
        same tmp+rename discipline as ``fleet_summary.json``.  A reader
        never sees a torn file; a crash mid-save leaves the previous
        snapshot restorable."""
        from apex_tpu.training.checkpoint import save_bundle
        return save_bundle(
            path,
            {"state": self.state, "key": jax.random.key_data(self.key)},
            self.snapshot_meta())

    def restore_snapshot(self, path: str) -> dict:
        """Warm-rejoin from a snapshot: bit-exact replay state, key
        chain, and counters.  Batches sampled-but-unresolved at snapshot
        time (loose mode) are forgiven — their learner round-trips died
        with the old process.  Raises ValueError on a spec mismatch (the
        caller starts cold rather than corrupt)."""
        from apex_tpu.training.checkpoint import restore_bundle
        bundle, meta = restore_bundle(
            path,
            {"state": self.state, "key": jax.random.key_data(self.key)})
        for pin in self._SNAP_PINS + ("capacity",):
            want = (self.replay.capacity if pin == "capacity"
                    else getattr(self, pin))
            if meta.get(pin) != want:
                raise ValueError(
                    f"snapshot {pin}={meta.get(pin)!r} != live shard "
                    f"{pin}={want!r} — refusing a shape-shifting restore")
        self.state = bundle["state"]
        self.key = jax.random.wrap_key_data(bundle["key"])
        self.ingested = int(meta["ingested"])
        self.chunks = int(meta["chunks"])
        self.sampled = int(meta["sampled"])
        self.dup_wb = int(meta["dup_wb"])
        self.stale_wb = int(meta.get("stale_wb", 0))
        self.epoch_forgiven = int(meta.get("epoch_forgiven", 0))
        self.learner_epoch = int(meta.get("learner_epoch", 0))
        # in-flight tail forgiven: late write-backs land as counted dups
        self.wb_applied = self.sampled
        self._outbox.clear()
        self._pending_spans.clear()
        self.restored = self.ingested
        return meta

    # -- observability -----------------------------------------------------------

    def stats(self) -> dict:
        return {
            "ingested": self.ingested,
            "chunks": self.chunks,
            "sampled": self.sampled,
            "wb_applied": self.wb_applied,
            "dup_wb": self.dup_wb,
            "stale_wb": self.stale_wb,
            "epoch_forgiven": self.epoch_forgiven,
            "learner_epoch": self.learner_epoch,
            "restored": self.restored,
            "outbox": len(self._outbox),
            "warm": self.warm,
            "quota": self.quota,
            "quota_dropped": self.quota_dropped,
        }
