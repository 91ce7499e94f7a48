"""Chunk lineage spans: timestamp metadata following one chunk fleet-wide.

A span is a tiny dict riding OUTSIDE the tensor payload on the chunk
message (``msg[SPAN_KEY]`` is a list of spans — one per source chunk
after merges), so the ingest path's bit-parity contracts (PR 2/3:
``merge_chunk_messages`` / ``merge_group_messages`` compare payloads
field for field) never see it:

    {"pv": <param version the chunk was acted under>,
     "hops": {hop: (monotonic, wall), ...}}

Hops, in stream order (all optional — a transport that skips one just
leaves the histogram that needs it un-fed):

    sealed   actor: chunk materialized by the FrameChunkBuilder drain
    send     actor: handed to the chunk queue / socket sender
    recv     learner: decoded off the wire (or polled off the mp queue)
    merge    learner: coalesced into a merged/stacked ingest payload
    stage    learner: H2D staged by the ingest pipeline
    consume  learner: fused/ingest dispatch issued with this chunk
    prio_wb  learner: dispatch returned (the on-device priority
             write-back is fused into that program — this is its host
             issue-complete time, the closest host-observable proxy)

Both clocks are stamped because neither alone survives the fleet:
monotonic is comparable only within one process (frame-age across the
actor->learner boundary uses wall), wall is comparable across hosts only
up to skew (the heartbeat-derived offsets in
:mod:`apex_tpu.fleet.registry` measure that skew; ``obs.merge`` applies
it).  Stamping is first-wins per hop, so a double-instrumented path
(socket recv + pipeline poll) keeps the earlier, truer time.

The learner-side join lives in :class:`LearnerObs`: a bounded
publish-time ledger (version -> publish clocks and learner step) plus
the headline :class:`LatencyHistogram`\\ s — *frame-age-at-train*
(consume wall - sealed wall), *param-propagation-lag* (consume mono -
publish mono of the version the chunk was ACTED under: how long a
published policy takes to come back as trainable experience, the Ape-X
staleness loop measured end to end) and *policy lag*, the same loop in
learner steps (steps now - step at which that version left).  ``pv`` is
the version the worker held when it SENT the chunk (:func:`mark_send`),
so both lags are those of the chunk's last transitions and a lower
bound for its first.

Everything is stdlib + host clocks: safe on hot loops (J006), and J010
flags any of these calls straying into jit/shard_map trace scope.
"""

from __future__ import annotations

import os
import time
from collections import OrderedDict, deque

from apex_tpu.utils.metrics import percentile

#: chunk-message metadata key (a LIST of span dicts)
SPAN_KEY = "obs_spans"

#: canonical hop order (lineage trace events pair consecutive present
#: hops).  The three shard_* hops exist only on the sharded replay
#: service path (apex_tpu/replay_service): chunk decoded on the shard
#: socket -> folded into a pre-sampled batch -> batch handed to the
#: learner's pull — so frame-age-at-train stays measurable across the
#: extra network hop (a batch carries the spans of the freshest source
#: chunks folded into it since the previous sample).  The three infer_*
#: hops ride POLICY-REQUEST messages on the inference plane
#: (apex_tpu/infer_service): request shipped by the actor -> coalesced
#: into a server batch -> reply issued — they precede ``sealed`` because
#: acting happens before the transition is recorded, and they keep the
#: extra acting-time network hop visible in the same span vocabulary.
HOPS = ("infer_send", "infer_batch", "infer_reply",
        "sealed", "send", "shard_recv", "shard_sample", "batch_send",
        "recv", "merge", "stage", "consume", "prio_wb")


def enabled() -> bool:
    """Span stamping is on by default; ``APEX_OBS_SPANS=0`` disables it
    (the A/B for "does stamping cost anything on this box")."""
    return os.environ.get("APEX_OBS_SPANS", "1").lower() not in (
        "0", "false", "no")


def _now() -> tuple[float, float]:
    return (time.monotonic(), time.time())


def new_span(param_version: int = 0, hop: str = "sealed") -> dict:
    return {"pv": int(param_version), "hops": {hop: _now()}}


def spans_of(msg) -> list:
    """The message's span list ([] when unstamped/disabled)."""
    if isinstance(msg, dict):
        return msg.get(SPAN_KEY) or []
    return []


def stamp_spans(spans, hop: str) -> None:
    """Stamp ``hop`` on every span that lacks it (first wins: pipeline
    order is monotone, so the earliest stamp is the true hop time)."""
    if not spans:
        return
    t = _now()
    for span in spans:
        span["hops"].setdefault(hop, t)


def stamp(msg, hop: str) -> None:
    """Stamp ``hop`` on a chunk message's spans; no-op when unstamped."""
    stamp_spans(spans_of(msg), hop)


def mark_send(msg, param_version: int = 0) -> None:
    """Actor-side send site: ensure the message carries a span (sealed is
    stamped by ``drain_builder_chunks``; a bare message gets one here),
    record the param version the chunk was acted under, and stamp
    ``send``.  One call per chunk put, both worker loops."""
    if not enabled() or not isinstance(msg, dict):
        return
    spans = msg.get(SPAN_KEY)
    if not spans:
        spans = msg[SPAN_KEY] = [new_span(param_version, hop="sealed")]
    t = _now()
    for span in spans:
        span["pv"] = int(param_version)
        span["hops"].setdefault("send", t)


def merge_spans(msgs: list, hop: str = "merge") -> list:
    """Flatten the span lists of ``msgs`` (merge/stack/aggregate sites)
    and stamp ``hop`` — the merged message carries one span per SOURCE
    chunk, so per-chunk ages survive coalescing."""
    out: list = []
    for m in msgs:
        out.extend(spans_of(m))
    stamp_spans(out, hop)
    return out


class LatencyHistogram:
    """Bounded sliding-window histogram (seconds, or whatever unit the
    caller records): record floats, read nearest-rank percentiles.  Pure
    host bookkeeping."""

    def __init__(self, window: int = 4096):
        self._vals: deque[float] = deque(maxlen=window)
        self.count = 0
        self.total = 0.0
        self.max = 0.0

    def record(self, v: float) -> None:
        v = float(v)
        self._vals.append(v)
        self.count += 1
        self.total += v
        if v > self.max:
            self.max = v

    def quantile(self, q: float) -> float:
        """Nearest-rank ``q`` of the window, in the recorded unit."""
        return percentile(sorted(self._vals), q)

    def snapshot(self) -> dict:
        s = sorted(self._vals)
        return {
            "count": self.count,
            "mean_s": round(self.total / self.count, 6) if self.count else 0.0,
            "p50_s": round(percentile(s, 0.50), 6),
            "p90_s": round(percentile(s, 0.90), 6),
            "p99_s": round(percentile(s, 0.99), 6),
            "max_s": round(self.max, 6),
        }


class LearnerObs:
    """Learner-side span join: publish ledger + the headline histograms
    + chunk-lineage trace events.

    Call order per consumed slot:
    :meth:`pre_consume` immediately before the dispatch (stamps
    ``consume``), :meth:`post_consume` right after the dispatch call
    returns (stamps ``prio_wb``, feeds the histograms, emits lineage
    events and one ``consume`` event per span with ``pv``, ``lag_steps``
    and ``age_s``).  :meth:`note_publish` records each version's publish
    time and learner step — the join key for both lags.
    """

    def __init__(self, ring=None, max_versions: int = 1024,
                 clock=time.monotonic, wall=time.time):
        self.frame_age = LatencyHistogram()
        self.param_lag = LatencyHistogram()
        self.policy_lag = LatencyHistogram()        # learner steps
        self._pub: OrderedDict[int, tuple] = OrderedDict()
        self._max_versions = max_versions
        self.ring = ring
        self._clock = clock
        self._wall = wall
        self.spans_consumed = 0

    # -- publish ledger ----------------------------------------------------

    def note_publish(self, version: int, step: int | None = None) -> None:
        """``step``: the learner's update count as the version leaves."""
        self._pub[int(version)] = (self._clock(), self._wall(), step)
        while len(self._pub) > self._max_versions:
            self._pub.popitem(last=False)

    def _publish_step(self, pv: int) -> int | None:
        """The step at which version ``pv`` left; for a version the
        ledger no longer (or never) held, that of the first later one it
        does hold — the lag then reads too low, as it does anyway (module
        docstring), never too high."""
        pub = self._pub.get(pv)
        if pub is None:
            pub = next((p for v, p in self._pub.items() if v >= pv), None)
        return None if pub is None else pub[2]

    # -- consume join ------------------------------------------------------

    def pre_consume(self, spans) -> None:
        stamp_spans(spans, "consume")

    def post_consume(self, spans, step: int | None = None) -> None:
        """``step``: the learner's update count now (policy lag in steps
        is left out where the caller gives none)."""
        if not spans:
            return
        stamp_spans(spans, "prio_wb")
        now_mono, now_wall = self._clock(), self._wall()
        for span in spans:
            self.spans_consumed += 1
            hops = span.get("hops", {})
            sealed = hops.get("sealed")
            age = None
            if sealed is not None:
                # wall clocks: the only pair comparable across the
                # actor->learner process (or host) boundary
                age = now_wall - sealed[1]
                if age >= 0:
                    self.frame_age.record(age)
            pv = int(span.get("pv", -1))
            pub = self._pub.get(pv)
            if pub is not None:
                # mono clocks: publish and consume both happen HERE
                self.param_lag.record(max(0.0, now_mono - pub[0]))
            lag_steps = None
            left_at = None if step is None else self._publish_step(pv)
            if left_at is not None:
                lag_steps = max(0, int(step) - int(left_at))
                self.policy_lag.record(lag_steps)
            if self.ring is not None and self.ring.enabled:
                self._emit_lineage(span)
                self.ring.instant("consume", track="chunk-lineage",
                                  args={"pv": pv, "lag_steps": lag_steps,
                                        "age_s": age})

    def _emit_lineage(self, span: dict) -> None:
        """One trace event per consecutive hop pair, on the learner
        ring's wall timebase — the chunk's whole journey renders as one
        stacked track in the merged perfetto timeline."""
        hops = span.get("hops", {})
        present = [(h, hops[h]) for h in HOPS if h in hops]
        for (h1, t1), (h2, t2) in zip(present, present[1:]):
            dur = t2[1] - t1[1]
            if dur < 0:          # cross-host wall skew can invert a hop
                continue
            self.ring.complete_wall(f"{h1}→{h2}", t1[1], dur,
                                    track="chunk-lineage",
                                    args={"pv": span.get("pv", 0)})

    # -- read surface ------------------------------------------------------

    def scalars(self) -> dict:
        """The ``obs_*`` learner scalar set (logged at the trainer's log
        cadence)."""
        fa, pl = self.frame_age.snapshot(), self.param_lag.snapshot()
        return {
            "obs_frame_age_p50_s": fa["p50_s"],
            "obs_frame_age_p99_s": fa["p99_s"],
            "obs_param_lag_p50_s": pl["p50_s"],
            "obs_param_lag_p99_s": pl["p99_s"],
            "obs_policy_lag_p50_steps": self.policy_lag.quantile(0.50),
            "obs_policy_lag_p99_steps": self.policy_lag.quantile(0.99),
            "obs_spans_consumed": self.spans_consumed,
        }

    def summary(self) -> dict:
        """The e2e bench ``latency`` section body."""
        return {
            "frame_age_at_train_s": self.frame_age.snapshot(),
            "param_propagation_lag_s": self.param_lag.snapshot(),
            "spans_consumed": self.spans_consumed,
        }
