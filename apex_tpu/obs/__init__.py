"""Experience-lifecycle tracing + metrics export (the obs plane).

Three surfaces, one unit of account — a frame chunk:

* :mod:`apex_tpu.obs.spans` — chunk lineage spans: compact
  (monotonic, wall) timestamp pairs stamped into chunk-message METADATA
  at each hop (sealed -> send -> recv -> merge -> stage -> consume ->
  prio_wb), never into tensor payloads, so the merge/stack bit-parity
  contracts of the ingest pipeline are untouched.  The learner joins
  them against its publish-time ledger into the headline histograms:
  *frame-age-at-train*, *param-propagation-lag* (seconds) and *policy
  lag* (learner steps since the version a chunk was sent under left).
* :mod:`apex_tpu.obs.trace` — a bounded, host-only trace-event ring per
  process, flushed as Chrome trace-event JSON (perfetto-loadable) on
  exit, periodically, or on SIGUSR2, each flush a segment file of what
  is new since the one before; its ``span`` is the one span
  primitive (a ring event and a ``jax.profiler.TraceAnnotation`` over
  the same interval, so the profiler's trace names the same phases on
  the device's clock); :mod:`apex_tpu.obs.merge` aligns
  the per-process clocks (heartbeat-derived offsets when a
  ``fleet_summary.json`` is present) into ONE fleet timeline.
* :mod:`apex_tpu.obs.metrics` — Prometheus text exposition served from
  the existing fleet-status REP server (port 52003), so MetricLogger
  tails, rates, fleet states, and the latency histograms are pollable
  by standard tooling — plus the declared metric registry
  (``REGISTERED_GAUGES``/``REGISTERED_FAMILIES``) apexlint J015
  enforces on every literal gauge/family name.

Two judging layers sit on top of those signals:

* :mod:`apex_tpu.obs.slo` — the fleet SLO engine: declarative
  objectives over the fleet-summary signal space, multi-window
  burn-rate evaluation on the learner's health tick, flap-damped
  OK -> BURNING -> BREACHED -> RESOLVED alert machines, ``apex_slo_*``
  exposition rows, the ``--scale-signal slo`` autoscaling input, and
  the ``--check`` bench/soak regression differ.
* :mod:`apex_tpu.obs.soak` — the standing saturation soak: a
  loadgen-saturated fleet driven for a wall budget with the engine
  sampled each tick, emitting the machine-readable ``SOAK_*.json``
  artifact (compliance %, alert timeline, throughput vs offered load).

Everything here is stdlib-only (the profiler annotation is imported when
the first live span asks for it) and hot-loop-safe: clock reads and deque
appends, no device syncs (apexlint J006) — and apexlint J010 flags any
clock read or span emission that strays inside jit/shard_map scope.
"""

from apex_tpu.obs.spans import (HOPS, SPAN_KEY, LatencyHistogram,
                                LearnerObs, mark_send, merge_spans,
                                spans_of, stamp, stamp_spans)
from apex_tpu.obs.trace import TraceRing, get_ring, set_process_label

__all__ = ["HOPS", "SPAN_KEY", "LatencyHistogram", "LearnerObs",
           "mark_send", "merge_spans", "spans_of", "stamp", "stamp_spans",
           "TraceRing", "get_ring", "set_process_label"]
