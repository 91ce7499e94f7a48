"""Profiler hooks and host-loop timers (SURVEY.md §5.1).

The reference's observability is wall-clock BPS prints
(``origin_repo/learner.py:171-175``).  TPU-side we add what locates a
bottleneck on the host side of a device loop:

* :func:`trace` — ``jax.profiler`` trace context; open the dump in
  TensorBoard/XProf to see per-op HBM + MXU utilization.
* :class:`PhaseTimer` / :class:`DispatchGapTimer` — where a host loop's
  wall time goes between device dispatches.

FLOP counts, device peaks and utilization live with the benchmark
(``benchmark/costs.py``, ``benchmark/peaks.json``).
"""

from __future__ import annotations

import contextlib
import time
from collections import deque
from typing import Iterator

import jax

from apex_tpu.utils.metrics import percentile  # noqa: F401 (re-export)


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """``with trace("/tmp/prof"): run_steps()`` -> XProf dump in logdir.
    The process's spans (:meth:`apex_tpu.obs.trace.TraceRing.span`) hold
    their profiler annotations meanwhile, so the dump names the loop's
    host phases beside the device's programs."""
    from apex_tpu.obs.trace import get_ring
    ring = get_ring()
    jax.profiler.start_trace(logdir)
    ring.annotate(True)
    try:
        yield
    finally:
        ring.annotate(False)
        jax.profiler.stop_trace()


class PhaseTimer:
    """Named wall-time phase accounting for a host loop (the actor-plane
    counterpart of :class:`DispatchGapTimer`): callers wrap each phase of a
    step — policy-wait, env-step, chunk drain — and :meth:`window` reports
    what fraction of the elapsed wall each phase consumed since the last
    reset.  Fractions need not sum to 1; the remainder is unattributed
    host time (param polls, Python bookkeeping).

    Pure host timing — never touches the device, so it is safe on the hot
    loop.

    ``ring``: an optional :class:`apex_tpu.obs.trace.TraceRing` — when
    attached, every phase also goes through the ring's ``span`` (one
    Chrome trace event and one profiler annotation while tracing is live;
    host clock reads only, apexlint J006/J010 stay clean).
    """

    def __init__(self, ring=None, track: str | None = None):
        self._acc: dict[str, float] = {}
        self._t0 = time.perf_counter()
        self.ring = ring
        self.track = track

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        t = time.perf_counter()
        try:
            if self.ring is None:
                yield
            else:
                with self.ring.span(name, self.track):
                    yield
        finally:
            self.add(name, time.perf_counter() - t)

    def add(self, name: str, seconds: float) -> None:
        self._acc[name] = self._acc.get(name, 0.0) + seconds

    def window(self, reset: bool = True) -> dict:
        """``{"wall_s", "fracs": {name: frac}}`` over the window since
        construction or the last resetting call."""
        now = time.perf_counter()
        wall = max(now - self._t0, 1e-9)
        out = {"wall_s": wall,
               "fracs": {k: v / wall for k, v in self._acc.items()}}
        if reset:
            self._acc = {k: 0.0 for k in self._acc}
            self._t0 = now
        return out


class DispatchGapTimer:
    """Host-side dispatch-gap accounting for async-dispatch hot loops.

    The gap is the wall time between one device dispatch RETURNING (the
    jitted call handing back futures — not the computation finishing) and
    the next dispatch being ISSUED.  Under async dispatch that gap is
    exactly the host-side hole in the device's work feed: polling, chunk
    stacking, H2D staging, Python bookkeeping.  A saturated learner keeps
    it near zero; the ingest pipeline exists to move the gap's contents
    onto a staging thread (training/ingest_pipeline.py).

    Pure host timing — never touches the device, so it is safe on the hot
    loop (unlike ``block_until_ready`` fences, which apexlint J006 flags
    there).
    """

    def __init__(self, window: int = 512, ring=None,
                 track: str | None = None):
        self._last_return: float | None = None
        self._gaps: deque[float] = deque(maxlen=window)
        self.count = 0
        self.total = 0.0
        self.max = 0.0
        # optional obs.trace ring: each measured gap becomes one
        # "host_gap" trace event (host timing only)
        self.ring = ring
        self.track = track

    def about_to_dispatch(self) -> None:
        """Call immediately before issuing a device dispatch."""
        if self._last_return is None:
            return
        t0 = self._last_return
        gap = time.perf_counter() - t0
        self._gaps.append(gap)
        self.count += 1
        self.total += gap
        if gap > self.max:
            self.max = gap
        self._last_return = None
        if self.ring is not None:
            self.ring.complete("host_gap", t0, gap, track=self.track)

    def dispatch_returned(self) -> None:
        """Call immediately after the dispatch call returns."""
        self._last_return = time.perf_counter()

    def snapshot(self) -> dict:
        """Non-mutating stats dict (ms units; nearest-rank percentiles
        over the last ``window`` gaps) — callers may sample it at any
        cadence."""
        gaps = sorted(self._gaps)
        return {
            "dispatch_gap_ms_mean":
                1000.0 * self.total / self.count if self.count else 0.0,
            "dispatch_gap_ms_p50": 1000.0 * percentile(gaps, 0.50),
            "dispatch_gap_ms_p90": 1000.0 * percentile(gaps, 0.90),
            "dispatch_gap_ms_p99": 1000.0 * percentile(gaps, 0.99),
            "dispatch_gap_ms_max": 1000.0 * self.max,
            "dispatches": self.count,
        }
