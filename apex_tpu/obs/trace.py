"""Per-role trace ring: bounded, host-only Chrome trace events.

One :class:`TraceRing` per process, enabled when ``APEX_TRACE_DIR`` is
set (else :func:`get_ring` returns a disabled stub whose methods cost one
attribute check).  Producers are the existing hook points — the actor
families' :class:`~apex_tpu.utils.profiling.PhaseTimer` /
:class:`~apex_tpu.utils.profiling.DispatchGapTimer`, the ingest
pipeline's staging thread, and the learner's chunk-lineage join
(:class:`apex_tpu.obs.spans.LearnerObs`) — all of which record plain
host clock reads into a ``deque(maxlen=...)``: no device sync ever
(apexlint J006), no lock on the append path (GIL-atomic); the ring's
capacity is its only bound.

:meth:`TraceRing.span` is the one span primitive: a context manager that
records one complete event in the ring AND holds a
``jax.profiler.TraceAnnotation`` of the same name over the same
interval, so the span is also in the profiler's ``.xplane.pb``, on the
device trace's clock (its ``args`` come back as the event's stats).  It
is live when the ring is enabled or a profiler trace was started through
:func:`apex_tpu.utils.profiling.trace` (``--profile-dir``); otherwise it
returns one shared no-op object.

Two timebases per event: ``perf`` (``time.perf_counter`` — in-process
phases/gaps) and ``wall`` (``time.time`` — chunk-lineage hops, whose
stamps cross process boundaries).  At dump time everything is emitted in
WALL microseconds using the anchor captured at ring creation, so each
per-process file is immediately perfetto-loadable and
:mod:`apex_tpu.obs.merge` only has to apply cross-host skew offsets and
re-zero the fleet timeline.

Dump triggers: atexit, a periodic flusher thread (every
``APEX_TRACE_FLUSH_S``, default 10 — so SIGKILLed/terminated roles still
leave a near-complete trace, the same evidence-survival discipline as
``fleet_summary.json``), and SIGUSR2 when the process's main thread can
install handlers.
"""

from __future__ import annotations

import atexit
import json
import os
import signal
import threading
import time
from collections import deque

#: env knobs (read at ring creation)
TRACE_DIR_ENV = "APEX_TRACE_DIR"
CAPACITY_ENV = "APEX_TRACE_CAPACITY"
FLUSH_ENV = "APEX_TRACE_FLUSH_S"


class _NoSpan:
    """What :meth:`TraceRing.span` hands out when nothing is live."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **args) -> None:
        pass


_NO_SPAN = _NoSpan()
_ANNOTATION = None          # jax.profiler.TraceAnnotation, or False


def _annotation():
    """``jax.profiler.TraceAnnotation``, imported on the first live span
    (this module stays importable without JAX); False where it is not to
    be had."""
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except Exception:           # no JAX in this role: ring only
            _ANNOTATION = False
    return _ANNOTATION


class _Span:
    """One live span: a ring event (recorded at exit, so :meth:`note` can
    add args until then) and a profiler annotation (its args are those
    known at entry)."""

    __slots__ = ("ring", "name", "track", "args", "t0", "ann")

    def __init__(self, ring, name, track, args):
        self.ring, self.name, self.track, self.args = ring, name, track, args
        ann = _annotation()
        self.ann = ann(name, **(args or {})) if ann else None

    def __enter__(self):
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.ring.complete(self.name, self.t0, dur, track=self.track,
                           args=self.args)
        return False

    def note(self, **args) -> None:
        """Args known only once the span's work is under way (they reach
        the ring event; the annotation was named at entry)."""
        self.args = {**self.args, **args} if self.args else args


class TraceRing:
    """Bounded ring of trace events for one process."""

    def __init__(self, label: str, enabled: bool = True,
                 capacity: int = 65536):
        self.label = label
        self.enabled = enabled
        # spans are live while the ring records or a profiler trace runs
        self.live = enabled
        self._events: deque[tuple] = deque(maxlen=capacity)
        self._tracks: dict[str, int] = {}
        self._tracks_lock = threading.Lock()
        # wall<->perf anchor: dump converts perf-timebase events to wall
        self._anchor_wall = time.time()
        self._anchor_perf = time.perf_counter()

    # -- producers (hot-loop safe) ----------------------------------------

    def _tid(self, track: str | None) -> int:
        if track is None:
            return threading.get_ident() % 100_000
        tid = self._tracks.get(track)
        if tid is None:
            with self._tracks_lock:
                tid = self._tracks.setdefault(track,
                                              1000 + len(self._tracks))
        return tid

    def complete(self, name: str, t0_perf: float, dur_s: float,
                 track: str | None = None, args: dict | None = None) -> None:
        """One complete ("X") event on the perf_counter timebase."""
        if not self.enabled:
            return
        self._events.append(("perf", name, t0_perf, dur_s,
                             self._tid(track), args))

    def span(self, name: str, track: str | None = None,
             args: dict | None = None):
        """``with ring.span("dispatch", track, {"it": 7}):`` — one ring
        event and one profiler annotation over the block (module
        docstring).  Not live: the shared no-op, for one attribute
        check."""
        if not self.live:
            return _NO_SPAN
        return _Span(self, name, track, args)

    def annotate(self, on: bool) -> None:
        """A profiler trace starts (ends): spans hold their annotation
        even where the ring itself records nothing."""
        self.live = self.enabled or bool(on)

    def complete_wall(self, name: str, t0_wall: float, dur_s: float,
                      track: str | None = None,
                      args: dict | None = None) -> None:
        """One complete event whose start is a WALL timestamp (lineage
        hops stamped in another process)."""
        if not self.enabled:
            return
        self._events.append(("wall", name, t0_wall, dur_s,
                             self._tid(track), args))

    def instant(self, name: str, track: str | None = None,
                args: dict | None = None) -> None:
        if not self.enabled:
            return
        self._events.append(("perf", name, time.perf_counter(), None,
                             self._tid(track), args))

    # -- dump --------------------------------------------------------------

    def _to_wall(self, timebase: str, t: float) -> float:
        if timebase == "wall":
            return t
        return self._anchor_wall + (t - self._anchor_perf)

    def to_chrome(self) -> dict:
        """Chrome trace-event JSON (ts/dur in wall microseconds) with the
        clock anchor + label in metadata."""
        pid = os.getpid()
        events: list[dict] = [
            {"ph": "M", "pid": pid, "tid": 0, "name": "process_name",
             "args": {"name": self.label}},
        ]
        with self._tracks_lock:
            tracks = dict(self._tracks)
        for track, tid in tracks.items():
            events.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_name", "args": {"name": track}})
        for timebase, name, t0, dur, tid, args in list(self._events):
            ev = {"name": name, "pid": pid, "tid": tid,
                  "ts": round(self._to_wall(timebase, t0) * 1e6, 1)}
            if dur is None:
                ev["ph"] = "i"
                ev["s"] = "t"
            else:
                ev["ph"] = "X"
                ev["dur"] = round(dur * 1e6, 1)
            if args:
                ev["args"] = args
            events.append(ev)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "metadata": {
                "label": self.label, "pid": pid,
                "clock_sync": {"wall": self._anchor_wall,
                               "perf": self._anchor_perf},
            },
        }

    def dump(self, path: str) -> None:
        """Atomic write (readers of a mid-run flush never see a torn
        file)."""
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(self.to_chrome(), fh)
        os.replace(tmp, path)


# -- process-global ring ----------------------------------------------------

_RING: TraceRing | None = None
_RING_LOCK = threading.Lock()
_FLUSHER: threading.Thread | None = None


def trace_dir() -> str | None:
    return os.environ.get(TRACE_DIR_ENV) or None


def _ring_path() -> str | None:
    d = trace_dir()
    if d is None or _RING is None:
        return None
    label = _RING.label.replace("/", "_")
    return os.path.join(d, f"trace-{label}-{os.getpid()}.json")


def dump_ring() -> str | None:
    """Flush the process ring to its trace file; returns the path (None
    when disabled).  Never raises — observability must not kill a run."""
    path = _ring_path()
    if path is None or not _RING.enabled:
        return None
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        _RING.dump(path)
        return path
    except OSError:
        return None


def _flusher_loop(interval_s: float) -> None:
    while True:
        time.sleep(interval_s)
        dump_ring()


def _install_triggers() -> None:
    global _FLUSHER
    atexit.register(dump_ring)
    interval = float(os.environ.get(FLUSH_ENV, "10"))
    if interval > 0 and _FLUSHER is None:
        _FLUSHER = threading.Thread(target=_flusher_loop, args=(interval,),
                                    daemon=True, name="apex-trace-flush")
        _FLUSHER.start()
    try:
        # SIGUSR2 -> on-demand dump (main thread only; worker children
        # spawned by mp enter here on their own main threads)
        signal.signal(signal.SIGUSR2, lambda *_: dump_ring())
    except (ValueError, OSError, AttributeError):
        pass                        # non-main thread / platform without it


def get_ring() -> TraceRing:
    """The process's trace ring — a real one when ``APEX_TRACE_DIR`` is
    set, else a disabled stub (every producer call is one attr check)."""
    global _RING
    if _RING is not None:
        return _RING
    with _RING_LOCK:
        if _RING is None:
            d = trace_dir()
            _RING = TraceRing(
                label=f"pid{os.getpid()}",
                enabled=d is not None,
                capacity=int(os.environ.get(CAPACITY_ENV, "65536")))
            if d is not None:
                _install_triggers()
    return _RING


def set_process_label(label: str) -> None:
    """Name this process's trace track by its role identity ("actor-3",
    "learner") — the merge tool joins these against the fleet registry's
    peer identities for clock-offset correction."""
    get_ring().label = label


def reset_for_tests() -> None:
    """Drop the process-global ring (tests re-enter with fresh env)."""
    global _RING
    with _RING_LOCK:
        _RING = None
