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

Flushes: atexit, a periodic flusher thread (every ``APEX_TRACE_FLUSH_S``,
default 10 — so SIGKILLed/terminated roles still leave everything up to
their last flush, the same evidence-survival discipline as
``fleet_summary.json``), and SIGUSR2 when the process's main thread can
install handlers.  A flush writes the events recorded since the previous
one, and only those, as one segment ``trace-<label>-<pid>.<n>.json`` (a
whole Chrome trace with the clock anchor; each event formatted straight
to JSON text, each ``args`` by the C encoder), so its cost follows what
it writes, not how long the process has run.  The segments a process
keeps on disk hold at most the ring's capacity of events: past it the
oldest are deleted.  The flush is itself a span, ``ring_flush``
(``events``, ``bytes``), on the track ``trace-flush``.

While the ring is enabled, every garbage collection is a span ``gc``
(``gen``, ``collected``) on the thread that triggered it, from
``gc.callbacks``; no callback is registered otherwise.
"""

from __future__ import annotations

import atexit
import gc
import json
import os
import signal
import sys
import threading
import time
from collections import deque

_ENCODE = json.JSONEncoder(separators=(",", ":")).encode   # the C encoder

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
    """``jax.profiler.TraceAnnotation`` once the process has imported JAX;
    until then False, and nothing is imported: no profiler can run in a
    process without JAX, and a span may open inside a garbage
    collection."""
    global _ANNOTATION
    if _ANNOTATION is None:
        profiler = sys.modules.get("jax.profiler")
        if profiler is None:
            return False
        _ANNOTATION = profiler.TraceAnnotation
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
        self.capacity = capacity
        self._events: deque[tuple] = deque(maxlen=capacity)
        # the same events, until a flush takes them off the left
        self._unflushed: deque[tuple] = deque(maxlen=capacity)
        self._tracks: dict[str, int] = {}
        self._tracks_lock = threading.Lock()
        # wall<->perf anchor: dump converts perf-timebase events to wall
        self._anchor_wall = time.time()
        self._anchor_perf = time.perf_counter()
        self._flush_lock = threading.Lock()
        self._flushes = 0
        self._names: dict[str, str] = {}      # event name -> its JSON text
        self._segments: deque[tuple[str, int]] = deque()  # (path, events)
        self._on_disk = 0
        # events the last flush recorded itself (its span, a collection it
        # set off): a flush with nothing besides writes nothing
        self._own = 0

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
        ev = ("perf", name, t0_perf, dur_s, self._tid(track), args)
        self._events.append(ev)
        self._unflushed.append(ev)

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
        ev = ("wall", name, t0_wall, dur_s, self._tid(track), args)
        self._events.append(ev)
        self._unflushed.append(ev)

    def instant(self, name: str, track: str | None = None,
                args: dict | None = None) -> None:
        if not self.enabled:
            return
        ev = ("perf", name, time.perf_counter(), None, self._tid(track),
              args)
        self._events.append(ev)
        self._unflushed.append(ev)

    # -- dump --------------------------------------------------------------

    def _to_wall(self, timebase: str, t: float) -> float:
        if timebase == "wall":
            return t
        return self._anchor_wall + (t - self._anchor_perf)

    def to_chrome(self) -> dict:
        """Chrome trace-event JSON of the whole ring (ts/dur in wall
        microseconds) with the clock anchor + label in metadata."""
        return self._chrome(list(self._events))

    def _chrome(self, items: list[tuple]) -> dict:
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
        for timebase, name, t0, dur, tid, args in items:
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

    def _encoded(self, items: list[tuple]) -> list[str]:
        """The events of :meth:`_chrome`, each already JSON text: a flush
        spends its time under the interpreter lock, and formatting a
        string is cheaper than building a dict for the encoder."""
        pid = os.getpid()
        wall, perf = self._anchor_wall, self._anchor_perf
        names = self._names
        out = []
        for timebase, name, t0, dur, tid, args in items:
            n = names.get(name)
            if n is None:
                n = names[name] = _ENCODE(name)
            ts = (wall + (t0 - perf) if timebase == "perf" else t0) * 1e6
            rest = ',"args":' + _ENCODE(args) if args else ""
            if dur is None:
                out.append('{"name":%s,"pid":%d,"tid":%d,"ts":%.1f,'
                           '"ph":"i","s":"t"%s}' % (n, pid, tid, ts, rest))
            else:
                out.append('{"name":%s,"pid":%d,"tid":%d,"ts":%.1f,'
                           '"ph":"X","dur":%.1f%s}'
                           % (n, pid, tid, ts, dur * 1e6, rest))
        return out

    def flush(self, directory: str, wait: bool = True) -> str | None:
        """Write the events recorded since the last flush to a segment of
        their own under ``directory`` (atomic: a reader never sees a torn
        file), under a ``ring_flush`` span; delete the oldest segments past
        the ring's capacity.  Returns the path, or None where there was
        nothing new, or (``wait`` False) another flush holds the lock."""
        if not self._flush_lock.acquire(timeout=30.0 if wait else 0):
            return None
        try:
            n = len(self._unflushed)
            if n <= self._own:
                return None
            with self.span("ring_flush", "trace-flush") as span:
                batch = [self._unflushed.popleft() for _ in range(n)]
                head = self._chrome([])          # the metadata, the anchor
                text = ('{"traceEvents":[%s],"displayTimeUnit":"ms",'
                        '"metadata":%s}' % (",".join(
                            [_ENCODE(ev) for ev in head["traceEvents"]]
                            + self._encoded(batch)),
                            _ENCODE(head["metadata"])))
                self._flushes += 1
                path = os.path.join(
                    directory, f"trace-{self.label.replace('/', '_')}-"
                               f"{os.getpid()}.{self._flushes}.json")
                tmp = path + ".tmp"
                with open(tmp, "w", encoding="utf-8") as fh:
                    fh.write(text)
                os.replace(tmp, path)
                span.note(events=n, bytes=len(text))     # ASCII: one a char
            own = {self._tid("trace-flush"), self._tid(None)}
            self._own = sum(1 for ev in list(self._unflushed)
                            if ev[4] in own)
            self._segments.append((path, n))
            self._on_disk += n
            while self._on_disk > self.capacity:
                old, k = self._segments.popleft()
                self._on_disk -= k
                try:
                    os.remove(old)
                except FileNotFoundError:
                    pass
            return path
        finally:
            self._flush_lock.release()


# -- process-global ring ----------------------------------------------------

_RING: TraceRing | None = None
_RING_LOCK = threading.Lock()
_FLUSHER: threading.Thread | None = None


def trace_dir() -> str | None:
    return os.environ.get(TRACE_DIR_ENV) or None


def dump_ring(wait: bool = True) -> str | None:
    """Flush what the process ring recorded since its last flush to a new
    segment file; returns its path (None when disabled or nothing was
    new).  Never raises — observability must not kill a run."""
    d = trace_dir()
    if d is None or _RING is None or not _RING.enabled:
        return None
    try:
        os.makedirs(d, exist_ok=True)
        return _RING.flush(d, wait=wait)
    except OSError:
        return None


def _flusher_loop(interval_s: float) -> None:
    while True:
        time.sleep(interval_s)
        dump_ring()


_GC_SPAN = None         # the collection under way (one at a time)


def _gc_span(phase: str, info: dict) -> None:
    """``gc.callbacks`` entry: a ``gc`` span from a collection's start to
    its stop, on the thread that triggered it."""
    global _GC_SPAN
    ring = _RING
    if ring is None:                # reset, or the interpreter shutting down
        return
    if phase == "start":
        _GC_SPAN = ring.span("gc", args={"gen": info["generation"]})
        _GC_SPAN.__enter__()
    elif _GC_SPAN is not None:
        span, _GC_SPAN = _GC_SPAN, None
        span.note(collected=info["collected"])
        span.__exit__(None, None, None)


def _install_triggers() -> None:
    global _FLUSHER
    atexit.register(dump_ring)
    gc.callbacks.append(_gc_span)
    interval = float(os.environ.get(FLUSH_ENV, "10"))
    if interval > 0 and _FLUSHER is None:
        _FLUSHER = threading.Thread(target=_flusher_loop, args=(interval,),
                                    daemon=True, name="apex-trace-flush")
        _FLUSHER.start()
    try:
        # SIGUSR2 -> on-demand dump (main thread only; worker children
        # spawned by mp enter here on their own main threads); it never
        # waits, for the main thread may hold the flush already
        signal.signal(signal.SIGUSR2, lambda *_: dump_ring(wait=False))
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
    """Drop the process-global ring and its collection callback (tests
    re-enter with fresh env)."""
    global _RING, _GC_SPAN
    with _RING_LOCK:
        while _gc_span in gc.callbacks:
            gc.callbacks.remove(_gc_span)
        _GC_SPAN = None
        _RING = None
