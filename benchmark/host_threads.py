"""What the learner process's other threads, its garbage collector and its
trace exporter did while the loop ran, read two ways:

1. **ring events over the window**: the ``gc`` spans (one a collection,
   on the thread that set it off) laid under the loop thread's phases
   (:func:`gc_pauses`), and the exporter's ``ring_flush`` spans
   (:func:`flushes`);
2. **host-plane annotations over the traced span** (:func:`idle_by_threads`):
   the device's idle gaps laid under the loop thread's innermost span AND
   the innermost span open on the other threads' lines (the staging
   thread's ``stage`` / ``publish`` / ``prio_writeback``, the exporter's
   ``ring_flush``, a ``gc`` on any thread: a collection holds the
   interpreter lock, so it stops the loop wherever it runs).

It reads ``benchmark.spans``' reductions and changes none of them.  Every
function returns ``None`` where the program has no such span (a checkout
from before they existed), and the readers then report nothing.
"""

from __future__ import annotations

import bisect

from benchmark import spans

GC = "gc"
FLUSH = "ring_flush"
#: the loop thread's phases: ``spans.LOOP_SPANS`` and the wait for the
#: update before last (``in_flight_wait``, which those leave under
#: ``loop_iter (own)``)
LOOP_SPANS = spans.LOOP_SPANS + ("in_flight_wait",)
#: the staging thread's work that ``idle_staging_pct`` counts
STAGING = ("stage", "publish")
#: spans of the threads beside the loop's that may own idle time
OTHER_SPANS = STAGING + ("prio_writeback", "rollout_dispatch", GC, FLUSH)
NONE = "-"


# -- 1. ring events over the window --------------------------------------------------

def _named(events: list[dict], name: str) -> list[dict]:
    return [ev for ev in events if ev["name"] == name and ev.get("ph") == "X"]


def flushes(events: list[dict]) -> dict | None:
    """The exporter's flushes among the window's ring events: count,
    seconds, events and bytes written.  ``None`` without one."""
    got = _named(events, FLUSH)
    if not got:
        return None
    args = [ev.get("args") or {} for ev in got]
    return dict(n=len(got), s=sum(ev["dur"] for ev in got) / 1e6,
                longest_s=max(ev["dur"] for ev in got) / 1e6,
                events=sum(a.get("events", 0) for a in args),
                bytes=sum(a.get("bytes", 0) for a in args))


def _loop_segments(events: list[dict]) -> list[tuple]:
    """The loop thread's phases in the ring as disjoint ``(start, end,
    name)`` segments (microseconds), each owned by the innermost span."""
    passes = _named(events, spans.LOOP)
    if not passes:
        return []
    tid = passes[0]["tid"]
    return spans._leaf_segments([
        (ev["ts"], ev["ts"] + ev["dur"], ev["name"]) for ev in events
        if ev.get("ph") == "X" and ev["tid"] == tid
        and ev["name"] in LOOP_SPANS])


def _phase(name: str) -> str:
    return spans.LOOP + " (own)" if name == spans.LOOP else name


def gc_pauses(events: list[dict]) -> dict | None:
    """The window's collections: count and seconds, by generation, and by
    the loop thread's innermost phase they fell in (``loop_iter (own)``
    where no child was open, ``outside any pass`` where no pass was).
    ``None`` without a ``gc`` span."""
    pauses = _named(events, GC)
    if not pauses:
        return None
    segments = _loop_segments(events)
    starts = [s[0] for s in segments]
    by_phase: dict[str, float] = {}
    by_gen: dict[int, dict] = {}
    for ev in pauses:
        a, b = ev["ts"], ev["ts"] + ev["dur"]
        args = ev.get("args") or {}
        gen = by_gen.setdefault(args.get("gen", -1),
                                {"n": 0, "s": 0.0, "collected": 0})
        gen["n"] += 1
        gen["s"] += ev["dur"] / 1e6
        gen["collected"] += args.get("collected", 0) or 0
        under = 0.0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(segments) and segments[i][0] < b:
            s0, s1, name = segments[i]
            cut = min(b, s1) - max(a, s0)
            if cut > 0:
                key = _phase(name)
                by_phase[key] = by_phase.get(key, 0.0) + cut / 1e6
                under += cut
            i += 1
        if ev["dur"] - under > 0:
            by_phase["outside any pass"] = (by_phase.get("outside any pass",
                                                         0.0)
                                            + (ev["dur"] - under) / 1e6)
    return dict(n=len(pauses), s=sum(ev["dur"] for ev in pauses) / 1e6,
                by_gen=dict(sorted(by_gen.items())),
                by_phase=dict(sorted(by_phase.items(),
                                     key=lambda kv: -kv[1])))


# -- 2. host-plane annotations over the traced span ----------------------------------

def _label_at(segments: list[tuple], starts: list, t: int) -> str | None:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and segments[i][0] <= t < segments[i][1]:
        return segments[i][2]
    return None


def idle_by_threads(planes: list) -> dict | None:
    """The device's idle gaps in the traced span shared out by pairs
    (the loop thread's innermost span, the other threads' innermost spans
    joined by ``+``; ``-`` for none), clocks as written, and the idle
    seconds during which the staging thread's line had ``stage`` or
    ``publish`` open.  The staging thread's line is the one with the most
    ``stage`` annotations.  ``None`` where the trace has no ``loop_iter``
    or ``stage`` annotation, or no device operation."""
    plane, loop_line = spans._loop_line(planes)
    busy = []
    for dev in spans._device_planes(planes):
        ops = dev.line("XLA Ops") or dev.line("XLA Modules") or []
        busy += [(a, a + d) for _m, a, d, _r in ops]
    if loop_line is None or not busy:
        return None

    def annotations(events, names):
        return [(a, a + d, plane.meta(m)["name"]) for m, a, d, _r in events
                if plane.meta(m)["name"] in names]

    staging, best = None, 0
    others = []                     # each line's leaf segments
    for _name, events in plane.lines:
        if events is loop_line:
            others.append(spans._leaf_segments(annotations(events, (GC,))))
            continue
        n = sum(1 for m, *_ in events if plane.meta(m)["name"] == "stage")
        if n > best:
            staging, best = events, n
        others.append(spans._leaf_segments(annotations(events, OTHER_SPANS)))
    if staging is None:
        return None
    loop = spans._leaf_segments(annotations(loop_line, LOOP_SPANS))
    work = spans._merge([(a, b) for a, b, _n in
                         annotations(staging, STAGING)])
    work = [(a, b, "staging") for a, b in work]
    lines = [(segs, [s[0] for s in segs]) for segs in [loop, work] + others]
    busy = spans._merge(busy)
    gaps = [(a, b) for (_a0, a), (b, _b1) in zip(busy, busy[1:])]
    cuts = sorted({t for segs, _s in lines for s in segs for t in s[:2]})
    idle = staging_ps = 0
    pairs: dict[tuple[str, str], int] = {}
    for a, b in gaps:
        idle += b - a
        inner = cuts[bisect.bisect_right(cuts, a):bisect.bisect_left(cuts, b)]
        for t0, t1 in zip([a] + inner, inner + [b]):
            labels = [_label_at(segs, starts, t0) for segs, starts in lines]
            loop_name = labels[0]
            if labels[1] is not None:
                staging_ps += t1 - t0
            other = "+".join(sorted({x for x in labels[2:] if x}))
            key = (_phase(loop_name) if loop_name else NONE, other or NONE)
            pairs[key] = pairs.get(key, 0) + (t1 - t0)
    return dict(idle_s=idle / 1e12, staging_s=staging_ps / 1e12,
                stage_annotations=best,
                pairs=sorted(((k, v / 1e12) for k, v in pairs.items()),
                             key=lambda kv: -kv[1]))


# -- what the readers call ------------------------------------------------------------

def threads(ctx: dict) -> dict | None:
    got = spans.load(ctx)
    if "threads" not in got:
        got["threads"] = (idle_by_threads(got["planes"])
                          if got["planes"] is not None else None)
    return got["threads"]
