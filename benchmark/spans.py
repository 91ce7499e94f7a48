"""What the program's own spans and scopes say, read three ways:

1. **ring events by name over the window** (:func:`loop_phases`,
   :func:`policy_lag`): the learner loop's ``loop_iter`` passes and their
   children (``poll_slot``, ``dispatch_key``, ``beta``, ``dispatch``, ...)
   in the program's trace ring (``apex_tpu.obs.trace``), and the
   ``consume`` events of the chunk-lineage join;
2. **host-plane annotations by name over the traced span**
   (:func:`host_attribution`): the same spans as the profiler recorded
   them (``jax.profiler.TraceAnnotation``, on the device trace's clock),
   laid over the device's idle intervals and beside its ``XLA Modules``;
3. **device seconds by scope** (:func:`device_scopes`): each operation of
   the step programs grouped under the first of ``jax.named_scope``'s
   five names on its ``tf_op`` path.

``jax.profiler.ProfileData`` gives an ``XLA Ops`` event its times only;
the scope path is a stat of the event's *metadata*, which it does not
expose.  So this module reads that much of the ``.xplane.pb`` wire format
itself (XSpace -> XPlane -> lines/events, ``event_metadata`` ->
``stats``): varints and length-delimited fields, standard library only.

``ctx`` (``harness.layer_context``) holds neither the ring nor the
profile directory, so :func:`load` fetches both.  Every reduction returns
``None`` where the program has no such span or scope (a checkout from
before they existed), and the readers in ``metrics/`` then report
nothing.

``python benchmark/spans.py <file.xplane.pb>`` prints reductions 2 and 3;
``--check`` compares them with the numbers recorded beside the fixture.
"""

from __future__ import annotations

import bisect
import json
import math
import os
import re
import statistics
import struct
import sys

SCOPES = ("ingest", "sample", "gather", "update", "writeback")
LOOP = "loop_iter"
#: the spans of the loop's thread that may own idle time (``host_gap`` is
#: not among them: it is written after the fact and overlaps the rest)
LOOP_SPANS = (LOOP, "poll_slot", "dispatch_key", "beta", "dispatch", "adopt",
              "obs_join", "publish_handoff", "drain_stats", "health_tick",
              "log_scalars", "checkpoint", "ratio_sleep")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = "/host:CPU"


# -- the wire format -----------------------------------------------------------

def _fields(buf):
    """``(field number, wire type, value)`` of one message: an int for a
    varint, a memoryview for the other kinds."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        number, kind = key >> 3, key & 7
        if kind == 0:
            value = shift = 0
            while True:
                b = buf[i]
                i += 1
                value |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield number, kind, value
        elif kind == 2:
            size = shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield number, kind, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            yield number, kind, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {kind} in an xplane file")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stats(raw: list, stat_names: dict) -> dict:
    """XStat messages -> ``{stat name: value}``."""
    out = {}
    for buf in raw:
        key = value = None
        for number, _kind, v in _fields(buf):
            if number == 1:
                key = v
            elif number == 2:
                value = struct.unpack("<d", v)[0]
            elif number == 3:
                value = v
            elif number == 4:
                value = _signed(v)
            elif number in (5, 6):
                value = _text(v)
            elif number == 7:                  # a reference to a stat name
                value = stat_names.get(v, v)
        out[stat_names.get(key, key)] = value
    return out


class Plane:
    """One XPlane: ``lines`` are ``(name, [event, ...])`` with an event
    ``(metadata id, start ps, duration ps, raw stats)``; metadata and
    stats are decoded on request."""

    def __init__(self, buf):
        self.name, self.lines = "", []
        self._meta_raw, self._meta, self.stat_names = {}, {}, {}
        for number, _kind, v in _fields(buf):
            if number == 2:
                self.name = _text(v)
            elif number == 3:
                self.lines.append(self._line(v))
            elif number == 4:                   # map<int64, XEventMetadata>
                entry = {n: x for n, _k, x in _fields(v)}
                if 2 in entry:
                    self._meta_raw[entry.get(1, 0)] = entry[2]
            elif number == 5:                   # map<int64, XStatMetadata>
                entry = {n: x for n, _k, x in _fields(v)}
                if 2 in entry:
                    meta = {n: x for n, _k, x in _fields(entry[2])}
                    self.stat_names[entry.get(1, 0)] = _text(meta.get(2, b""))

    @staticmethod
    def _line(buf):
        name, t0_ns, events = "", 0, []
        for number, _kind, v in _fields(buf):
            if number == 2:
                name = _text(v)
            elif number == 3:
                t0_ns = _signed(v)
            elif number == 4:
                meta = offset = dur = 0
                raw = []
                for n, _k, x in _fields(v):
                    if n == 1:
                        meta = x
                    elif n == 2:
                        offset = _signed(x)
                    elif n == 3:
                        dur = x
                    elif n == 4:
                        raw.append(x)
                events.append((meta, offset, dur, raw))
        t0_ps = t0_ns * 1000
        return name, [(m, t0_ps + o, d, raw) for m, o, d, raw in events]

    def meta(self, meta_id: int) -> dict:
        """``{"name", "stats"}`` of one event metadata."""
        got = self._meta.get(meta_id)
        if got is None:
            name, raw = "", []
            for number, _kind, v in _fields(self._meta_raw.get(meta_id, b"")):
                if number == 2:
                    name = _text(v)
                elif number == 5:
                    raw.append(v)
            got = self._meta[meta_id] = {
                "name": name, "stats": _stats(raw, self.stat_names)}
        return got

    def line(self, *names: str):
        return next((evs for name, evs in self.lines if name in names), None)


def read_xspace(path: str) -> list[Plane]:
    with open(path, "rb") as f:
        data = memoryview(f.read())
    return [Plane(v) for number, _kind, v in _fields(data) if number == 1]


# -- intervals -----------------------------------------------------------------------

def _merge(spans: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(spans):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _self_times(events: list[tuple[int, int]]) -> tuple[list[int], list[int]]:
    """Events ``(start, end)`` sorted by start (a parent before its
    children) -> each one's own time (its duration less what its children
    cover) and its parent's position (-1 at the top)."""
    own = [b - a for a, b in events]
    parent = [-1] * len(events)
    stack: list[int] = []
    for i, (a, b) in enumerate(events):
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            parent[i] = stack[-1]
            own[stack[-1]] -= min(b, events[stack[-1]][1]) - a
        stack.append(i)
    return own, parent


def _leaf_segments(spans: list[tuple[int, int, str]]):
    """Nested named spans of one thread -> disjoint ``(start, end, name)``
    segments, each owned by the innermost span open there."""
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    out, stack = [], []

    def emit(a, b, name):
        if b > a:
            out.append((a, b, name))

    cursor = None
    for a, b, name in spans:
        while stack and stack[-1][1] <= a:
            top = stack.pop()
            emit(cursor, top[1], top[2])
            cursor = top[1]
        if stack:
            emit(cursor, a, stack[-1][2])
        cursor = a
        stack.append((a, b, name))
    while stack:
        top = stack.pop()
        emit(cursor, top[1], top[2])
        cursor = top[1]
    return out


def _quantile(values: list[float], q: float) -> float:
    """Nearest-rank quantile of a non-empty list."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


# -- 1. ring events by name over the window ----------------------------------------------

def ring_window(chrome: dict, lo_wall: float, hi_wall: float) -> list[dict]:
    lo, hi = lo_wall * 1e6, hi_wall * 1e6
    return [ev for ev in chrome.get("traceEvents", ())
            if ev.get("ph") in ("X", "i") and lo <= ev.get("ts", -1) <= hi]


def loop_phases(events: list[dict]) -> dict | None:
    """The loop's passes and phases among the ring's events: seconds and
    count by name, ``loop_iter``'s own time (what no child covers),
    dispatches by program, passes by kind.  ``None`` without a pass."""
    passes = [ev for ev in events
              if ev["name"] == LOOP and ev.get("ph") == "X"]
    if not passes:
        return None
    tid = passes[0]["tid"]
    by_name: dict[str, dict] = {}
    children, programs, kinds = [], {}, {}
    for ev in events:
        if ev.get("ph") != "X" or ev["tid"] != tid \
                or ev["name"] not in LOOP_SPANS:
            continue
        agg = by_name.setdefault(ev["name"], {"n": 0, "s": 0.0})
        agg["n"] += 1
        agg["s"] += ev["dur"] / 1e6
        args = ev.get("args") or {}
        if ev["name"] == LOOP:
            kind = args.get("kind", "?")
            kinds[kind] = kinds.get(kind, 0) + 1
            continue
        children.append((ev["ts"], ev["ts"] + ev["dur"]))
        if ev["name"] == "dispatch":
            p = programs.setdefault(args.get("program", "?"),
                                    {"n": 0, "s": 0.0})
            p["n"] += 1
            p["s"] += ev["dur"] / 1e6
    covered = _merge(children)
    starts = [a for a, _ in covered]
    own = 0.0
    for ev in passes:
        a, b = ev["ts"], ev["ts"] + ev["dur"]
        own += b - a
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(covered) and covered[i][0] < b:
            own -= max(0.0, min(b, covered[i][1]) - max(a, covered[i][0]))
            i += 1
    return dict(by_name=by_name, loop_self_s=own / 1e6, programs=programs,
                kinds=kinds)


def policy_lag(events: list[dict]) -> list[float]:
    """``lag_steps`` of the window's ``consume`` events."""
    return [float(ev["args"]["lag_steps"]) for ev in events
            if ev["name"] == "consume"
            and (ev.get("args") or {}).get("lag_steps") is not None]


# -- 2. host-plane annotations over the traced span ------------------------------------------

def _device_planes(planes: list[Plane]) -> list[Plane]:
    return [p for p in planes if DEVICE_PLANE.match(p.name)]


def _loop_line(planes: list[Plane]):
    """The host thread that ran the loop: the line of the host plane with
    the most ``loop_iter`` annotations, as ``(plane, events)``."""
    best = (0, None, None)
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for _name, events in plane.lines:
            n = sum(1 for m, *_ in events if plane.meta(m)["name"] == LOOP)
            if n > best[0]:
                best = (n, plane, events)
    return best[1], best[2]


def _program(name: str) -> str:
    return name.split("(", 1)[0].strip()


def host_attribution(planes: list[Plane]) -> dict | None:
    """The loop thread's annotations against the device: idle seconds by
    the innermost span open meanwhile, what ran inside ``dispatch``, and
    each ``dispatch`` beside the device program it issued.  ``None``
    where the trace has no ``loop_iter`` annotation or no device
    operation.

    Both sides are read on the clocks the profiler wrote.  It lines the
    device's clock up with the host's anew in every session, to some
    tenths of a millisecond: where a program reads as started before the
    ``dispatch`` that issued it (``one_clock``:
    ``started_before_dispatch``), the device's clock is behind by at least
    ``clock_skew_floor_us``.  So the idle seconds are shared out twice:
    on the clocks as written (``idle_by_span``, ``idle_unattributed_s``:
    what the metric reads) and with the device's times moved later by
    that floor (``..._moved``); the distance between the two is what the
    clocks leave open, and the truth lies at the second or beyond it.
    Idle time under ``loop_iter`` alone (no child span open) is listed,
    and counts as unattributed: the pass wraps the whole loop body, so it
    names no phase."""
    plane, line = _loop_line(planes)
    busy, modules = [], []
    for dev in _device_planes(planes):
        ops = dev.line("XLA Ops") or dev.line("XLA Modules") or []
        busy += [(a, a + d) for _m, a, d, _r in ops]
        modules += [(a, a + d, _program(dev.meta(m)["name"]))
                    for m, a, d, _r in dev.line("XLA Modules") or []]
    if line is None or not busy:
        return None
    named, inside, dispatches, counts = [], {}, [], {}
    spans = []
    for m, a, d, raw in line:
        name = plane.meta(m)["name"]
        spans.append((a, a + d, name, raw))
        if name in LOOP_SPANS:
            named.append((a, a + d, name))
            counts[name] = counts.get(name, 0) + 1
            if name == "dispatch":
                stats = _stats(raw, plane.stat_names)
                dispatches.append((a, a + d, str(stats.get("program", "?"))))
    dispatches.sort()
    clock = _one_clock(dispatches, sorted(modules))
    skew = 0.0
    if clock is not None and clock["offset_us_min"] is not None:
        skew = max(0.0, -clock["offset_us_min"])
    segments = _leaf_segments(named)
    idle_ps, by_span, loose = _idle_by_span(_merge(busy), segments)
    _, by_span_moved, loose_moved = _idle_by_span(
        _merge([(a + round(skew * 1e6), b + round(skew * 1e6))
                for a, b in busy]), segments)
    # host events inside the dispatch calls, by name
    d_starts = [d[0] for d in dispatches]
    for a, b, name, _raw in spans:
        if name in LOOP_SPANS:
            continue
        i = bisect.bisect_right(d_starts, a) - 1
        if i >= 0 and b <= dispatches[i][1]:
            agg = inside.setdefault(name, {"n": 0, "s": 0.0})
            agg["n"] += 1
            agg["s"] += (b - a) / 1e12
    return dict(
        idle_s=idle_ps / 1e12, idle_unattributed_s=loose / 1e12,
        idle_by_span=by_span, clock_skew_floor_us=skew,
        idle_unattributed_moved_s=loose_moved / 1e12,
        idle_by_span_moved=by_span_moved, annotations=counts,
        inside_dispatch=sorted(inside.items(), key=lambda kv: -kv[1]["s"]),
        one_clock=clock)


def _idle_by_span(busy: list, segments: list) -> tuple[int, dict, int]:
    """The gaps between the device's merged busy intervals laid under the
    loop thread's leaf segments: picoseconds idle, seconds by the
    innermost span open meanwhile (``loop_iter`` alone as ``loop_iter
    (own)``), and the picoseconds under no phase: under no span at all,
    or under ``loop_iter`` alone."""
    seg_starts = [s[0] for s in segments]
    by_span: dict[str, float] = {}
    idle_total = attributed = 0
    for (_a0, a), (b, _b1) in zip(busy, busy[1:]):
        idle_total += b - a
        i = max(0, bisect.bisect_right(seg_starts, a) - 1)
        while i < len(segments) and segments[i][0] < b:
            s0, s1, name = segments[i]
            cut = min(b, s1) - max(a, s0)
            if cut > 0:
                key = name if name != LOOP else LOOP + " (own)"
                by_span[key] = by_span.get(key, 0.0) + cut / 1e12
                attributed += cut if name != LOOP else 0
            i += 1
    return (idle_total,
            dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
            idle_total - attributed)


def _one_clock(dispatches: list, modules: list) -> dict | None:
    """``dispatch`` annotations against the device's ``XLA Modules`` events
    of the programs they name, in order.  The two sequences are aligned by
    the programs' names alone (a run may start or end between a dispatch
    and its execution: up to 3 at either edge), then the offsets say
    whether the clocks agree: no program may start before its dispatch."""
    named = {p for _a, _b, p in dispatches}
    mods = [m for m in modules if m[2] in named]
    if not dispatches or not mods:
        return None
    best = None
    for shift in range(-3, 4):          # module i + shift <-> dispatch i
        pairs = [(d, mods[i + shift]) for i, d in enumerate(dispatches)
                 if 0 <= i + shift < len(mods)]
        wrong = sum(1 for d, m in pairs if d[2] != m[2])
        edge = len(dispatches) + len(mods) - 2 * len(pairs)
        key = (wrong, abs(shift))
        if pairs and (best is None or key < best[0]):
            best = (key, shift, pairs, edge)
    (wrong, _), shift, pairs, edge = best
    offsets = [(m[0] - d[0]) / 1e6 for d, m in pairs if d[2] == m[2]]
    return dict(dispatches=len(dispatches), modules=len(mods), shift=shift,
                paired=len(pairs), mismatched=wrong, unpaired_at_edges=edge,
                started_before_dispatch=sum(1 for o in offsets if o < 0),
                offset_us_median=statistics.median(offsets) if offsets
                else None,
                offset_us_p95=_quantile(offsets, 0.95) if offsets else None,
                offset_us_min=min(offsets) if offsets else None)


# -- 3. device seconds by scope ------------------------------------------------------------

def scope_of(tf_op: str | None) -> str | None:
    """The first of the five names on an operation's ``tf_op`` path
    (``jit(fused_step)/update/loss_grad/jvp(DuelingDQN)/Conv_0/
    conv_general_dilated:``); the last component is the primitive's name
    and never a scope."""
    if not tf_op:
        return None
    parts = tf_op.split(":", 1)[0].split("/")
    for part in parts[:-1]:
        if part in SCOPES:
            return part
    return None


def device_scopes(planes: list[Plane], programs) -> dict | None:
    """Device seconds of the named programs' operations by scope.  An
    operation counts with its own time (a ``while`` less its body's
    operations); one without a scope of its own takes that of the
    operation it is nested in; ``totals`` is :func:`scope_totals` of the
    rest.  ``None`` where no operation of those programs carries a
    scope."""
    programs = set(programs)
    by_program: dict[str, dict] = {}
    left: dict[str, dict] = {}
    for dev in _device_planes(planes):
        mods = sorted((a, a + d, _program(dev.meta(m)["name"]))
                      for m, a, d, _r in dev.line("XLA Modules") or [])
        mods = [m for m in mods if m[2] in programs]
        ops = sorted(((a, a + d, m) for m, a, d, _r
                      in dev.line("XLA Ops") or []),
                     key=lambda e: (e[0], -e[1]))
        if not mods or not ops:
            continue
        for _a, b, name in mods:
            agg = by_program.setdefault(name, {
                "calls": 0, "seconds": 0.0, "unscoped_s": 0.0,
                "scopes": dict.fromkeys(SCOPES, 0.0)})
            agg["calls"] += 1
            agg["seconds"] += (b - _a) / 1e12
        own, parent = _self_times([(a, b) for a, b, _m in ops])
        m_starts = [m[0] for m in mods]
        scopes: list[str | None] = []
        for i, (a, b, meta_id) in enumerate(ops):
            meta = dev.meta(meta_id)
            scope = scope_of(meta["stats"].get("tf_op"))
            if scope is None and parent[i] >= 0:
                scope = scopes[parent[i]]
            scopes.append(scope)
            j = bisect.bisect_right(m_starts, a) - 1
            if j < 0 or a >= mods[j][1]:
                continue                      # another program's operation
            agg = by_program[mods[j][2]]
            if scope is None:
                agg["unscoped_s"] += own[i] / 1e12
                rest = left.setdefault(meta["name"], {
                    "s": 0.0, "n": 0,
                    "source": meta["stats"].get("source"),
                    "category": meta["stats"].get("hlo_category")})
                rest["s"] += own[i] / 1e12
                rest["n"] += 1
            else:
                agg["scopes"][scope] += own[i] / 1e12
    if not any(s > 0 for p in by_program.values()
               for s in p["scopes"].values()):
        return None
    red = dict(programs=by_program,
               unscoped_ops=sorted(left.items(),
                                   key=lambda kv: -kv[1]["s"])[:12])
    return dict(red, totals=scope_totals(red))


def scope_totals(red: dict) -> dict:
    """Over the step programs together: seconds and calls by scope (the
    calls of the programs that contain it); the programs' device seconds
    (``module_s``, the sum of their ``XLA Modules`` events) and what of
    them lies under no scope: operations without one (``unscoped_ops_s``)
    and the time between operations (``between_ops_s``)."""
    out = {s: {"s": 0.0, "calls": 0} for s in SCOPES}
    unscoped = seconds = calls = 0.0
    for prog in red["programs"].values():
        unscoped += prog["unscoped_s"]
        seconds += prog["seconds"]
        calls += prog["calls"]
        for s in SCOPES:
            if prog["scopes"][s] > 0:
                out[s]["s"] += prog["scopes"][s]
                out[s]["calls"] += prog["calls"]
    scoped = sum(v["s"] for v in out.values())
    return dict(scopes=out, scoped_s=scoped, unscoped_ops_s=unscoped,
                between_ops_s=seconds - scoped - unscoped,
                module_s=seconds, calls=int(calls))


# -- what the readers call -------------------------------------------------------------------

def load(ctx: dict) -> dict:
    """The run's ring events over the window, and its profiler trace's
    planes; fetched once a run and kept in ``ctx``."""
    got = ctx.get("_spans")
    if got is not None:
        return got
    got = ctx["_spans"] = {"ring": [], "planes": None}
    try:
        from apex_tpu.obs.trace import get_ring
        got["ring"] = ring_window(get_ring().to_chrome(),
                                  ctx["open"]["wall"], ctx["close"]["wall"])
    except Exception as e:                     # a program without the ring
        ctx["say"](f"spans: no trace ring to read ({e!r})")
    if ctx.get("trace") is not None:
        from benchmark import xplane
        from benchmark.harness import RUN_DIR
        path = xplane.find_xplane(os.path.join(RUN_DIR, "profile"))
        if path is not None:
            got["planes"] = read_xspace(path)
    return got


def phases(ctx: dict) -> dict | None:
    got = load(ctx)
    if "phases" not in got:
        got["phases"] = loop_phases(got["ring"])
    return got["phases"]


def share(ctx: dict, *names: str) -> float | None:
    """The named phases' seconds as a share of the window, in percent."""
    red = phases(ctx)
    if red is None:
        return None
    return 100.0 * sum(red["by_name"].get(n, {"s": 0.0})["s"]
                       for n in names) / ctx["window_s"]


def attribution(ctx: dict) -> dict | None:
    got = load(ctx)
    if "host" not in got:
        got["host"] = (host_attribution(got["planes"])
                       if got["planes"] is not None else None)
    return got["host"]


def scopes(ctx: dict) -> dict | None:
    got = load(ctx)
    if "scopes" not in got:
        got["scopes"] = (
            device_scopes(got["planes"], ctx["traffic"]["step_programs"])
            if got["planes"] is not None else None)
    return got["scopes"]


def scope_ms(ctx: dict, scope: str) -> float | None:
    """Device milliseconds of one scope per call of the step programs that
    contain it."""
    red = scopes(ctx)
    if red is None:
        return None
    got = red["totals"]["scopes"][scope]
    if not got["calls"]:
        return None
    return 1000.0 * got["s"] / got["calls"]


# -- the command -----------------------------------------------------------------------------

def reduce_file(path: str, programs) -> dict:
    planes = read_xspace(path)
    return dict(host=host_attribution(planes),
                scopes=device_scopes(planes, programs))


def main(argv: list[str]) -> int:
    check = "--check" in argv
    paths = [a for a in argv if not a.startswith("--")]
    path = paths[0] if paths else os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tests", "fixtures",
        "scoped.xplane.pb")
    red = reduce_file(path, ("jit_fused_step", "jit_train_step"))
    print(json.dumps(red, indent=1))
    if check:
        with open(path[:-len(".xplane.pb")] + ".expected.json") as f:
            want = json.load(f)["reduced"]
        if json.loads(json.dumps(red)) != want:
            print("MISMATCH with " + os.path.basename(path))
            return 1
        print("scoped fixture check passed")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
