"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read: device busy seconds, device time per XLA program,
the operations that took most time, and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` alone.  On a TPU the device planes
are named ``/device:TPU:<n>``; their ``XLA Modules`` line carries one
event per program execution (``jit_train_step(<fingerprint>)``) and the
``XLA Ops`` line one per operation.  Busy time is the union of the
intervals on the operations line (programs overlap their own ops, so the
module line alone would hide gaps inside a program; where there is no
operations line the module line stands in).

``python benchmark/xplane.py <file.xplane.pb>`` prints the reduction;
``--check`` compares it with the numbers recorded beside the fixture.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def short_name(name: str) -> str:
    """An operation's event name is its whole HLO line; keep the
    instruction's name, its result type and its opcode."""
    m = re.match(r"^(%[\w.\-]+) = (\(?[a-z0-9]+\[[^\]]*\])?[^ ]* ?.*? "
                 r"([a-z][a-z\-]*)\(", name)
    if m:
        return " ".join(x for x in m.groups() if x)[:120]
    return name[:120]


def _events(line) -> list[tuple[str, float, float]]:
    out = []
    for ev in line.events:
        start = float(ev.start_ns)
        out.append((short_name(ev.name), start,
                    start + float(ev.duration_ns)))
    return out


def _union_seconds(spans: list[tuple[float, float]]) -> float:
    total, end = 0.0, None
    for a, b in sorted(spans):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total / 1e9


def program_name(event_name: str) -> str:
    """``jit_train_step(123)`` -> ``jit_train_step``."""
    return event_name.split("(", 1)[0].strip()


def reduce_plane(plane) -> dict | None:
    lines = {line.name: line for line in plane.lines}
    ops_line = next((lines[n] for n in OPS_LINES if n in lines), None)
    mod_line = next((lines[n] for n in MODULE_LINES if n in lines), None)
    if ops_line is None and mod_line is None:
        return None
    ops = _events(ops_line) if ops_line is not None else []
    mods = _events(mod_line) if mod_line is not None else []
    busy_src = ops or mods
    if not busy_src:
        return None
    programs: dict[str, dict] = {}
    for name, a, b in mods:
        p = programs.setdefault(program_name(name),
                                {"calls": 0, "seconds": 0.0})
        p["calls"] += 1
        p["seconds"] += (b - a) / 1e9
    by_op: dict[str, float] = {}
    for name, a, b in ops:
        by_op[name] = by_op.get(name, 0.0) + (b - a) / 1e9
    # idle gaps between consecutive busy intervals, labelled by the
    # programs on either side (the host was between those two dispatches)
    gaps: dict[str, float] = {}
    order = sorted(mods or busy_src, key=lambda e: e[1])
    end, prev = None, None
    for name, a, b in order:
        if end is not None and a > end:
            label = f"{program_name(prev)}->{program_name(name)}"
            gaps[label] = gaps.get(label, 0.0) + (a - end) / 1e9
        if end is None or b > end:
            end, prev = b, name
    first = min(e[1] for e in busy_src)
    last = max(e[2] for e in busy_src)
    return dict(
        busy_s=_union_seconds([(a, b) for _, a, b in busy_src]),
        span_s=(last - first) / 1e9,
        programs=programs,
        top_ops=sorted(by_op.items(), key=lambda kv: -kv[1])[:10],
        idle_gaps=sorted(gaps.items(), key=lambda kv: -kv[1])[:10],
        n_op_events=len(ops), n_module_events=len(mods))


def reduce_file(path: str, chips: int | None = None) -> dict | None:
    """Reduction over the device planes of one trace; ``busy_s`` is the
    mean over the chips used (planes with no event count as idle when
    ``chips`` says they were in use)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = [reduce_plane(p) for p in data.planes
              if DEVICE_PLANE.match(p.name)]
    planes = [p for p in planes if p is not None]
    if not planes:
        return None
    n = max(chips or 0, len(planes))
    programs: dict[str, dict] = {}
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for p in planes:
        for name, v in p["programs"].items():
            agg = programs.setdefault(name, {"calls": 0, "seconds": 0.0})
            agg["calls"] += v["calls"]
            agg["seconds"] += v["seconds"]
        for name, s in p["top_ops"]:
            ops[name] = ops.get(name, 0.0) + s
        for name, s in p["idle_gaps"]:
            gaps[name] = gaps.get(name, 0.0) + s
    return dict(
        chips=n,
        busy_s=sum(p["busy_s"] for p in planes) / n,
        span_s=max(p["span_s"] for p in planes),
        programs=programs,
        top_ops=sorted(ops.items(), key=lambda kv: -kv[1])[:10],
        idle_gaps=sorted(gaps.items(), key=lambda kv: -kv[1])[:10],
        n_op_events=sum(p["n_op_events"] for p in planes),
        n_module_events=sum(p["n_module_events"] for p in planes))


def main(argv: list[str]) -> int:
    check = "--check" in argv
    paths = [a for a in argv if not a.startswith("--")]
    path = paths[0] if paths else os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tests", "fixtures",
        "small.xplane.pb")
    red = reduce_file(path)
    print(json.dumps(red, indent=1, default=list))
    if check:
        with open(os.path.splitext(os.path.splitext(path)[0])[0]
                  + ".expected.json") as f:
            want = json.load(f)
        for key in ("busy_s", "span_s", "n_op_events", "n_module_events"):
            if abs(red[key] - want[key]) > 1e-9 * max(1.0, abs(want[key])):
                print(f"MISMATCH {key}: {red[key]} != {want[key]}")
                return 1
        for name, v in want["programs"].items():
            got = red["programs"].get(name)
            if got is None or got["calls"] != v["calls"]:
                print(f"MISMATCH program {name}: {got} != {v}")
                return 1
        print("xplane fixture check passed")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
