"""Device seconds by the torso's trace scopes (``jax.named_scope`` in
``apex_tpu/models/glm4_moe_lite.py``: ``embed``, ``mla``, ``dense_ffn``,
``router``, ``experts``, ``shared_expert``, ``q_head``), program by
program, and the routing counters of the updates the window ran.

``spans.device_scopes`` groups by the step's five outer scopes (the first
on an operation's path); the torso's scopes lie inside ``update`` and
``rollout``, so this reads the same planes by the INNERMOST torso name on
the path (``experts`` lies inside ``router``).  An operation without one
takes that of the operation it is nested in (a ``while`` body's).  Where
the program has no such scope or counter, as a checkout from before
PR 29 has none, every function here returns ``None``.
"""

from __future__ import annotations

import bisect
import re
import statistics

from benchmark import spans

TORSO = ("embed", "mla", "dense_ffn", "router", "experts", "shared_expert",
         "q_head")


#: operation-name prefix -> scope, for kernels the compiler names itself
KERNELS = {"ragged-dot": "experts"}
_WRAPPED = re.compile(r"^(?:\w+\()+(.*?)\)+$")


def scope_of(tf_op: str | None) -> str | None:
    """The innermost torso scope on an operation's ``tf_op`` path.  A
    part a transform wrapped (``jvp(experts)``, ``transpose(jvp(mla))``)
    counts as the name inside."""
    if not tf_op:
        return None
    parts = tf_op.split(":", 1)[0].split("/")
    for part in reversed(parts[:-1]):
        inner = _WRAPPED.match(part)
        part = inner.group(1) if inner else part
        if part in TORSO:
            return part
    return None


def op_scope(name: str | None, tf_op: str | None) -> str | None:
    """The scope of one device operation: by its own name where the
    compiler named a kernel (``%ragged-dot-none.3 = f32[...] custom-call``),
    else by its path."""
    base = (name or "").lstrip("%")
    for prefix, scope in KERNELS.items():
        if base.startswith(prefix) or (tf_op or "").startswith(prefix):
            return scope
    return scope_of(tf_op)


def reduce_planes(planes, programs) -> dict | None:
    """``{program: {calls, seconds, scopes: {name: seconds}, kernels_s,
    rest}}`` for the named programs: ``kernels_s`` is what of the scopes'
    seconds was placed by an operation's name (``KERNELS``), ``rest`` the
    seconds of the operations under no torso scope, by name."""
    programs = set(programs)
    out: dict[str, dict] = {}
    for dev in spans._device_planes(planes):
        mods = sorted((a, a + d, spans._program(dev.meta(m)["name"]))
                      for m, a, d, _r in dev.line("XLA Modules") or [])
        mods = [m for m in mods if m[2] in programs]
        ops = sorted(((a, a + d, m) for m, a, d, _r
                      in dev.line("XLA Ops") or []),
                     key=lambda e: (e[0], -e[1]))
        if not mods or not ops:
            continue
        for a, b, name in mods:
            agg = out.setdefault(name, {"calls": 0, "seconds": 0.0,
                                        "scopes": dict.fromkeys(TORSO, 0.0),
                                        "kernels_s": 0.0, "rest": {}})
            agg["calls"] += 1
            agg["seconds"] += (b - a) / 1e12
        own, parent = spans._self_times([(a, b) for a, b, _m in ops])
        starts = [m[0] for m in mods]
        scopes: list[str | None] = []
        for i, (a, _b, meta_id) in enumerate(ops):
            meta = dev.meta(meta_id)
            scope = op_scope(meta["name"], meta["stats"].get("tf_op"))
            by_name = scope != scope_of(meta["stats"].get("tf_op"))
            if scope is None and parent[i] >= 0:
                scope = scopes[parent[i]]
            scopes.append(scope)
            j = bisect.bisect_right(starts, a) - 1
            if j < 0 or a >= mods[j][1]:
                continue
            agg = out[mods[j][2]]
            if scope is None:
                short = meta["name"].split(" ", 1)[0]
                agg["rest"][short] = agg["rest"].get(short, 0.0) \
                    + own[i] / 1e12
                continue
            agg["scopes"][scope] += own[i] / 1e12
            if by_name:
                agg["kernels_s"] += own[i] / 1e12
    if not any(s > 0 for p in out.values() for s in p["scopes"].values()):
        return None
    return out


def load(ctx: dict) -> dict | None:
    """The run's reduction, made once and kept in ``ctx``; a line on
    stderr by program, and the share of device time under the scopes."""
    got = spans.load(ctx)
    if "torso" in got:
        return got["torso"]
    got["torso"] = red = (
        reduce_planes(got["planes"], ctx["traffic"]["step_programs"])
        if got["planes"] is not None else None)
    if red is not None:
        for name, p in sorted(red.items()):
            ctx["say"](f"{name}: {p['calls']} calls, {p['seconds']:.4f} s on "
                       f"the device; " + ", ".join(
                           f"{s} {v:.4f}" for s, v in p["scopes"].items())
                       + f"; of them {p['kernels_s']:.4f} in kernels placed "
                       "by name; the largest under no torso scope: "
                       + ", ".join(f"{n} {v:.4f}" for n, v in sorted(
                           p["rest"].items(), key=lambda kv: -kv[1])[:4]))
        scoped = sum(sum(p["scopes"].values()) for p in red.values())
        busy = ctx["trace"]["busy_s"]
        ctx["say"](f"torso scopes hold {scoped:.4f} s of the device's "
                   f"{busy:.4f} busy seconds: {100.0 * scoped / busy:.1f}%")
    return red


def update_programs(ctx: dict, red: dict) -> list[dict]:
    """The reductions of the programs that carry a learner update."""
    return [red[name] for name, prog
            in ctx["traffic"]["step_programs"].items()
            if prog.get("learner_steps") and name in red]


def scope_ms(ctx: dict, scope: str) -> float | None:
    """Device milliseconds of one scope per call of the update programs
    (the rollout program's share is on the stderr line)."""
    red = load(ctx)
    if red is None:
        return None
    progs = [p for p in update_programs(ctx, red) if p["scopes"][scope] > 0]
    calls = sum(p["calls"] for p in progs)
    if not calls:
        return None
    return 1000.0 * sum(p["scopes"][scope] for p in progs) / calls


def counters(ctx: dict) -> list[dict]:
    """The ``moe_stats`` instants of the window (one an update, written by
    the trainer loop from the step's own metrics), oldest first."""
    return [ev.get("args") or {} for ev in spans.load(ctx)["ring"]
            if ev.get("name") == "moe_stats"]


def counter_median(ctx: dict, key: str) -> float | None:
    values = [c[key] for c in counters(ctx) if key in c]
    return statistics.median(values) if values else None
