"""Device seconds by a token torso's trace scopes (``jax.named_scope`` in
``apex_tpu/models/``), program by program: ONE reduction over a family's
table of names.

``torso_scopes.py`` (GLM) and ``nemotron_h_scopes.py`` are this reduction
written out twice over a closed tuple each.  Here the names are handed in:
a family is a module ``benchmark/scopes_<family>.py`` beside this file,
found by the ``family`` the configuration names as ``costs_<family>.py``
is, that gives

``SCOPES``   every torso scope of the family's programs;
``KERNELS``  operation-name prefix -> scope, for what the compiler names
             itself and leaves without a scope path;
``INSIDE``   outer scope -> the scopes that lie inside it.

An operation counts under the INNERMOST of the family's names on its
``tf_op`` path; one without any takes that of the operation it is nested
in (a ``while`` body's).  :func:`scope_ms` of an outer name adds what lies
inside it.  The planes, the programs' names and the self times are
``spans``'s.
"""

from __future__ import annotations

import bisect
import importlib
import re
import types

from benchmark import spans

_WRAPPED = re.compile(r"^(?:\w+\()+(.*?)\)+$")


def table_for(family: str) -> types.ModuleType:
    """``benchmark/scopes_<family>.py``."""
    return importlib.import_module(f"benchmark.scopes_{family}")


def scope_of(table, tf_op: str | None) -> str | None:
    """The innermost of the table's ``SCOPES`` on an operation's ``tf_op``
    path; a part a transform wrapped (``jvp(delta)``,
    ``transpose(jvp(gdn))``) counts as the name inside."""
    if not tf_op:
        return None
    parts = tf_op.split(":", 1)[0].split("/")
    for part in reversed(parts[:-1]):
        inner = _WRAPPED.match(part)
        part = inner.group(1) if inner else part
        if part in table.SCOPES:
            return part
    return None


def op_scope(table, name: str | None, tf_op: str | None) -> str | None:
    """The scope of one device operation: by its own name where the
    compiler named it (``KERNELS``), else by its path."""
    base = (name or "").lstrip("%")
    for prefix, scope in table.KERNELS.items():
        if base.startswith(prefix) or (tf_op or "").startswith(prefix):
            return scope
    return scope_of(table, tf_op)


def reduce_planes(table, planes, programs) -> dict:
    """``{program: {calls, seconds, scopes: {name: seconds}, kernels_s,
    rest}}`` for the named programs: own seconds by innermost scope,
    ``kernels_s`` what of them was placed by an operation's name, ``rest``
    the seconds of the operations under none, by name."""
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
            agg = out.setdefault(name, {
                "calls": 0, "seconds": 0.0,
                "scopes": dict.fromkeys(table.SCOPES, 0.0),
                "kernels_s": 0.0, "rest": {}})
            agg["calls"] += 1
            agg["seconds"] += (b - a) / 1e12
        own, parent = spans._self_times([(a, b) for a, b, _m in ops])
        starts = [m[0] for m in mods]
        scopes: list[str | None] = []
        for i, (a, _b, meta_id) in enumerate(ops):
            meta = dev.meta(meta_id)
            tf_op = meta["stats"].get("tf_op")
            scope = op_scope(table, meta["name"], tf_op)
            by_name = scope != scope_of(table, tf_op)
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
    return out


def load(ctx: dict) -> dict | None:
    """The run's reduction over its family's table, made once and kept in
    ``ctx``; a line on stderr by program, and the share of device time
    under the scopes."""
    got = spans.load(ctx)
    if "family_scopes" in got:
        return got["family_scopes"]
    got["family_scopes"] = red = (
        reduce_planes(table_for(ctx["config"]["family"]), got["planes"],
                      ctx["traffic"]["step_programs"])
        if got["planes"] is not None else None)
    if red:
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
        # the expert layer's routing counters of the window's updates
        # (the ring's ``moe_stats`` instants): what each held expert saw
        stats = [ev.get("args") or {} for ev in got["ring"]
                 if ev.get("name") == "moe_stats"]
        for key in ("moe_local_pairs", "moe_local_share",
                    "moe_load_max_over_mean"):
            values = sorted(c[key] for c in stats if key in c)
            if values:
                ctx["say"](f"moe_stats {key} over {len(values)} updates: "
                           f"least {values[0]:.6g}, median "
                           f"{values[len(values) // 2]:.6g}, most "
                           f"{values[-1]:.6g}")
    return red


def update_programs(ctx: dict, red: dict) -> list[dict]:
    """The reductions of the programs that carry a learner update."""
    return [red[name] for name, prog
            in ctx["traffic"]["step_programs"].items()
            if prog.get("learner_steps") and name in red]


def scope_seconds(ctx: dict, scope: str) -> tuple[float, int] | None:
    """(device seconds under ``scope``, what lies inside it included;
    calls) over the programs that carry a learner update."""
    red = load(ctx)
    if red is None:
        return None
    table = table_for(ctx["config"]["family"])
    names = (scope, *table.INSIDE.get(scope, ()))
    progs = update_programs(ctx, red)
    seconds = sum(p["scopes"][n] for p in progs for n in names)
    calls = sum(p["calls"] for p in progs)
    if not calls or seconds <= 0.0:
        return None
    return seconds, calls


def scope_ms(ctx: dict, scope: str) -> float | None:
    """Device milliseconds of one scope per call of the update programs
    (the rollout program's share is on the stderr line)."""
    got = scope_seconds(ctx, scope)
    return None if got is None else 1000.0 * got[0] / got[1]
