"""Device seconds by the Nemotron-H torso's trace scopes
(``jax.named_scope`` in ``apex_tpu/models/nemotron_h.py`` and the expert
layer it shares: ``embed``, ``mamba`` with ``conv`` and ``ssd`` inside it,
``attention``, ``router`` with ``experts`` inside it, ``shared_expert``,
``q_head``), program by program.

``torso_scopes.py`` does this for the GLM torso over a closed tuple of
names; this is the same reduction over this family's names, through that
file's helpers (the wrapped-part pattern, the kernels placed by name) and
``spans``'s planes.  An operation counts under the INNERMOST of the names
on its ``tf_op`` path (``ssd`` lies inside ``mamba``); one without any
takes that of the operation it is nested in (a ``while`` body's).
:func:`scope_ms` of an outer name adds what lies inside it.  Where the
program has no such scope, as a checkout from before PR 33 has none, every
function here returns ``None``.
"""

from __future__ import annotations

import bisect

from benchmark import spans, torso_scopes

SCOPES = ("embed", "mamba", "conv", "ssd", "attention", "router", "experts",
          "shared_expert", "q_head")
#: operation-name prefix -> scope, for what the compiler names itself and
#: leaves without a scope path: the grouped products (``torso_scopes``) and
#: ``jnp.cumsum``'s lowering, which the scan's running decays go through
#: (the router's 8-element cumulative counts go the same way: microseconds)
KERNELS = {**torso_scopes.KERNELS, "reduce_window_sum": "ssd"}
#: outer scope -> what lies inside it
INSIDE = {"mamba": ("conv", "ssd"), "router": ("experts",)}


def scope_of(tf_op: str | None) -> str | None:
    """The innermost of :data:`SCOPES` on an operation's ``tf_op`` path; a
    part a transform wrapped (``jvp(ssd)``) counts as the name inside."""
    if not tf_op:
        return None
    parts = tf_op.split(":", 1)[0].split("/")
    for part in reversed(parts[:-1]):
        inner = torso_scopes._WRAPPED.match(part)
        part = inner.group(1) if inner else part
        if part in SCOPES:
            return part
    return None


def op_scope(name: str | None, tf_op: str | None) -> str | None:
    """The scope of one device operation: by its own name where the
    compiler named it (:data:`KERNELS`), else by its path."""
    base = (name or "").lstrip("%")
    for prefix, scope in KERNELS.items():
        if base.startswith(prefix) or (tf_op or "").startswith(prefix):
            return scope
    return scope_of(tf_op)


def reduce_planes(planes, programs) -> dict | None:
    """``{program: {calls, seconds, scopes: {name: seconds}, rest}}`` for
    the named programs: own seconds by innermost scope, ``rest`` the
    seconds of the operations under none, by name."""
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
                                        "scopes": dict.fromkeys(SCOPES, 0.0),
                                        "rest": {}})
            agg["calls"] += 1
            agg["seconds"] += (b - a) / 1e12
        own, parent = spans._self_times([(a, b) for a, b, _m in ops])
        starts = [m[0] for m in mods]
        scopes: list[str | None] = []
        for i, (a, _b, meta_id) in enumerate(ops):
            meta = dev.meta(meta_id)
            scope = op_scope(meta["name"], meta["stats"].get("tf_op"))
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
            else:
                agg["scopes"][scope] += own[i] / 1e12
    if not any(p["scopes"][s] > 0 for p in out.values()
               for s in ("mamba", "conv", "ssd")):
        return None
    return out


def load(ctx: dict) -> dict | None:
    """The run's reduction, made once and kept in ``ctx``; a line on
    stderr by program, and the share of device time under the scopes."""
    got = spans.load(ctx)
    if "nemotron_h" in got:
        return got["nemotron_h"]
    got["nemotron_h"] = red = (
        reduce_planes(got["planes"], ctx["traffic"]["step_programs"])
        if got["planes"] is not None else None)
    if red is not None:
        for name, p in sorted(red.items()):
            ctx["say"](f"{name}: {p['calls']} calls, {p['seconds']:.4f} s on "
                       f"the device; " + ", ".join(
                           f"{s} {v:.4f}" for s, v in p["scopes"].items())
                       + "; the largest under no torso scope: "
                       + ", ".join(f"{n} {v:.4f}" for n, v in sorted(
                           p["rest"].items(), key=lambda kv: -kv[1])[:4]))
        scoped = sum(sum(p["scopes"].values()) for p in red.values())
        busy = ctx["trace"]["busy_s"]
        ctx["say"](f"torso scopes hold {scoped:.4f} s of the device's "
                   f"{busy:.4f} busy seconds: {100.0 * scoped / busy:.1f}%")
    return red


def scope_seconds(ctx: dict, scope: str) -> tuple[float, int] | None:
    """(device seconds under ``scope``, what lies inside it included;
    calls) over the programs that carry a learner update."""
    red = load(ctx)
    if red is None:
        return None
    names = (scope, *INSIDE.get(scope, ()))
    progs = torso_scopes.update_programs(ctx, red)
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
