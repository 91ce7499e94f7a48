"""Share of the step programs' device seconds (their ``XLA Modules``
events, ``step_roofline``'s denominator) under none of the five scopes
(``ingest``, ``sample``, ``gather``, ``update``, ``writeback``): operations
without a scope and the time between operations.  The account by program
and the operations left over, each with the source line the trace gives
it, on earlier lines."""

from benchmark import spans


def read(ctx):
    red = spans.scopes(ctx)
    if red is None:
        return None
    t = red["totals"]
    for name, p in sorted(red["programs"].items()):
        ctx["say"](f"{name}: {p['calls']} calls, {p['seconds']:.4f} s on the "
                   f"device; " + ", ".join(
                       f"{s} {v:.4f}" for s, v in p["scopes"].items())
                   + f", unscoped {p['unscoped_s']:.4f}")
    ctx["say"](f"step programs {t['module_s']:.4f} s on the device = scopes "
               f"{t['scoped_s']:.4f} + operations under none "
               f"{t['unscoped_ops_s']:.4f} + between operations "
               f"{t['between_ops_s']:.4f}")
    for name, v in red["unscoped_ops"][:8]:
        ctx["say"](f"unscoped: {v['s']:.4f} s {v['n']}x {name[:90]} "
                   f"[{v['category']}; source {v['source']}]")
    return 100.0 * (t["module_s"] - t["scoped_s"]) / t["module_s"]
