"""Share of the window the trainer loop spent inside the jitted calls
themselves (the ``dispatch`` spans of the program's trace ring: from the
call to ``gap.dispatch_returned()``, so with the hand-over of the results,
``adopt``, inside it).  With ``host_gap`` and ``beta`` it makes up the
window.  Calls by program, the ``beta`` and ``adopt`` shares beside it,
and the five host events that took most time inside the calls (the
profiler's host plane) on earlier lines."""

from benchmark import spans


def read(ctx):
    red = spans.phases(ctx)
    if red is None:
        return None
    ctx["say"]("dispatch spans by program: " + ", ".join(
        f"{name} {p['n']} calls {p['s']:.3f} s"
        for name, p in sorted(red["programs"].items())))
    ctx["say"](f"beta spans: {spans.share(ctx, 'beta'):.2f}% of the window; "
               f"dispatch_key: {spans.share(ctx, 'dispatch_key'):.2f}%; "
               f"adopt (inside dispatch): {spans.share(ctx, 'adopt'):.2f}%")
    host = spans.attribution(ctx)
    if host is not None:
        ctx["say"]("longest host events inside dispatch (traced span): "
                   + "; ".join(f"{name} {v['n']}x {v['s']:.4f} s"
                               for name, v in host["inside_dispatch"][:5]))
    return spans.share(ctx, "dispatch")
