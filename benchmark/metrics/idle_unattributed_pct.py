"""Of the device's idle seconds in the traced span (between its first and
its last operation), the share under no named phase of the loop's thread:
the profiler's host plane (``TraceAnnotation``) laid over the gaps of the
device's operations line, clocks as written, each gap's seconds going to
the innermost span open meanwhile.  Time under ``loop_iter`` alone counts
as unattributed: the pass wraps the whole loop body and names no phase.
On earlier lines: idle seconds by span, each beside what it reads with
the device's times moved later by the least that puts every program after
its ``dispatch`` (the two clocks agree only to some tenths of a
millisecond; what lies between the two readings is what they leave open),
and the ``dispatch`` annotations beside the device programs they issued
(one clock)."""

from benchmark import spans


def read(ctx):
    host = spans.attribution(ctx)
    if host is None or host["idle_s"] <= 0.0:
        return None
    moved = host["idle_by_span_moved"]
    ctx["say"](f"device idle {host['idle_s']:.3f} s of the traced span, by "
               f"the loop thread's span, clocks as written / device "
               f"{host['clock_skew_floor_us']:.0f} us later: " + ", ".join(
                   f"{name} {s:.3f} / {moved.get(name, 0.0):.3f}"
                   for name, s in host["idle_by_span"].items())
               + f"; under no phase {host['idle_unattributed_s']:.3f} / "
                 f"{host['idle_unattributed_moved_s']:.3f}")
    ctx["say"]("annotations on the loop's thread: " + ", ".join(
        f"{name} {n}" for name, n in sorted(host["annotations"].items())))
    clock = host["one_clock"]
    if clock is not None:
        ctx["say"]("one clock (as written): " + ", ".join(
            f"{k} {v:.1f}" if isinstance(v, float) else f"{k} {v}"
            for k, v in clock.items()))
    return 100.0 * host["idle_unattributed_s"] / host["idle_s"]
