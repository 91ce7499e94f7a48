"""Share of the window inside a ``loop_iter`` span and under none of its
children: what the loop does that no span names.  Every phase's share of
the window, the passes by kind and what lies between passes on earlier
lines."""

from benchmark import spans


def read(ctx):
    red = spans.phases(ctx)
    if red is None:
        return None
    window = ctx["window_s"]
    by = red["by_name"]
    ctx["say"]("loop phases, % of the window (events): " + ", ".join(
        f"{name} {100 * v['s'] / window:.2f} ({v['n']})"
        for name, v in sorted(by.items(), key=lambda kv: -kv[1]["s"])))
    ctx["say"]("loop passes by kind: " + ", ".join(
        f"{k} {n}" for k, n in sorted(red["kinds"].items()))
        + f"; outside any pass "
          f"{100 * (1 - by[spans.LOOP]['s'] / window):.2f}% of the window")
    return 100.0 * red["loop_self_s"] / window
