"""95th percentile of the trainer loop's ``host_gap`` events (time between
one dispatch returning and the next being issued) over the window.  A cell
that issues some tens of dispatches a window has no tail to take: ten
samples have to lie beyond it."""

import statistics


def read(ctx):
    gaps = ctx["host_gaps_s"]
    ctx["say"](f"host_gap events in the window: {len(gaps)}")
    if len(gaps) < 200:
        return None
    return 1000.0 * statistics.quantiles(gaps, n=20)[-1]
