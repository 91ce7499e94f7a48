"""Share of the window the trainer loop spent between dispatches."""


def read(ctx):
    gaps = ctx["host_gaps_s"]
    if not gaps:
        return None
    return 100.0 * sum(gaps) / ctx["window_s"]
