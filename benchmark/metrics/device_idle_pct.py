"""1 - union of the device's operation intervals over the traced window."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not ctx["traced_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / ctx["traced_s"])
