"""Share of the window the learner process's trace exporter spent in its
flushes: the seconds of the window's ``ring_flush`` spans over the window.
It is the tracer's own cost, measured where it is paid (the flusher thread
holds the interpreter lock while it encodes).  Flushes, events and bytes
written on an earlier line."""

from benchmark import host_threads, spans


def read(ctx):
    red = host_threads.flushes(spans.load(ctx)["ring"])
    if red is None:
        return None
    ctx["say"](f"trace ring flushes in the window: {red['n']}, "
               f"{red['s']:.4f} s (longest {red['longest_s']:.4f} s), "
               f"{red['events']} events, {red['bytes']} bytes written")
    return 100.0 * red["s"] / ctx["window_s"]
