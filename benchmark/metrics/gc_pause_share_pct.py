"""Share of the window the learner process spent in garbage collection:
the seconds of the window's ``gc`` spans (on whichever thread set the
collection off; it holds the interpreter lock throughout) over the window.
On earlier lines: collections by generation, and their seconds by the loop
thread's innermost phase meanwhile (``loop_iter (own)``, ``adopt``,
``dispatch``, ...)."""

from benchmark import host_threads, spans


def read(ctx):
    red = host_threads.gc_pauses(spans.load(ctx)["ring"])
    if red is None:
        return None
    window = ctx["window_s"]
    ctx["say"](f"gc in the window: {red['n']} collections, {red['s']:.4f} s;"
               " by generation: " + ", ".join(
                   f"{g}: {v['n']} / {v['s']:.4f} s / {v['collected']} freed"
                   for g, v in red["by_gen"].items()))
    ctx["say"]("gc by the loop's phase, % of the window: " + ", ".join(
        f"{name} {100 * s / window:.3f}"
        for name, s in red["by_phase"].items()))
    return 100.0 * red["s"] / window
