"""Of the device's idle seconds in the traced span, the share during which
the ingest staging thread had ``stage`` or ``publish`` open on its line of
the profiler's host plane (clocks as written).  On an earlier line: the
idle seconds by pair, the loop thread's innermost span against the
innermost spans open on the other threads' lines (staging, the trace
exporter's ``ring_flush``, ``gc`` on any thread), so each idle gap is put
down to what the host was doing."""

from benchmark import host_threads


def read(ctx):
    red = host_threads.threads(ctx)
    if red is None or red["idle_s"] <= 0.0:
        return None
    ctx["say"](f"device idle {red['idle_s']:.4f} s of the traced span by "
               f"(loop thread / other threads): " + ", ".join(
                   f"{loop} / {other} {s:.4f}"
                   for (loop, other), s in red["pairs"]))
    ctx["say"](f"staging thread ({red['stage_annotations']} stage spans) "
               f"in stage or publish for {red['staging_s']:.4f} s of it")
    return 100.0 * red["staging_s"] / red["idle_s"]
