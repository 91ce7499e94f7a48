"""Device milliseconds under the scope ``sample`` (the stratified descent of
the sum tree, the staleness guard and the importance weights) per call of
the step programs that contain it, by the ``tf_op`` path of each operation
in the profiler trace."""

from benchmark import spans


def read(ctx):
    return spans.scope_ms(ctx, "sample")
