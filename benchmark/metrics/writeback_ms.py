"""Device milliseconds under the scope ``writeback`` (the new priorities
written into the trees) per call of the step programs that contain it, by
the ``tf_op`` path of each operation in the profiler trace."""

from benchmark import spans


def read(ctx):
    return spans.scope_ms(ctx, "writeback")
