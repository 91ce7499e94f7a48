"""Device milliseconds under the scope ``gather`` (the sampled stacks and
fields read out of the rings) per call of the step programs that contain it,
by the ``tf_op`` path of each operation in the profiler trace."""

from benchmark import spans


def read(ctx):
    return spans.scope_ms(ctx, "gather")
