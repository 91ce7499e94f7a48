"""Device milliseconds under the scope ``update`` (loss and gradients, the
optimizer and the target sync) per call of the step programs that contain
it, by the ``tf_op`` path of each operation in the profiler trace."""

from benchmark import spans


def read(ctx):
    return spans.scope_ms(ctx, "update")
