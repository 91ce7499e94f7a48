"""Device milliseconds under the scope ``ingest`` (the chunk's rows written
into the frame ring and the trees) per call of the step programs that
contain it, by the ``tf_op`` path of each operation in the profiler trace."""

from benchmark import spans


def read(ctx):
    return spans.scope_ms(ctx, "ingest")
