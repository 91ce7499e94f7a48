"""Device milliseconds under the torso's scope ``mamba`` (a Mamba-2 layer's in-projection, convolution, scan, gated norm and out-projection: ``conv`` and ``ssd`` included) per call of the
programs that carry a learner update (three forward passes and a backward
one), by the innermost torso name on each operation's ``tf_op`` path."""

from benchmark import nemotron_h_scopes


def read(ctx):
    return nemotron_h_scopes.scope_ms(ctx, "mamba")
