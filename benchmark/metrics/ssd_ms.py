"""Device milliseconds under the torso's scope ``ssd`` alone (the chunked scan: decays, the products inside a chunk, the state carried between chunks, the output) per call of the
programs that carry a learner update (three forward passes and a backward
one), by the innermost torso name on each operation's ``tf_op`` path."""

from benchmark import nemotron_h_scopes


def read(ctx):
    return nemotron_h_scopes.scope_ms(ctx, "ssd")
