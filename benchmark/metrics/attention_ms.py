"""Device milliseconds under the torso's scope ``attention`` (a grouped-query attention layer's projections and kernel) per call of the
programs that carry a learner update (three forward passes and a backward
one), by the innermost torso name on each operation's ``tf_op`` path."""

from benchmark import nemotron_h_scopes


def read(ctx):
    return nemotron_h_scopes.scope_ms(ctx, "attention")
