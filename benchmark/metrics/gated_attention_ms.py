"""Device milliseconds under the torso's scope ``gated_attention`` (a gated attention layer's projections, head norms, RoPE, kernel and output gate) per call of the
programs that carry a learner update (three forward passes and a backward
one), by the innermost torso name on each operation's ``tf_op`` path
(``family_scopes.py`` over the family's table)."""

from benchmark import family_scopes


def read(ctx):
    return family_scopes.scope_ms(ctx, "gated_attention")
