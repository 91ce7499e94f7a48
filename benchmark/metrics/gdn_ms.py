"""Device milliseconds under the torso's scope ``gdn`` (a Gated DeltaNet layer's two in-projections, convolution, delta rule, gated norm and out-projection: ``conv`` and ``delta`` included) per call of the
programs that carry a learner update (three forward passes and a backward
one), by the innermost torso name on each operation's ``tf_op`` path
(``family_scopes.py`` over the family's table)."""

from benchmark import family_scopes


def read(ctx):
    return family_scopes.scope_ms(ctx, "gdn")
