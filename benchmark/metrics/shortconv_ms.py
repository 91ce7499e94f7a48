"""Device milliseconds under the torso's scope ``shortconv`` (the
double-gated short convolution: in-projection, gates, the three-tap sum
under ``conv``, out-projection), ``conv`` included, per call of the
programs that carry a learner update (three forward passes and a backward
one), by the innermost torso name on each operation's ``tf_op`` path
(``family_scopes.py`` over the family's table)."""

from benchmark import family_scopes


def read(ctx):
    return family_scopes.scope_ms(ctx, "shortconv")
