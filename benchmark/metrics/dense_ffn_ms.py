"""Device milliseconds under the torso's scope ``dense_ffn`` per call of the
programs that carry a learner update (three forward passes and a backward
one), by the innermost torso name on each operation's ``tf_op`` path."""

from benchmark import torso_scopes


def read(ctx):
    return torso_scopes.scope_ms(ctx, "dense_ffn")
