"""Device milliseconds under the torso's scope ``qk_norm_attention`` (an
attention layer's projections, RMSNorm on each query and key head, RoPE,
the kernel and the out-projection) per call of the programs that carry a
learner update, by the innermost torso name on each operation's ``tf_op``
path (``family_scopes.py`` over the family's table)."""

from benchmark import family_scopes


def read(ctx):
    return family_scopes.scope_ms(ctx, "qk_norm_attention")
