"""Share of the window the trainer loop spent preparing a dispatch's
operands in eager programs of their own: the ``dispatch_key`` spans
(``jax.random.split`` and the unstack behind it) and the ``beta`` spans
(``jnp.float32(beta)``) of the program's trace ring."""

from benchmark import spans


def read(ctx):
    return spans.share(ctx, "dispatch_key", "beta")
