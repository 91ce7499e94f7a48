"""XLA backend-compile seconds during set-up (jax.monitoring), cache hits
and misses on an earlier line."""


def read(ctx):
    hits, misses = ctx["cache"]
    ctx["say"](f"compile cache during set-up: {hits} hits, {misses} misses")
    return ctx["compile_s_setup"]
