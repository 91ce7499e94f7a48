"""Persistent XLA compilation cache, placed from outside.

A cold TPU compile of the fused learner step is tens of seconds; every
entry point that jits (``runtime/cli.main``, ``bench.main``,
``chip_smoke.py``) calls :func:`ensure_compile_cache` before its first
jit so a second launch — and every actor worker it spawns — finds the
programs already built.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
# the checkout that holds this package: fixed across launches (the cache
# directory is part of the cache key's lookup, so a path that moves —
# tempfile, pid, time — never hits), git-ignored
_IN_TREE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache() -> str:
    """Where the compile cache lives.  ``JAX_COMPILATION_CACHE_DIR`` set
    by the operator wins and nothing else is configured (JAX reads it
    itself); unset, the cache goes to ``<checkout>/.jax_cache`` and the
    variable is exported so spawned workers share it.  Must run before
    the process's first compile — JAX latches "no cache" at that point."""
    path = os.environ.get(_ENV)
    if path:
        return path
    os.environ[_ENV] = _IN_TREE
    import jax
    # jax read the (then unset) variable when it was imported
    jax.config.update("jax_compilation_cache_dir", _IN_TREE)
    return _IN_TREE
