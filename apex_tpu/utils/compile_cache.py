"""Persistent XLA compilation cache, placed from outside.

A cold TPU compile of the fused learner step is tens of seconds; every
entry point that jits (``runtime/cli.main``, ``benchmark/harness.py``,
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


def ensure_compile_cache(traced: bool = False) -> str:
    """Where the compile cache lives.  ``JAX_COMPILATION_CACHE_DIR`` set
    by the operator wins and no other directory is configured (JAX reads
    it itself); unset, the cache goes to ``<checkout>/.jax_cache`` and the
    variable is exported so spawned workers share it.  Must run before
    the process's first compile — JAX latches "no cache" at that point.

    A run that is traced (``traced``: a profiler trace will be taken; or
    the trace ring is on, ``APEX_TRACE_DIR``) counts a program's metadata
    in its cache key.  JAX's default leaves it out, so a cache filled
    before a ``jax.named_scope`` or a source line moved hands back an
    executable that still carries the OLD names, and the profiler's trace
    (with every metric that groups device time by scope) describes the
    source as it was.  Only traced runs pay for that with a compile of
    their own after traced source moves; every other run is served as
    before."""
    import jax
    if traced or os.environ.get("APEX_TRACE_DIR"):
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    path = os.environ.get(_ENV)
    if path:
        return path
    os.environ[_ENV] = _IN_TREE
    # jax read the (then unset) variable when it was imported
    jax.config.update("jax_compilation_cache_dir", _IN_TREE)
    return _IN_TREE
