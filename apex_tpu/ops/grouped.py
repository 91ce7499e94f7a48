"""Grouped matrix products ``xs[i] @ w[g(i)]`` for the expert layer of
the token torsos (:class:`apex_tpu.models.glm4_moe_lite.MoE`): rows sorted
by group, ``group_sizes`` rows each, the rows past the last group no
group's.  One algorithm, two kernels, picked by what the code can observe.

``ragged`` is :func:`jax.lax.ragged_dot`.  XLA:TPU makes it a kernel of its
own that takes, for each of the two weight dimensions, the largest of 512 /
256 / 128 that DIVIDES it.  At GLM's 2,048 x 1,536 that is 512 x 512 and
the kernel runs at 47% of a v5e's peak; at Nemotron's 2,688 x 1,856 it is
128 x 128, 315 grid steps a 512-row tile where GLM's takes 12, and the
kernel is bound by per-step overhead at 7-9% (PERF.md, PR 34).

``tiled`` is the grouped kernel JAX ships
(:mod:`jax.experimental.pallas.ops.tpu.megablox`), which takes its tiles as
arguments: a weight dimension gets the candidate tile that pads it least
(:func:`weight_tile`: 2,688 = 3 x 896 as it stands, 1,856 -> 1,920 = 3 x
640), the operands go in with exact zeros up to that width, and the
backward pass is two more kernels of the same package (``gmm`` with the
weights transposed for the rows' gradient, ``tgmm`` for the weights'), each
with operands in the forward's dtype and float32 accumulation.

:func:`plan` is the rule, a static function of the two widths, the rows of
a call and the platform a program is compiled for: off a TPU, below one
512 tile, or where 512 divides both widths (where ``ragged`` already has
its best tiles), nothing changes; else ``tiled``.  No flag and no
environment variable: :func:`grouped_path` says which way a layer's widths
go, for the trace ring and the start-up line.  The alternatives measured
on the chip (``scripts/grouped_sweep.py``; PERF.md, PR 34): the same
``ragged_dot`` handed widths zero-padded to multiples of 512 or 256 takes
a round of both products and their gradients from 30.7 ms to 17.7 / 18.7
ms, ``tiled`` to 13.1 ms.
"""

from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp

# the package's own ``gmm`` is a ``custom_vjp`` that hands the float32
# cotangent to the backward kernels as it comes, which makes their products
# float32 ones; :func:`tiled` casts it and calls the kernels' module
_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

#: a TPU vector register's lanes: a tile is a multiple of it
LANES = 128
#: the tile ``ragged`` is at its best with, and the most rows of a tile
BEST = 512
#: tiles a weight dimension may get, widest first: multiples of
#: :data:`LANES` from three lanes up to what three double-buffered
#: operands and a float32 accumulator leave room for in 16 MiB of VMEM
TILES = (896, 768, 640, 512, 384)


def weight_tile(n: int) -> tuple[int, int]:
    """``(tile, handed)`` for a weight dimension of ``n``: the tile of
    :data:`TILES` whose multiples reach ``n`` with the least padding, the
    widest of those that tie, and ``n`` rounded up to it."""
    tile = min(TILES, key=lambda t: (-(-n // t) * t, -t))
    return tile, -(-n // tile) * tile


def row_tile(rows: int) -> int:
    """The most rows of a tile that divide ``rows``, :data:`BEST` at
    most."""
    return math.gcd(rows, BEST)


def plan(hidden: int, width: int, rows: int, platform: str):
    """``None`` where an expert layer of ``hidden`` x ``width`` goes
    through ``ragged`` as it stands; else what ``tiled`` is handed,
    ``((hidden's tile, hidden handed), (width's tile, width handed))``,
    ``rows`` in tiles of :func:`row_tile`."""
    if (platform != "tpu" or min(hidden, width) < BEST
            or (hidden % BEST == 0 and width % BEST == 0)
            or row_tile(rows) < LANES):
        return None
    return weight_tile(hidden), weight_tile(width)


def grouped_path(hidden: int, width: int, platform: str) -> dict:
    """Which way an expert layer's products go at these widths in a
    program compiled for ``platform`` (at a number of rows a row tile
    divides, as every round of the presets has): the arguments of the
    trace ring's ``grouped_path`` instant.  ``tile_k`` / ``tile_n`` are the
    weight window the kernel walks for ``hidden`` / ``width``: for
    ``ragged`` on a TPU the largest of 512 / 256 / 128 that divides the
    width (128 after the compiler's own padding where none does), 0 where
    there is no such kernel."""
    out = {"hidden": hidden, "width": width, "platform": platform}
    tiled_ = plan(hidden, width, BEST, platform)
    if tiled_ is not None:
        (tile_k, hidden_to), (tile_n, width_to) = tiled_
        return {**out, "hidden_handed": hidden_to, "width_handed": width_to,
                "tile_k": tile_k, "tile_n": tile_n, "impl": "megablox_gmm"}
    tile_k, tile_n = (
        next((t for t in (512, 256) if n % t == 0), 128)
        if platform == "tpu" else 0 for n in (hidden, width))
    return {**out, "hidden_handed": hidden, "width_handed": width,
            "tile_k": tile_k, "tile_n": tile_n, "impl": "ragged_dot"}


def product(xs: jax.Array, w: jax.Array, group_sizes: jax.Array,
            tiles=None) -> jax.Array:
    """``[m, k] x [g, k, n] -> f32[m, n']``, operands in the dtype they
    come in, float32 accumulation.  ``tiles`` ``None``: ``ragged``, ``n' =
    n``.  Else ``((k's tile, k handed), (n's tile, n handed))`` as
    :func:`plan` gives them: ``tiled``, handed ``xs`` and ``w`` with zeros
    up to those widths, and ``n'`` the width handed (the columns past
    ``n`` are exact zeros).  Either way the rows past the last group are
    left unwritten, here and in the cotangent handed back."""
    if tiles is None:
        return jax.lax.ragged_dot(xs, w, group_sizes,
                                  preferred_element_type=jnp.float32)
    (tk, k), (tn, n) = tiles
    rows = xs.shape[0]
    return tiled(widened(xs, (rows, k)), widened(w, (w.shape[0], k, n)),
                 group_sizes, (row_tile(rows), tk, tn))


def widened(x: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """``x`` with zeros after the end of every axis, up to ``shape``."""
    if x.shape == shape:
        return x
    return jnp.pad(x, [(0, to - n) for n, to in zip(x.shape, shape)])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def tiled(xs: jax.Array, w: jax.Array, group_sizes: jax.Array,
          tiling: tuple[int, int, int]) -> jax.Array:
    """``[m, k] x [g, k, n] -> f32[m, n]`` from the tiled kernel at
    ``tiling = (rows, k, n)``, each dividing its dimension."""
    return _megablox.gmm(xs, w, group_sizes, jnp.float32, tiling)


def _tiled_fwd(xs, w, group_sizes, tiling):
    return tiled(xs, w, group_sizes, tiling), (xs, w, group_sizes)


def _tiled_bwd(tiling, res, dy):
    xs, w, group_sizes = res
    tm, tk, tn = tiling
    dy = dy.astype(xs.dtype)
    dxs = _megablox.gmm(dy, w, group_sizes, jnp.float32, (tm, tn, tk),
                        transpose_rhs=True)
    dw = _megablox.tgmm(xs.swapaxes(0, 1), dy, group_sizes, jnp.float32,
                        tiling)
    return dxs.astype(xs.dtype), dw.astype(w.dtype), None


tiled.defvjp(_tiled_fwd, _tiled_bwd)
