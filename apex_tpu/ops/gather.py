"""Pallas TPU kernel for the replay frame-stack gather.

The hottest data movement in the fused learner step is sample-time stack
reconstruction (:meth:`apex_tpu.replay.frame_pool.FramePoolReplay.sample`):
``2 * B * S`` random rows of the HBM frame ring — for the reference config
(B=512, S=4, 84x84 frames) ~29MB of data-dependent gather per step.  XLA
lowers ``frames[ids]`` to a generic dynamic-gather; this kernel instead
streams rows through Mosaic's own grid pipeline driven by scalar-prefetched
indices (the embedding-lookup pattern from the pallas guide): the row ids
land in SMEM before the kernel body runs, the input BlockSpec's
``index_map`` reads ``ids[i]`` to pick each grid step's source row, and
Mosaic double-buffers the row DMAs — fetching step ``i+1``'s row while
step ``i`` writes back.

History: the first version of this kernel hand-rolled the DMAs
(``make_async_copy`` with a per-row semaphore array) and hung a live chip
— and an orphaned on-device DMA wait wedges the device for every
subsequent client, which is the worst failure mode a replay-path op can
have.  This rewrite delegates all DMA scheduling/semaphores to Mosaic's
pipeline machinery precisely to remove that class of deadlock.

On-chip record: ``chip_smoke.py``'s ``gather_kernel`` stage compiles this
kernel (not interpreted) on a TPU v5e over the full-width ring the replay
stores (``u8[2^20, 8, 896]``) with one learner step's 4096 row ids and
compares it bit-for-bit with ``jnp.take``.  It is correct there; whether it
is FASTER than the XLA gather has not been measured, so the kernel stays
strictly OPT-IN (see :func:`resolved_mode`): ``gather_mode="pallas"`` on
the replay spec, or the process-global ``APEX_GATHER_MODE=pallas`` — which
still gates per-operand on layout eligibility.  Everything else (CPU CI,
the virtual mesh, un-opted TPU runs) takes ``jnp.take``; parity is also
pinned by ``tests/test_gather.py`` in interpret mode.

Mosaic constrains DMA slices of 2-D buffers to (8, 128)-tile boundaries, so
single-row slices of ``[F, D]`` only lower when each row is itself a whole
number of tiles: rows must span a multiple of ``ROW_UNIT = 8 * 128``
elements.  :class:`~apex_tpu.replay.frame_pool.FramePoolReplay` pads its
ring rows to this unit for pixel frames (84x84 -> 7168, +1.6%); the kernel
then views the ring as ``[F, 8, D/8]`` and blocks dim 0, which carries no
tiling constraint.

Reference analogue: the torch side pays this cost in
``_encode_sample``'s host-side ``np.stack`` of LazyFrames
(``memory.py:348-362``) — per-sample Python decompression on the replay
host.  Here it is one compiled device op either way; the kernel is meant
to remove XLA's gather overhead on top (not measured).
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one (8, 128) tile, in elements: the row-size quantum the kernel needs
ROW_UNIT = 8 * 128


def _gather_kernel(ids_ref, in_ref, out_ref):
    """Per grid step: one gathered row, already staged into VMEM by the
    pipeline (the in_spec's index_map chose the source row from the
    prefetched ids).  The body is a plain VMEM copy; all DMA issue/wait
    is Mosaic's."""
    del ids_ref
    out_ref[...] = in_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_gather(frames3: jax.Array, ids: jax.Array,
                   interpret: bool = False) -> jax.Array:
    """``frames3`` MUST already be the tiled 3-D view ``[F, 8, D/8]`` —
    reshaping a 2-D ring inside the same jit makes XLA materialize a copy
    of the whole ring as the custom-call operand, which costs more than the
    gather itself.  FramePoolReplay therefore STORES its ring 3-D."""
    n, c = ids.shape[0], frames3.shape[2]
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[pl.BlockSpec((1, 8, c),
                                   lambda i, ids: (ids[i], 0, 0))],
            out_specs=pl.BlockSpec((1, 8, c), lambda i, ids: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, 8, c), frames3.dtype),
        interpret=interpret,
    )(ids, frames3)
    return out.reshape(n, 8 * c)


def pallas_eligible(d: int, dtype) -> bool:
    """Row layouts the TPU kernel can slice: whole (8, 128) tiles.
    FramePoolReplay pads pixel rows to satisfy this.  (bf16's (16, 128)
    native tile doesn't fit the 8-sublane row view — frames are u8/f32.)"""
    return d % ROW_UNIT == 0 and jnp.dtype(dtype).itemsize in (1, 4)


def resolved_mode(frames: jax.Array, mode: str = "auto") -> str:
    """The concrete path :func:`gather_rows` will take for this operand —
    ``pallas`` | ``interpret`` | ``xla`` — with the ``APEX_GATHER_MODE``
    operational override applied.  Benches report this so a silent
    fallback is visible in the recorded JSON.

    ``auto`` currently resolves to ``xla`` EVERYWHERE, including eligible
    TPU layouts: the kernel is correct on the chip (module docstring) but
    has not been timed against the XLA gather there, and a misbehaving
    gather kernel doesn't just fail, it can wedge the whole device for
    every later client.  Until a trace says it wins, the kernel path is
    strictly opt-in — ``APEX_GATHER_MODE=pallas`` or an explicit
    ``gather_mode="pallas"`` — and ``chip_smoke.py`` attempts that
    opt-in LAST, after the other stages are recorded."""
    if mode != "auto":
        if mode == "pallas":
            # explicit API opt-in gets the same per-operand eligibility
            # gate as the env override — but loudly: the caller named the
            # kernel path, so an unsliceable layout is a usage error
            # worth a clear message, not a Mosaic lowering traceback (and
            # not a silent xla swap that would misreport what's being
            # measured).  ``interpret`` stays permissive down to the
            # d % 8 row-view check in :func:`gather_rows` — it is the CPU
            # emulation lane and deliberately parity-tests layouts the
            # chip would reject.
            d = math.prod(frames.shape[1:])
            if not (frames.ndim == 3 and pallas_eligible(d, frames.dtype)):
                raise ValueError(
                    f"gather_mode='pallas' needs the tiled 3-D ring view "
                    f"[F, 8, D/8] with rows of whole (8, 128) tiles "
                    f"(D % {ROW_UNIT} == 0) and a 1- or 4-byte dtype; "
                    f"got shape {frames.shape} dtype {frames.dtype}. "
                    f"Use 'xla' (or 'auto') for this layout.")
        return mode
    forced = os.environ.get("APEX_GATHER_MODE")
    if forced not in (None, "", "auto"):
        if forced not in ("pallas", "interpret", "xla"):
            raise ValueError(
                f"APEX_GATHER_MODE={forced!r}: expected pallas | "
                f"interpret | xla | auto")
        if forced in ("pallas", "interpret"):
            # the env opt-in is process-GLOBAL but eligibility is
            # per-OPERAND: a process can hold both an eligible pixel ring
            # (stored 3-D) and a small vector pool (2-D, rows not whole
            # tiles) — the latter must quietly keep the XLA path rather
            # than hand Mosaic an unsliceable layout (interpret gets the
            # same gate so a CPU parity lane behaves like the chip would)
            d = math.prod(frames.shape[1:])
            if not (frames.ndim == 3 and pallas_eligible(d, frames.dtype)):
                return "xla"
        return forced
    return "xla"


def gather_rows(frames: jax.Array, ids: jax.Array,
                mode: str = "auto") -> jax.Array:
    """Row gather from a frame ring; returns flat rows ``[N, D]``.

    ``frames`` is either the flat ring ``[F, D]`` or the tiled 3-D view
    ``[F, 8, D/8]`` the pallas kernel needs (what FramePoolReplay stores
    for pixel frames).  mode: ``auto`` currently resolves to ``jnp.take``
    everywhere unless ``APEX_GATHER_MODE`` overrides (see
    :func:`resolved_mode` for why); ``pallas`` / ``interpret`` / ``xla``
    force a path (tests, benches, opted-in production).
    """
    d = math.prod(frames.shape[1:])
    mode = resolved_mode(frames, mode)
    if mode in ("pallas", "interpret"):
        if d % 8:
            raise ValueError(
                f"pallas gather needs row dim % 8 == 0, got {d}")
        f3 = (frames if frames.ndim == 3
              else frames.reshape(frames.shape[0], 8, d // 8))
        return _pallas_gather(f3, ids, interpret=(mode == "interpret"))
    return jnp.take(frames, ids, axis=0).reshape(ids.shape[0], d)
