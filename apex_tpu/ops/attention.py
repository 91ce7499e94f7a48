"""Causal attention ``softmax(q k^T * scale) v`` for the token torsos
(:mod:`apex_tpu.models`): one algorithm, two implementations, picked by
what the code can observe.

``plain`` is the ``jax.numpy`` path: scores and softmax in float32, the
two products in the operands' dtype with float32 accumulation.  It writes
the ``[b, H, T, T]`` float32 scores to HBM, reads them for the mask and
the softmax, writes the probabilities and reads them for ``p v``; under
``jax.grad`` it keeps them for the backward pass.  At the published widths
that traffic, not the products, was 60% of a learner update on a v5e
(PERF.md, PR 29).

``fused`` is the flash-attention kernel JAX ships
(:mod:`jax.experimental.pallas.ops.tpu.flash_attention`): a block of
scores, the running maximum and the running sum stay in VMEM (online
softmax, float32 there; the operands go to the MXU as they come, float32
accumulation: the same precision as ``plain``), blocks above the diagonal
are skipped, and the backward pass makes the scores again from ``q``, ``k``
and the saved row statistics (two more kernels, ``dkv`` and ``dq``).

:func:`causal_attention` takes the kernel when the program is compiled for
a TPU (``lax.platform_dependent``: decided when the program is lowered, so
an ahead-of-time compile for a described chip on a CPU host takes it too)
AND the shapes allow it (:func:`kernel_eligible`); everything else, the
CPU tests and the toy preset among it, is ``plain``.  No flag and no
environment variable: :func:`attention_path` says which way a shape goes
on a platform, for the trace ring and the start-up line.

The block size is a constant, chosen by chip runs at ``[16, 20, 1024,
256]`` bfloat16 (PERF.md, PR 30, with the alternatives measured, the
splash-attention kernel of the same package among them).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import flash_attention as _flash

#: a TPU vector register's lanes: the kernel tiles context and head width
#: in multiples of it
LANES = 128
#: rows of ``q`` and columns of ``k`` / ``v`` a grid step works on, in the
#: forward kernel and in both backward kernels (a shorter context is one
#: block).  The shipped default, 128 everywhere, is 3 x slower forward and
#: 2.6 x slower backward at width 256 on a v5e; around 512 the choice is
#: flat to 3% (PERF.md, PR 30)
BLOCK = 512


#: head widths under :data:`LANES` the kernel takes: its row statistics
#: are 128 lanes wide and it slices them to a narrower head
#: (``flash_attention.py``'s ``l_broadcast``); 64 is LFM2-8B-A1B's
#: (``lfm2_moe``), the only such width a token torso has at published size
NARROW = (64,)


def kernel_eligible(context: int, qk_head_dim: int, v_head_dim: int) -> bool:
    """What the kernel takes: a context that is a multiple of 128 and of
    its block; ``q k^T``'s width equal to ``v``'s; and that width a
    multiple of 128 (a head fills whole vector registers) or one of
    :data:`NARROW` (half a register, sliced in the kernel; checked against
    ``plain`` in interpret mode, forward and gradients, and compiled by
    Mosaic for a v5e).  Other narrow widths, the toy presets' among them,
    take ``plain``."""
    return (context % LANES == 0 and context % min(BLOCK, context) == 0
            and (qk_head_dim % LANES == 0 or qk_head_dim in NARROW)
            and qk_head_dim == v_head_dim)


def _blocks(context: int) -> _flash.BlockSizes:
    blk = min(BLOCK, context)
    return _flash.BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=blk, block_k_major_dkv=blk, block_k_dkv=blk,
        block_q_dkv=blk, block_k_major_dq=blk, block_k_dq=blk,
        block_q_dq=blk)


def plain(q: jax.Array, k: jax.Array, v: jax.Array,
          scale: float) -> jax.Array:
    """``[b, H, T, d]`` operands -> ``[b, H, T, d_v]`` in their dtype:
    scores and softmax in float32, through HBM."""
    t = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def fused(q: jax.Array, k: jax.Array, v: jax.Array,
          scale: float) -> jax.Array:
    """The same from the kernel: no ``[T, T]`` buffer leaves the chip's
    VMEM, forward or backward (``custom_vjp``).  Runs on a TPU, or
    anywhere under ``pltpu.force_tpu_interpret_mode()``."""
    return _flash.flash_attention(q, k, v, causal=True, sm_scale=scale,
                                  block_sizes=_blocks(q.shape[2]))


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     scale: float) -> jax.Array:
    """``softmax_causal(q k^T * scale) v`` for ``[b, H, T, d]`` operands
    (already in the dtype the MXU is to multiply).  Grouped queries: where
    ``k`` and ``v`` have fewer heads than ``q``, query heads ``i * H / H_kv
    .. (i + 1) * H / H_kv - 1`` read key/value head ``i``.  Both
    implementations take equal head counts, so the shared heads are
    repeated (``[16, 16, 1024, 128]`` bfloat16 is 67 MB each for ``k`` and
    ``v``); their gradient is the sum over the query heads that read
    them."""
    groups = q.shape[1] // k.shape[1]
    if groups > 1:
        k, v = (jnp.repeat(x, groups, axis=1) for x in (k, v))
    if not kernel_eligible(q.shape[2], q.shape[3], v.shape[3]):
        return plain(q, k, v, scale)
    return jax.lax.platform_dependent(
        q, k, v, tpu=functools.partial(fused, scale=scale),
        default=functools.partial(plain, scale=scale))


def attention_path(context: int, qk_head_dim: int, v_head_dim: int,
                   platform: str) -> dict:
    """Which way :func:`causal_attention` goes at these widths in a
    program compiled for ``platform``: the arguments of the trace ring's
    ``attention_path`` instant."""
    ok = (platform == "tpu"
          and kernel_eligible(context, qk_head_dim, v_head_dim))
    out = {"fused": int(ok), "context": context, "qk_head_dim": qk_head_dim,
           "v_head_dim": v_head_dim, "platform": platform}
    if ok:
        out["block"] = min(BLOCK, context)
    return out
