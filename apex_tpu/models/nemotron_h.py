"""Nemotron-H (``nemotron_h``) as a Q-network over token contexts: the
52-layer causal tower that
https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16
``config.json`` defines, a layer-pattern string of three kinds of layer,
each ONE pre-norm residual part ``x <- x + part(RMSNorm(x))``:

``M``  a Mamba-2 mixer (Dao & Gu 2024, arXiv:2405.21060): in-projection to
       ``[z | xBC | dt]``, a causal depthwise convolution of 4 over
       ``xBC``, the selective state-space recurrence ``S_t = exp(dt_t a)
       S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t`` a head, a gated
       per-group RMSNorm and the out-projection;
``E``  one shared and ``k`` of ``n`` routed two-matrix ``relu^2`` experts:
       :class:`apex_tpu.models.glm4_moe_lite.MoE`, the expert layer of
       every token torso;
``*``  grouped-query attention, no position embedding (Nemotron-H,
       arXiv:2504.03624), through :func:`apex_tpu.ops.attention
       .causal_attention`.

As in :mod:`apex_tpu.models.glm4_moe_lite` a frame is a context of ``T``
ids, ``Q(s, .)`` is the output head at the last position over the ids
held, and the model is **one chip's share** of a deployment that divides
each layer: the expert layer is told which routed experts it holds, and
the two mixers are told how many HEADS they hold.  A Mamba-2 head reads
the ``B`` and ``C`` of its group and the gated norm is per group, so a
chip that holds whole groups (heads ``[rank * held, (rank + 1) * held)``
with their groups, their channels of the convolution and their rows of
the out-projection) computes its heads exactly and its out-projection
gives a partial sum; likewise the query heads of whole key/value heads.
That partial sum is what goes on to the next layer: nothing is routed to,
added for or stood in for the absent heads (:func:`share_of_layer` cuts an
uncut layer's parameters into a rank's).

The scan is computed chunked (the "SSD" form): within a chunk of
``chunk_size`` positions the quadratic form ``(L o C B^T) (dt x)`` with
``L`` the lower-triangular decay, between chunks the carried state, one
short ``lax.scan`` over the chunks.  Decays, their cumulative sums (always
as differences under ``exp``) and the carried state are float32; the
products' operands are in ``compute_dtype`` with float32 accumulation.
Plain JAX, one implementation (:data:`SSD_IMPL`); each layer is
rematerialised (``nn.remat``), the scan with it.

Not held: the second (denoiser) tower, adaLN, cross-tower conditioning and
block-diffusion decoding, which ``config.json`` gives no sizes for and a
Q-network, which generates nothing, has no work for; a state / key-value
cache (training and acting both run whole contexts).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from apex_tpu.models.glm4_moe_lite import (Linear, MoE, RMSNorm, Weight, _Base,
                                           _normal, no_routing, routing_stats,
                                           token_ids)
from apex_tpu.ops import attention, grouped

#: what computes the scan (``torso_layout``'s ``ssd_impl``)
SSD_IMPL = "xla"

#: ``--torso`` presets: the published widths at one of 16 chips' share of
#: each layer (routed experts 16-way, mixer heads 2-way, vocabulary 8-way),
#: the pattern's first nine layers; and the toy the CPU tests run, cut the
#: same way.  ``*_held`` count what this chip holds of the published
#: ``mamba_num_heads`` / ``num_attention_heads`` / ``n_routed_experts``.
PRESETS: dict[str, dict[str, Any]] = {
    "nemotron_twotower_ep16": dict(
        hidden_size=2688, pattern="MEMEM*EME",
        mamba_num_heads=64, mamba_heads_held=32, mamba_head_dim=64,
        n_groups=8, ssm_state_size=128, conv_kernel=4, chunk_size=128,
        num_attention_heads=32, attention_heads_held=16,
        num_key_value_heads=2, head_dim=128,
        moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
        n_routed_experts=128, n_held_experts=8, num_experts_per_tok=6,
        routed_scaling_factor=2.5,
        vocab_held=16384, norm_eps=1e-5, context=1024),
    "nemotron_h_tiny": dict(
        hidden_size=64, pattern="ME*ME",
        mamba_num_heads=4, mamba_heads_held=2, mamba_head_dim=16,
        n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=8,
        num_attention_heads=4, attention_heads_held=2,
        num_key_value_heads=2, head_dim=16,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
        n_routed_experts=8, n_held_experts=2, num_experts_per_tok=2,
        routed_scaling_factor=2.5,
        vocab_held=64, norm_eps=1e-5, context=32),
}


def held_widths(c: dict) -> dict[str, int]:
    """Heads, groups and key/value heads a chip holds at a preset's
    ``*_held`` counts: whole groups and whole key/value heads only."""
    per_group = c["mamba_num_heads"] // c["n_groups"]
    per_kv = c["num_attention_heads"] // c["num_key_value_heads"]
    hm, ha = c["mamba_heads_held"], c["attention_heads_held"]
    if hm % per_group or ha % per_kv:
        raise ValueError(f"held heads {hm} / {ha} are not whole groups of "
                         f"{per_group} / key-value heads of {per_kv}")
    return dict(mamba_heads=hm, groups=hm // per_group, attn_heads=ha,
                kv_heads=ha // per_kv)


# -- the scan -----------------------------------------------------------------

def ssd(x, dt, a, b_in, c_in, d_skip, chunk: int, compute_dtype):
    """The selective state-space recurrence over whole contexts, chunked.

    ``x [b, T, H, P]``, ``dt [b, T, H]`` (after ``softplus``), ``a [H]``
    (negative), ``b_in``, ``c_in`` ``[b, T, G, N]`` (head ``h`` reads group
    ``h // (H / G)``), ``d_skip [H]`` -> ``y f32[b, T, H, P]`` with the
    state nought at every context's start."""
    bsz, t, h, p = x.shape
    g, n = b_in.shape[2:]
    r, q = h // g, min(chunk, t)
    if t % q:
        raise ValueError(f"a context of {t} is not whole chunks of {q}")
    nc = t // q
    f32, cd = jnp.float32, compute_dtype
    mm = functools.partial(jnp.einsum, preferred_element_type=f32)

    x32 = x.astype(f32)
    xd32 = (x32 * dt[..., None]).reshape(bsz, nc, q, g, r, p)
    xd = xd32.astype(cd)
    bc = b_in.astype(cd).reshape(bsz, nc, q, g, n)
    cc = c_in.astype(cd).reshape(bsz, nc, q, g, n)
    # log-decay a step, and its running sum inside a chunk: [b, c, G, r, Q]
    da = (dt * a).reshape(bsz, nc, q, g, r).transpose(0, 1, 3, 4, 2)
    cum = jnp.cumsum(da, axis=-1)
    # inside a chunk: (L o C B^T)(dt x), L[l, s] = exp(cum_l - cum_s), l >= s
    lower = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    scores = mm("bclgn,bcsgn->bcgls", cc, bc)
    y = mm("bcgrls,bcsgrp->bclgrp",
           (scores[:, :, :, None] * decay).astype(cd), xd)
    # what a chunk adds to the state by its end, each step decayed to there
    to_end = jnp.exp(cum[..., -1:] - cum).transpose(0, 1, 4, 2, 3)
    states = mm("bcsgn,bcsgrp->bcgrpn", bc,
                (xd32 * to_end[..., None]).astype(cd))
    # between chunks: the state that enters each, carried in float32
    chunk_decay = jnp.exp(cum[..., -1])

    def carry(s, inp):
        add, dec = inp
        return s * dec[..., None, None] + add, s

    _, entering = jax.lax.scan(
        carry, jnp.zeros((bsz, g, r, p, n), f32),
        (states.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)))
    entering = entering.swapaxes(0, 1)
    from_start = jnp.exp(cum).transpose(0, 1, 4, 2, 3)
    y = y + mm("bclgn,bcgrpn->bclgrp", cc,
               entering.astype(cd)) * from_start[..., None]
    return y.reshape(bsz, t, h, p) + x32 * d_skip[:, None]


@jax.custom_vjp
def causal_conv(x, w):
    """Causal depthwise convolution ``out[t] = sum_i w[i] x[t + i - (K -
    1)]`` of ``x [b, T, C]`` with ``w f32[K, C]`` -> float32.  Its backward
    pass keeps ``x`` as it came (the compute dtype) and makes the shifted
    products again: autodiff of the shifted sum keeps ``K`` float32 copies
    of ``x`` a layer (0.8 GB at the published widths)."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, i:i + t] * w[i] for i in range(k))


def _causal_conv_fwd(x, w):
    return causal_conv(x, w), (x, w)


def _causal_conv_bwd(res, dy):
    x, w = res
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    dw = jnp.stack([(dy * padded[:, i:i + t]).sum((0, 1)) for i in range(k)])
    ahead = jnp.pad(dy, ((0, 0), (0, k - 1), (0, 0)))
    dx = sum(ahead[:, k - 1 - i:k - 1 - i + t] * w[i] for i in range(k))
    return dx.astype(x.dtype), dw


causal_conv.defvjp(_causal_conv_fwd, _causal_conv_bwd)


def _a_log_init(_key, shape, dtype=jnp.float32):
    """``log(1 .. 16)`` over the heads: Mamba-2's default range of ``A``."""
    return jnp.log(jnp.linspace(1.0, 16.0, shape[0], dtype=dtype))


def _dt_bias_init(_key, shape, dtype=jnp.float32):
    """The inverse ``softplus`` of steps spread over ``time_step_min ..
    time_step_max`` = 0.001 .. 0.1 (Mamba-2's default)."""
    dt = jnp.asarray(np.geomspace(1e-3, 1e-1, shape[0]), dtype)
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba2(_Base):
    """The Mamba-2 mixer over the heads held: ``num_heads`` heads of
    ``head_dim`` in ``n_groups`` groups, all whole."""

    num_heads: int = 32
    head_dim: int = 64
    n_groups: int = 4
    state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    eps: float = 1e-5

    @nn.compact
    def __call__(self, u):
        dt_c = self.compute_dtype
        b, t, d = u.shape
        h, p, g, n = (self.num_heads, self.head_dim, self.n_groups,
                      self.state_size)
        inner, bc = h * p, g * n
        # one published kernel ``[z | xBC | dt]``; the step sizes leave it
        # in float32 (a rounded ``dt`` is a rounded decay at every later
        # position), the rest in the compute dtype
        w_in = Weight(dt_c, (d, 2 * inner + 2 * bc + h), name="in_proj")()
        proj = self.dot(u, w_in[:, :-h])
        dt = self.dot(u, w_in[:, -h:], jnp.float32)
        w = self.param("conv_kernel", _normal(),
                       (self.conv_kernel, inner + 2 * bc))
        bias = self.param("conv_bias", nn.initializers.zeros,
                          (inner + 2 * bc,))
        a_log = self.param("A_log", _a_log_init, (h,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,))
        d_skip = self.param("D", nn.initializers.ones, (h,))
        gain = self.param("norm_scale", nn.initializers.ones, (inner,))

        z, xbc = proj[..., :inner], proj[..., inner:]
        with jax.named_scope("conv"):
            xbc = jax.nn.silu(causal_conv(xbc, w) + bias)
        with jax.named_scope("ssd"):
            y = ssd(xbc[..., :inner].reshape(b, t, h, p),
                    jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log),
                    xbc[..., inner:inner + bc].reshape(b, t, g, n),
                    xbc[..., inner + bc:].reshape(b, t, g, n), d_skip,
                    self.chunk_size, dt_c)
        # gated norm: the gate first, then RMSNorm a group of channels
        y = (y.reshape(b, t, inner) * jax.nn.silu(z)).reshape(b, t, g, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + self.eps)
        y = y.reshape(b, t, inner) * gain
        return Linear(dt_c, d, name="out_proj")(y, jnp.float32)


class GQA(_Base):
    """Grouped-query causal attention over whole contexts for the query
    heads held, with their key/value heads; no bias, no position
    embedding, no cache.  ``q`` is written ``[b, H, T, d]``, ``k`` and
    ``v`` ``[b, H_kv, T, d]``."""

    num_heads: int = 16
    num_kv_heads: int = 1
    head_dim: int = 128

    @nn.compact
    def __call__(self, u):
        dt = self.compute_dtype
        d = u.shape[-1]
        nh, nkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        mm = functools.partial(jnp.einsum,
                               preferred_element_type=jnp.float32)

        def heads(name, count):
            w = Weight(dt, (d, count * hd), name=name)()
            return mm("btd,dhk->bhtk", u.astype(dt),
                      w.reshape(d, count, hd)).astype(dt)

        o = attention.causal_attention(heads("q", nh), heads("k", nkv),
                                       heads("v", nkv), hd ** -0.5)
        w_o = Weight(dt, (nh * hd, d), name="o")()
        return mm("bhtk,hkf->btf", o, w_o.reshape(nh, hd, d))


class Layer(_Base):
    """``x + part(RMSNorm(x))``, the part by the pattern's letter."""

    cfg: Any = None             # the preset's dict, frozen
    kind: str = "M"
    expert_rank: int = 0

    @nn.compact
    def __call__(self, x):
        c, dt = dict(self.cfg), self.compute_dtype
        held = held_widths(c)
        u = RMSNorm(c["norm_eps"], name="norm")(x)
        routing = no_routing(c["n_held_experts"])
        if self.kind == "M":
            with jax.named_scope("mamba"):
                y = Mamba2(dt, held["mamba_heads"], c["mamba_head_dim"],
                           held["groups"], c["ssm_state_size"],
                           c["conv_kernel"], c["chunk_size"], c["norm_eps"],
                           name="mamba")(u)
        elif self.kind == "*":
            with jax.named_scope("attention"):
                y = GQA(dt, held["attn_heads"], held["kv_heads"],
                        c["head_dim"], name="attention")(u)
        elif self.kind == "E":
            y, routing = MoE(dt, c["moe_intermediate_size"],
                             c["n_routed_experts"], c["n_held_experts"],
                             self.expert_rank, c["num_experts_per_tok"],
                             c["routed_scaling_factor"], "relu2",
                             c["moe_shared_expert_intermediate_size"],
                             name="moe")(u)
        else:
            raise ValueError(f"layer kind {self.kind!r} in the pattern")
        return x + y, routing


class NemotronHQ(nn.Module):
    """``Q(s, .) = RMSNorm(x_T) W_head`` over the ids held, float32."""

    num_actions: int
    preset: str = "nemotron_h_tiny"
    compute_dtype: Any = jnp.bfloat16
    expert_rank: int = 0
    n_held_experts: int | None = None   # None = the preset's share
    remat: bool = True

    #: ``__call__(obs, with_stats=True)`` also returns routing scalars
    #: (``models.learner_apply_fn``)
    COUNTS_STATS = True
    #: leaves the forward pass reads in float32 (``models.acting_params``):
    #: gains, the router, rows read without a product, and what the scan
    #: and the convolution compute in float32
    ACTING_KEEPS_FLOAT32 = ("scale", "router_kernel", "router_bias",
                            "embedding", "A_log", "dt_bias", "D",
                            "norm_scale", "conv_kernel", "conv_bias")

    @property
    def cfg(self) -> dict[str, Any]:
        c = dict(PRESETS[self.preset], vocab_held=self.num_actions)
        if self.n_held_experts is not None:
            c["n_held_experts"] = self.n_held_experts
        return c

    def attention_path(self, platform: str) -> dict:
        """Which implementation the ``*`` layers' attention takes in a
        program compiled for ``platform``."""
        c = self.cfg
        return attention.attention_path(c["context"], c["head_dim"],
                                        c["head_dim"], platform)

    def grouped_path(self, platform: str) -> dict:
        """What the ``E`` layers hand the grouped kernel at this preset's
        widths in a program compiled for ``platform``."""
        c = self.cfg
        return grouped.grouped_path(c["hidden_size"],
                                    c["moe_intermediate_size"], platform)

    def torso_layout(self) -> dict:
        """What this chip holds of each layer: the arguments of the trace
        ring's ``torso_layout`` instant."""
        c, held = self.cfg, held_widths(self.cfg)
        return {
            "pattern": c["pattern"],
            "mamba_heads": f"{held['mamba_heads']}/{c['mamba_num_heads']}",
            "groups": f"{held['groups']}/{c['n_groups']}",
            "attn_heads": f"{held['attn_heads']}/{c['num_attention_heads']}",
            "kv_heads": f"{held['kv_heads']}/{c['num_key_value_heads']}",
            "experts": f"{c['n_held_experts']}/{c['n_routed_experts']}",
            "expert_rank": self.expert_rank,
            "chunk": c["chunk_size"], "ssd_impl": SSD_IMPL,
            "params": param_count(c)}

    @nn.compact
    def __call__(self, obs, with_stats: bool = False):
        c, dt = self.cfg, self.compute_dtype
        with jax.named_scope("embed"):
            emb = self.param("embedding", _normal(),
                             (c["vocab_held"], c["hidden_size"]))
            x = emb[token_ids(obs, c["vocab_held"])]
        layer = nn.remat(Layer) if self.remat else Layer
        frozen = tuple(sorted(c.items()))
        routing = []
        for i, kind in enumerate(c["pattern"]):
            x, r = layer(dt, frozen, kind, self.expert_rank,
                         name=f"layers_{i}")(x)
            routing.append(r)
        with jax.named_scope("q_head"):
            last = RMSNorm(c["norm_eps"], name="final_norm")(x[:, -1])
            q = Linear(dt, c["vocab_held"], name="head")(last, jnp.float32)
        if not with_stats:
            return q
        pairs = (obs.shape[0] * (obs.shape[1] // 2) * c["pattern"].count("E")
                 * c["num_experts_per_tok"])
        return q, routing_stats(routing, pairs)


def share_of_layer(kind: str, p: dict, c: dict, head_rank: int) -> dict:
    """A rank's share of an UNCUT ``M`` or ``*`` layer's parameters ``p``
    (held = published in the preset ``c`` they were made with): its heads,
    their groups, their channels of the convolution and of the gated norm,
    their rows of the out-projection; the pre-norm whole."""
    held = held_widths(c)

    def cols(x, width, count, axis=-1):
        """``count`` blocks of ``width`` from block ``head_rank * count``."""
        lo = head_rank * count * width
        return jax.lax.slice_in_dim(x, lo, lo + count * width, axis=axis)

    if kind == "*":
        hd, a = c["head_dim"], p["attention"]
        cut = {"q": cols(a["q"]["kernel"], hd, held["attn_heads"]),
               "k": cols(a["k"]["kernel"], hd, held["kv_heads"]),
               "v": cols(a["v"]["kernel"], hd, held["kv_heads"]),
               "o": cols(a["o"]["kernel"], hd, held["attn_heads"], 0)}
        return {"norm": p["norm"],
                "attention": {k: {"kernel": v} for k, v in cut.items()}}
    m = p["mamba"]
    h_all, g_all = c["mamba_num_heads"], c["n_groups"]
    pd, n = c["mamba_head_dim"], c["ssm_state_size"]
    h, g = held["mamba_heads"], held["groups"]

    def channels(x, axis=-1, gate=True):
        """The held slices of ``[z |] x | B | C [| dt]`` along ``axis``."""
        at, parts = 0, []
        for width, count, total in (
                [(pd, h, h_all)] * (2 if gate else 1)
                + [(n, g, g_all)] * 2 + ([(1, h, h_all)] if gate else [])):
            block = jax.lax.slice_in_dim(x, at, at + total * width, axis=axis)
            parts.append(cols(block, width, count, axis))
            at += total * width
        return jnp.concatenate(parts, axis)

    return {"norm": p["norm"], "mamba": {
        "in_proj": {"kernel": channels(m["in_proj"]["kernel"])},
        "conv_kernel": channels(m["conv_kernel"], gate=False),
        "conv_bias": channels(m["conv_bias"], gate=False),
        "A_log": cols(m["A_log"], 1, h), "dt_bias": cols(m["dt_bias"], 1, h),
        "D": cols(m["D"], 1, h),
        "norm_scale": cols(m["norm_scale"], pd, h),
        "out_proj": {"kernel": cols(m["out_proj"]["kernel"], pd, h, 0)}}}


def param_count(c: dict) -> int:
    """Parameters of a preset ``c`` at its share, from its widths."""
    held, d = held_widths(c), c["hidden_size"]
    inner = held["mamba_heads"] * c["mamba_head_dim"]
    conv = inner + 2 * held["groups"] * c["ssm_state_size"]
    mamba = (d * (inner + conv + held["mamba_heads"])
             + (c["conv_kernel"] + 1) * conv + 3 * held["mamba_heads"]
             + inner + inner * d + d)
    attn = (2 * d * held["attn_heads"] * c["head_dim"]
            + 2 * d * held["kv_heads"] * c["head_dim"] + d)
    moe = (d * c["n_routed_experts"] + c["n_routed_experts"]
           + 2 * d * c["moe_shared_expert_intermediate_size"]
           + c["n_held_experts"] * 2 * d * c["moe_intermediate_size"] + d)
    per = {"M": mamba, "*": attn, "E": moe}
    return (sum(per[k] for k in c["pattern"]) + 2 * c["vocab_held"] * d + d)
