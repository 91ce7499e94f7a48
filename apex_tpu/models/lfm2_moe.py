"""LFM2-8B-A1B (``lfm2_moe``) as a Q-network over token contexts: the
24-layer stack that https://huggingface.co/LiquidAI/LFM2-8B-A1B
``config.json`` defines.  Every layer is a mixer and a feed-forward part,
both pre-norm residual: ``x <- x + mixer(operator_norm(x))``; ``x <- x +
ffn(ffn_norm(x))``; after the last layer ``embedding_norm``, then the head.
``layer_types[i]`` names the mixer (18 ``conv``, 6 ``full_attention``:
from layer 2 on a period of attention, conv, conv, conv); the first
``num_dense_layers`` layers have a dense SwiGLU, the others the experts.

**Double-gated short convolution** (``conv``; the dense LFM2's
``Lfm2ShortConv`` in transformers): ``[B | C | x] = h W_in``, ``u = B *
x``, ``z[t] = sum_i w[i] u[t + i - (K - 1)]`` (depthwise, ``K =
conv_L_cache`` = 3 taps, zero history, no bias), ``out = (C * z) W_out``.
No activation: the two input-dependent gates are the mechanism.  The
three-tap sum is :func:`apex_tpu.models.nemotron_h.causal_conv` (float32,
its backward pass keeps ``u`` in the compute dtype).

**QK-norm attention** (``full_attention``): grouped-query causal softmax
with RMSNorm on each query and key head (a gain of ``head_dim``), then
rotate-half RoPE on the whole head, through
:func:`apex_tpu.ops.attention.causal_attention`; no bias, no gate.

**Experts**: :class:`apex_tpu.models.glm4_moe_lite.MoE`, the expert layer
of every token torso, with sigmoid scores, a bias that only selects
(``use_expert_bias``), the picks' scores renormalised (``norm_topk_prob``)
and scaled by ``routed_scaling_factor`` 1, and NO shared expert
(``n_shared_experts=0``).

Every norm is ``w * x / rms(x)`` (a gain, not zero-centred).  The
embedding is **tied** to the head: ``Q(s, .) = embedding_norm(x_T)
E^T`` over the ids held.  As in the other token torsos a frame is a
context of ``T`` ids and the model is **one chip's share** of a deployment
that divides each layer: here the experts 4-way (the expert layer is told
which it holds) and the vocabulary 4-way; mixers, router, norms and dense
feed-forward are whole on every chip.

Not held: a convolution state or key/value cache (training and acting both
run whole contexts).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from apex_tpu.models.glm4_moe_lite import (FeedForward, Linear, MoE, RMSNorm,
                                           Weight, _Base, _normal, no_routing,
                                           rope, routing_stats, token_ids)
from apex_tpu.models.nemotron_h import causal_conv
from apex_tpu.ops import attention, grouped

#: ``--torso`` presets: the published widths at one of 4 chips' share of
#: each layer (routed experts 4-way, vocabulary 4-way; the rest whole), the
#: leading dense layer and one whole period of the layer pattern; and the
#: toy the CPU tests run, cut the same way.  ``layer_types`` are the held
#: layers' (published layers 0, 2, 3, 4, 5); ``vocab_size`` and
#: ``num_experts`` are the published counts, ``vocab_held`` and
#: ``n_held_experts`` what this chip holds.
PRESETS: dict[str, dict[str, Any]] = {
    "lfm2_8b_a1b_ep4": dict(
        hidden_size=2048,
        layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        num_dense_layers=1, conv_L_cache=3, num_attention_heads=32,
        num_key_value_heads=8, head_dim=64, rope_theta=1e6,
        intermediate_size=7168, moe_intermediate_size=1792, num_experts=32,
        n_held_experts=8, num_experts_per_tok=4, routed_scaling_factor=1.0,
        vocab_size=65536, vocab_held=16384, norm_eps=1e-5, context=1024,
        ffn_block=4096),
    "lfm2_tiny": dict(
        hidden_size=64,
        layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        num_dense_layers=1, conv_L_cache=3, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, rope_theta=1e6,
        intermediate_size=128, moe_intermediate_size=32, num_experts=8,
        n_held_experts=2, num_experts_per_tok=4, routed_scaling_factor=1.0,
        vocab_size=65536, vocab_held=64, norm_eps=1e-5, context=32),
}

#: a layer's mixer by its ``layer_types`` entry, as one letter
KINDS = {"conv": "C", "full_attention": "A"}


def pattern(c: dict) -> str:
    """A letter a held layer: ``C`` short conv, ``A`` QK-norm attention."""
    return "".join(KINDS[kind] for kind in c["layer_types"])


class ShortConv(_Base):
    """The double-gated short convolution over the whole hidden width."""

    conv_kernel: int = 3

    @nn.compact
    def __call__(self, h):
        dt = self.compute_dtype
        d = h.shape[-1]
        b_gate, c_gate, xs = jnp.split(
            Linear(dt, 3 * d, name="in_proj")(h, jnp.float32), 3, axis=-1)
        w = self.param("conv_kernel", _normal(), (self.conv_kernel, d))
        with jax.named_scope("conv"):
            z = causal_conv((b_gate * xs).astype(dt), w)
        return Linear(dt, d, name="out_proj")(c_gate * z, jnp.float32)


class QKNormAttention(_Base):
    """Grouped-query causal attention over whole contexts: RMSNorm on each
    query and key head, RoPE on the whole head; no bias, no cache.  ``q``
    is written ``[b, H, T, d]``, ``k`` and ``v`` ``[b, H_kv, T, d]``."""

    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    eps: float = 1e-5

    @nn.compact
    def __call__(self, u):
        dt = self.compute_dtype
        d = u.shape[-1]
        nh, nkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        mm = functools.partial(jnp.einsum,
                               preferred_element_type=jnp.float32)

        def heads(name, count):
            w = Weight(dt, (d, count * hd), name=name)()
            return mm("btd,dhk->bhtk", u.astype(dt), w.reshape(d, count, hd))

        def turned(x, name):
            return rope(RMSNorm(self.eps, name=name)(x),
                        self.rope_theta).astype(dt)

        o = attention.causal_attention(
            turned(heads("q_proj", nh), "q_layernorm"),
            turned(heads("k_proj", nkv), "k_layernorm"),
            heads("v_proj", nkv).astype(dt), hd ** -0.5)
        w_o = Weight(dt, (nh * hd, d), name="out_proj")()
        return mm("bhtk,hkf->btf", o, w_o.reshape(nh, hd, d))


class Layer(_Base):
    """``x += mixer(operator_norm(x))``; ``x += ffn(ffn_norm(x))``, the
    mixer by the layer's kind, the feed-forward dense or the experts."""

    cfg: Any = None             # the preset's dict, frozen
    kind: str = "C"
    dense: bool = False
    expert_rank: int = 0

    @nn.compact
    def __call__(self, x):
        c, dt = dict(self.cfg), self.compute_dtype
        eps = c["norm_eps"]
        u = RMSNorm(eps, name="operator_norm")(x)
        if self.kind == "C":
            with jax.named_scope("shortconv"):
                x = x + ShortConv(dt, c["conv_L_cache"],
                                  name="short_conv")(u)
        elif self.kind == "A":
            with jax.named_scope("qk_norm_attention"):
                x = x + QKNormAttention(
                    dt, c["num_attention_heads"], c["num_key_value_heads"],
                    c["head_dim"], c["rope_theta"], eps,
                    name="self_attn")(u)
        else:
            raise ValueError(f"layer kind {self.kind!r} in the pattern")
        h = RMSNorm(eps, name="ffn_norm")(x)
        if self.dense:
            with jax.named_scope("dense_ffn"):
                y = FeedForward(dt, c["intermediate_size"],
                                c.get("ffn_block", 0),
                                name="feed_forward")(h)
            return x + y, no_routing(c["n_held_experts"])
        y, routing = MoE(dt, c["moe_intermediate_size"], c["num_experts"],
                         c["n_held_experts"], self.expert_rank,
                         c["num_experts_per_tok"],
                         c["routed_scaling_factor"], "swiglu", 0,
                         "sigmoid_bias", False, n_shared_experts=0,
                         round0_quarters=2, name="feed_forward")(h)
        return x + y, routing


class Lfm2MoeQ(nn.Module):
    """``Q(s, .) = embedding_norm(x_T) E^T`` over the ids held, float32,
    ``E`` the embedding (tied head)."""

    num_actions: int
    preset: str = "lfm2_tiny"
    compute_dtype: Any = jnp.bfloat16
    expert_rank: int = 0
    n_held_experts: int | None = None   # None = the preset's share
    remat: bool = True

    #: ``__call__(obs, with_stats=True)`` also returns routing scalars
    #: (``models.learner_apply_fn``)
    COUNTS_STATS = True
    #: leaves the forward pass reads in float32 (``models.acting_params``):
    #: gains, the router and its bias, the embedding (its rows are read
    #: without a product; as the head it is cast where it is used) and
    #: the convolution's taps
    ACTING_KEEPS_FLOAT32 = ("scale", "router_kernel", "router_bias",
                            "embedding", "conv_kernel")

    @property
    def cfg(self) -> dict[str, Any]:
        c = dict(PRESETS[self.preset], vocab_held=self.num_actions)
        if self.n_held_experts is not None:
            c["n_held_experts"] = self.n_held_experts
        return c

    def attention_path(self, platform: str) -> dict:
        """Which implementation the ``A`` layers' attention takes in a
        program compiled for ``platform``."""
        c = self.cfg
        return attention.attention_path(c["context"], c["head_dim"],
                                        c["head_dim"], platform)

    def grouped_path(self, platform: str) -> dict:
        """What the expert blocks hand the grouped kernel at this preset's
        widths in a program compiled for ``platform``."""
        c = self.cfg
        return grouped.grouped_path(c["hidden_size"],
                                    c["moe_intermediate_size"], platform)

    def torso_layout(self) -> dict:
        """What this chip holds of each layer: the arguments of the trace
        ring's ``torso_layout`` instant."""
        c = self.cfg
        dense = c["num_dense_layers"]
        return {
            "pattern": pattern(c),
            "ffn": "D" * dense + "E" * (len(c["layer_types"]) - dense),
            "experts": f"{c['n_held_experts']}/{c['num_experts']}",
            "expert_rank": self.expert_rank,
            "shared_experts": 0,
            "vocab": f"{c['vocab_held']}/{c['vocab_size']}",
            "tied_head": 1, "params": param_count(c)}

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
        for i, kind in enumerate(pattern(c)):
            x, r = layer(dt, frozen, kind, i < c["num_dense_layers"],
                         self.expert_rank, name=f"layers_{i}")(x)
            if i >= c["num_dense_layers"]:
                routing.append(r)
        with jax.named_scope("q_head"):
            last = RMSNorm(c["norm_eps"], name="embedding_norm")(x[:, -1])
            q = jnp.dot(last.astype(dt), emb.astype(dt).T,
                        preferred_element_type=jnp.float32)
        if not with_stats:
            return q
        pairs = (obs.shape[0] * (obs.shape[1] // 2)
                 * (len(c["layer_types"]) - c["num_dense_layers"])
                 * c["num_experts_per_tok"])
        return q, routing_stats(routing, pairs)


def param_count(c: dict) -> int:
    """Parameters of a preset ``c`` at its share, from its widths: the
    tied embedding once, the final norm, two norms a layer."""
    d, hd = c["hidden_size"], c["head_dim"]
    conv = 3 * d * d + c["conv_L_cache"] * d + d * d
    attn = (d * hd * (2 * c["num_attention_heads"]
                      + 2 * c["num_key_value_heads"]) + 2 * hd)
    dense = 3 * d * c["intermediate_size"]
    moe = (d * c["num_experts"] + c["num_experts"]
           + c["n_held_experts"] * 3 * d * c["moe_intermediate_size"])
    kinds, n_dense = pattern(c), c["num_dense_layers"]
    return (kinds.count("C") * conv + kinds.count("A") * attn
            + n_dense * dense + (len(kinds) - n_dense) * moe
            + len(kinds) * 2 * d + c["vocab_held"] * d + d)
