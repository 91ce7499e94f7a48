"""Qwen3-Next (``qwen3_next``) as a Q-network over token contexts: the
48-layer stack that
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``
defines.  Every layer is a mixer and an expert block, both pre-norm
residual parts: ``x <- x + mixer(norm(x))``; ``x <- x + moe(norm(x))``.
Layer ``i`` is gated softmax attention where ``(i + 1) %
full_attention_interval == 0`` and a Gated DeltaNet otherwise (3 : 1).

**Gated DeltaNet** (Yang et al. 2024, arXiv:2412.06464): one kernel gives
``[q | k | v | z]`` a key head (each key head serves ``Hv / Hk`` value
heads), another ``[b | a]``; a causal depthwise convolution of 4 and
``silu`` over ``[q | k | v]``; ``q`` and ``k`` made unit vectors a head
(``q`` then divided by ``sqrt(d)``); a value head's state is a ``d x d``
MATRIX corrected by what it already holds::

    S <- exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;
    o_t = S^T q_t

with ``beta = sigmoid(b)`` and ``g = -exp(A_log) softplus(a + dt_bias)``;
then a gated RMSNorm a head, ``(w o / rms(o)) silu(z)``, and the
out-projection.  :func:`delta_rule` computes it chunked (the WY / UT
form): inside a chunk of ``chunk_size`` positions ``u`` solves ``(I + A) u
= beta (v - exp(G) k^T S_0)`` with ``A[i, j] = beta_i (k_i . k_j) exp(G_i
- G_j)``, ``i > j``, so the chunk needs the inverse of a unit
lower-triangular matrix.  ``A`` is nilpotent, so :func:`unit_lower_inverse`
is ``(I - A)(I + A^2)(I + A^4) ...`` (:data:`INVERSE`): float32 products
at ``HIGHEST``, no loop over rows.  Decays, their cumulative sums (always
as differences under ``exp``), the inverse and the carried state are
float32; the other products' operands are in ``compute_dtype`` with
float32 accumulation.  One short ``lax.scan`` over the chunks carries the
state.  Plain JAX, one implementation.

**Gated attention**: the query kernel yields the query AND a per-element
output gate a head; RMSNorm on each query / key head; rotate-half RoPE on
the first ``rotary_dim`` of the head's dims; causal softmax through
:func:`apex_tpu.ops.attention.causal_attention`; ``(attn *
sigmoid(gate)) W_o``.

**Experts**: :class:`apex_tpu.models.glm4_moe_lite.MoE`, the expert layer
of every token torso, with a softmax router (``k`` of ``n``, renormalised,
no bias) and a shared expert behind ``sigmoid(h w_g)``.

All norms but the mixer's gated one are zero-centred: ``x / rms(x) * (1 +
w)``.  As in the other token torsos a frame is a context of ``T`` ids,
``Q(s, .)`` is the output head at the last position over the ids held,
and the model is **one chip's share** of a deployment that divides each
layer: the expert layer is told which experts it holds, the mixers how
many HEADS (key heads with their value heads; query heads with their
key/value heads).  Every norm and gate is per head, so a chip computes
its heads exactly and its out-projection gives a partial sum, which is
what goes on to the next layer (:func:`share_of_layer` cuts an uncut
layer's parameters into a rank's).

Not held: the multi-token-prediction module (a Q-network generates
nothing) and a state / key-value cache (training and acting both run
whole contexts).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from apex_tpu.models.glm4_moe_lite import (Linear, MoE, Weight, _Base,
                                           _normal, rms_norm, rope,
                                           routing_stats, token_ids)
from apex_tpu.models.nemotron_h import (_a_log_init, _dt_bias_init,
                                        causal_conv)
from apex_tpu.ops import attention, grouped

#: how the chunk's unit lower-triangular matrix is inverted
#: (``torso_layout``'s ``inverse``)
INVERSE = "nilpotent_doubling_f32"

#: ``--torso`` presets: the published widths at one of 16 chips' share of
#: each layer (routed experts 16-way, mixer heads 2-way, vocabulary 8-way),
#: one whole period of the layer pattern; and the toy the CPU tests run,
#: cut the same way.  ``*_held`` count what this chip holds of the
#: published ``linear_num_key_heads`` / ``num_attention_heads`` /
#: ``num_experts``.
PRESETS: dict[str, dict[str, Any]] = {
    "qwen3_next_80b_ep16": dict(
        hidden_size=2048, num_hidden_layers=4, full_attention_interval=4,
        linear_num_key_heads=16, linear_key_heads_held=8,
        linear_num_value_heads=32, linear_key_head_dim=128,
        linear_value_head_dim=128, linear_conv_kernel_dim=4, chunk_size=64,
        num_attention_heads=16, attention_heads_held=8,
        num_key_value_heads=2, head_dim=256, rotary_dim=64, rope_theta=1e7,
        moe_intermediate_size=512, shared_expert_intermediate_size=512,
        num_experts=512, n_held_experts=32, num_experts_per_tok=10,
        vocab_held=18992, rms_norm_eps=1e-6, context=1024, context_block=8),
    "qwen3_next_tiny": dict(
        hidden_size=64, num_hidden_layers=4, full_attention_interval=4,
        linear_num_key_heads=4, linear_key_heads_held=2,
        linear_num_value_heads=8, linear_key_head_dim=16,
        linear_value_head_dim=16, linear_conv_kernel_dim=4, chunk_size=8,
        num_attention_heads=4, attention_heads_held=2,
        num_key_value_heads=2, head_dim=16, rotary_dim=4, rope_theta=1e7,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_experts=32, n_held_experts=2, num_experts_per_tok=4,
        vocab_held=64, rms_norm_eps=1e-6, context=32, context_block=2),
}


def pattern(c: dict) -> str:
    """A letter a layer: ``D`` Gated DeltaNet, ``A`` gated attention."""
    return "".join("A" if (i + 1) % c["full_attention_interval"] == 0
                   else "D" for i in range(c["num_hidden_layers"]))


def held_widths(c: dict) -> dict[str, int]:
    """Key heads, value heads, query heads and key/value heads a chip
    holds at a preset's ``*_held`` counts: whole key/value heads only."""
    per_key = c["linear_num_value_heads"] // c["linear_num_key_heads"]
    per_kv = c["num_attention_heads"] // c["num_key_value_heads"]
    hk, ha = c["linear_key_heads_held"], c["attention_heads_held"]
    if ha % per_kv:
        raise ValueError(f"held query heads {ha} are not whole key-value "
                         f"heads of {per_kv}")
    return dict(key_heads=hk, value_heads=hk * per_key, attn_heads=ha,
                kv_heads=ha // per_kv)


# -- the delta rule -----------------------------------------------------------

def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` of a strictly lower-triangular float32 ``a [..., c,
    c]``: ``a^c = 0``, so the inverse is the finite product ``(I - a)(I +
    a^2)(I + a^4) ...`` up to the last power under ``c``, its products in
    float32 (``HIGHEST``)."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    out, power = eye - a, a
    for _ in range(max(c - 1, 1).bit_length() - 1):
        power = mm(power, power)
        out = out + mm(out, power)
    return out


def delta_rule(q, k, v, g, beta, chunk: int, compute_dtype):
    """The gated delta rule over whole contexts, chunked.

    ``q``, ``k`` ``[b, T, Hk, dk]`` (unit vectors a head, ``q`` scaled),
    ``v [b, T, Hv, dv]`` (value head ``j`` reads key head ``j // (Hv /
    Hk)``), ``g f32[b, T, Hv]`` (log-decay, negative), ``beta f32[b, T,
    Hv]`` -> ``o f32[b, T, Hv, dv]``, the state nought at every context's
    start."""
    bsz, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    r, c = hv // hk, min(chunk, t)
    if t % c:
        raise ValueError(f"a context of {t} is not whole chunks of {c}")
    nc = t // c
    f32, cd = jnp.float32, compute_dtype
    mm = functools.partial(jnp.einsum, preferred_element_type=f32)

    # [b, n, Hk, c, dk]; [b, n, Hk, r, c, dv]; [b, n, Hk, r, c]
    qc, kc = (x.astype(cd).reshape(bsz, nc, c, hk, dk).transpose(0, 1, 3, 2, 4)
              for x in (q, k))
    v32 = v.astype(f32).reshape(bsz, nc, c, hk, r, dv).transpose(
        0, 1, 3, 4, 2, 5)
    gc, bc = (x.astype(f32).reshape(bsz, nc, c, hk, r).transpose(
        0, 1, 3, 4, 2) for x in (g, beta))
    # the log-decay's running sum inside a chunk, as a float32 product with
    # a triangle of ones (``jnp.cumsum`` lowers to an operation without a
    # scope path)
    lower = jnp.tril(jnp.ones((c, c), bool))
    cum = jnp.einsum("bnhrj,ij->bnhri", gc, lower.astype(f32),
                     precision=jax.lax.Precision.HIGHEST)
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    # (I + A) u = beta (v - exp(G) k^T S_0): A's inverse, then its two
    # right-hand sides, the values and the keys decayed from the chunk's start
    kk = mm("bnhik,bnhjk->bnhij", kc, kc)[:, :, :, None]
    a = jnp.where(jnp.tril(lower, -1), kk * decay * bc[..., :, None], 0.0)
    inv = unit_lower_inverse(a).astype(cd)
    k32 = kc.astype(f32)[:, :, :, None]
    from_start = jnp.exp(cum)
    u = mm("bnhrij,bnhrjd->bnhrid", inv, (v32 * bc[..., None]).astype(cd))
    w = mm("bnhrij,bnhrjk->bnhrik", inv,
           (k32 * (bc * from_start)[..., None]).astype(cd)).astype(cd)
    # what a position reads of its own chunk: (q k^T o decay), diagonal in
    scores = (mm("bnhik,bnhjk->bnhij", qc, kc)[:, :, :, None]
              * decay).astype(cd)
    to_end = jnp.exp(cum[..., -1:] - cum)
    chunk_decay = jnp.exp(cum[..., -1])

    def carry(s, inp):
        q_i, k_i, u_i, w_i, scores_i, from_i, to_i, decay_i = inp
        s_c = s.astype(cd)
        v_new = u_i - mm("bhrik,bhrkd->bhrid", w_i, s_c)
        o = (mm("bhik,bhrkd->bhrid", q_i, s_c) * from_i[..., None]
             + mm("bhrij,bhrjd->bhrid", scores_i, v_new.astype(cd)))
        s = s * decay_i[..., None, None] + mm(
            "bhjk,bhrjd->bhrkd", k_i, (v_new * to_i[..., None]).astype(cd))
        return s, o

    _, o = jax.lax.scan(
        carry, jnp.zeros((bsz, hk, r, dk, dv), f32),
        jax.tree.map(lambda x: x.swapaxes(0, 1),
                     (qc, kc, u, w, scores, from_start, to_end,
                      chunk_decay)))
    # [n, b, Hk, r, c, dv] -> [b, T, Hv, dv]
    return o.transpose(1, 0, 4, 2, 3, 5).reshape(bsz, t, hv, dv)


class ZeroCentredRMSNorm(nn.Module):
    """``x / rms(x) * (1 + scale)``, ``scale`` drawn at nought."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],))
        return rms_norm(x, 1.0 + scale, self.eps)


class GatedDeltaNet(_Base):
    """The Gated DeltaNet mixer over the key heads held, each with its
    ``value_heads / key_heads`` value heads."""

    key_heads: int = 8
    value_heads: int = 16
    key_dim: int = 128
    value_dim: int = 128
    conv_kernel: int = 4
    chunk_size: int = 64
    eps: float = 1e-6

    @nn.compact
    def __call__(self, h):
        dt_c = self.compute_dtype
        b, t, d = h.shape
        hk, hv, dk, dv = (self.key_heads, self.value_heads, self.key_dim,
                          self.value_dim)
        r = hv // hk
        # the published kernels, a key head's columns together: ``[q | k |
        # v | z]`` (its value heads' ``v`` and ``z`` side by side) and
        # ``[b | a]``; the step sizes leave theirs in float32
        proj = Linear(dt_c, hk * (2 * dk + 2 * r * dv), name="in_proj_qkvz")(
            h).reshape(b, t, hk, 2 * dk + 2 * r * dv)
        ba = Linear(dt_c, hk * 2 * r, name="in_proj_ba")(
            h, jnp.float32).reshape(b, t, hk, 2 * r)
        w = self.param("conv_kernel", _normal(),
                       (self.conv_kernel, 2 * hk * dk + hv * dv))
        a_log = self.param("A_log", _a_log_init, (hv,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (hv,))
        gain = self.param("norm_scale", nn.initializers.ones, (dv,))

        q, k, v, z = jnp.split(proj, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
        with jax.named_scope("conv"):
            # the convolution's channels: all of q, all of k, all of v
            qkv = jax.nn.silu(causal_conv(jnp.concatenate(
                [x.reshape(b, t, -1) for x in (q, k, v)], -1), w))
        q, k = (x.reshape(b, t, hk, dk)
                for x in (qkv[..., :hk * dk], qkv[..., hk * dk:2 * hk * dk]))
        v = qkv[..., 2 * hk * dk:].reshape(b, t, hv, dv)
        beta = jax.nn.sigmoid(ba[..., :r]).reshape(b, t, hv)
        g = -jnp.exp(a_log) * jax.nn.softplus(
            ba[..., r:].reshape(b, t, hv) + dt_bias)
        with jax.named_scope("delta"):
            q, k = (x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                      + 1e-6) for x in (q, k))
            o = delta_rule(q * dk ** -0.5, k, v, g, beta, self.chunk_size,
                           dt_c)
        # gated norm: RMSNorm a head, its gain, then the gate
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + self.eps)
        y = o * gain * jax.nn.silu(z.astype(jnp.float32).reshape(b, t, hv, dv))
        return Linear(dt_c, d, name="out_proj")(y.reshape(b, t, hv * dv),
                                                jnp.float32)


class GatedAttention(_Base):
    """Grouped-query causal attention over whole contexts for the query
    heads held, with their key/value heads: a query and an output gate a
    head from one kernel, RMSNorm a head on ``q`` and ``k``, RoPE on the
    first ``rotary_dim`` dims; no bias, no cache.  ``q`` is written ``[b,
    H, T, d]``, ``k`` and ``v`` ``[b, H_kv, T, d]``."""

    num_heads: int = 8
    num_kv_heads: int = 1
    head_dim: int = 256
    rotary_dim: int = 64
    rope_theta: float = 1e7
    eps: float = 1e-6

    @nn.compact
    def __call__(self, u):
        dt = self.compute_dtype
        d = u.shape[-1]
        nh, nkv, hd, rd = (self.num_heads, self.num_kv_heads, self.head_dim,
                           self.rotary_dim)
        mm = functools.partial(jnp.einsum,
                               preferred_element_type=jnp.float32)

        def heads(name, count, width=hd):
            w = Weight(dt, (d, count * width), name=name)()
            return mm("btd,dhk->bhtk", u.astype(dt),
                      w.reshape(d, count, width))

        def turned(x, name):
            x = ZeroCentredRMSNorm(self.eps, name=name)(x)
            return jnp.concatenate(
                [rope(x[..., :rd], self.rope_theta), x[..., rd:]],
                -1).astype(dt)

        q_gate = heads("q", nh, 2 * hd)
        q, gate = q_gate[..., :hd], q_gate[..., hd:]
        o = attention.causal_attention(
            turned(q, "q_norm"), turned(heads("k", nkv), "k_norm"),
            heads("v", nkv).astype(dt), hd ** -0.5)
        o = (o.astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(dt)
        w_o = Weight(dt, (nh * hd, d), name="o")()
        return mm("bhtk,hkf->btf", o, w_o.reshape(nh, hd, d))


class Mixer(_Base):
    """``x + mixer(norm(x))``, the mixer by the pattern's letter."""

    cfg: Any = None             # the preset's dict, frozen
    kind: str = "D"

    @nn.compact
    def __call__(self, x):
        c, dt = dict(self.cfg), self.compute_dtype
        held, eps = held_widths(c), c["rms_norm_eps"]
        u = ZeroCentredRMSNorm(eps, name="norm")(x)
        if self.kind == "D":
            with jax.named_scope("gdn"):
                return x + GatedDeltaNet(
                    dt, held["key_heads"], held["value_heads"],
                    c["linear_key_head_dim"], c["linear_value_head_dim"],
                    c["linear_conv_kernel_dim"], c["chunk_size"], eps,
                    name="gdn")(u)
        if self.kind == "A":
            with jax.named_scope("gated_attention"):
                return x + GatedAttention(
                    dt, held["attn_heads"], held["kv_heads"], c["head_dim"],
                    c["rotary_dim"], c["rope_theta"], eps,
                    name="attention")(u)
        raise ValueError(f"layer kind {self.kind!r} in the pattern")


class Experts(_Base):
    """``x + moe(norm(x))`` and the block's routing (``MoE``)."""

    cfg: Any = None
    expert_rank: int = 0

    @nn.compact
    def __call__(self, x):
        c = dict(self.cfg)
        y, routing = MoE(self.compute_dtype, c["moe_intermediate_size"],
                         c["num_experts"], c["n_held_experts"],
                         self.expert_rank, c["num_experts_per_tok"], 1.0,
                         "swiglu", c["shared_expert_intermediate_size"],
                         "softmax", True, name="moe")(
                             ZeroCentredRMSNorm(c["rms_norm_eps"],
                                                name="norm")(x))
        return x + y, routing


class Layer(_Base):
    """``x += mixer(norm(x))``; ``x += moe(norm(x))``.  Each of the two
    parts runs ``context_block`` contexts at a time (``nn.scan`` over the
    blocks of a larger batch, the parameters shared), each block
    rematerialised by itself (``remat``): an update keeps the residual
    stream before either part and ONE block's internals of ONE part, and
    the gradients of a part's kernels are summed block by block inside the
    loop.  Whole, the update's temporaries at the published widths are
    7.0 GiB of the 5.9 a chip has left beside the learner's state
    (PERF.md, PR 35): the delta rule's float32 blocks stand beside the
    expert block's sorted rows, and the rounds of ``MoE.routed`` each hand
    back a gradient of the stacked experts that is added only where the
    gradient's norm is taken."""

    cfg: Any = None             # the preset's dict, frozen
    kind: str = "D"
    expert_rank: int = 0
    remat: bool = True

    def blocked(self, part, x):
        """``part(x)`` over blocks of contexts: the leaves of its output
        that have ``x``'s shape are laid end to end, the others (the
        routing counters) summed."""
        def one(mdl, x):
            return mdl(x)

        one = nn.remat(one) if self.remat else one
        b, block = x.shape[0], dict(self.cfg).get("context_block", 0)
        if not block or b <= block or b % block:
            return one(part, x)
        _, out = nn.scan(
            lambda mdl, carry, xb: (carry, one(mdl, xb)),
            variable_broadcast="params", split_rngs={"params": False})(
                part, None, x.reshape(b // block, block, *x.shape[1:]))
        return jax.tree.map(
            lambda y: y.reshape(x.shape) if y.shape[2:] == x.shape[1:]
            else y.sum(0), out)

    @nn.compact
    def __call__(self, x):
        x = self.blocked(Mixer(self.compute_dtype, self.cfg, self.kind,
                               name="mixer"), x)
        return self.blocked(Experts(self.compute_dtype, self.cfg,
                                    self.expert_rank, name="experts"), x)


class Qwen3NextQ(nn.Module):
    """``Q(s, .) = RMSNorm(x_T) W_head`` over the ids held, float32."""

    num_actions: int
    preset: str = "qwen3_next_tiny"
    compute_dtype: Any = jnp.bfloat16
    expert_rank: int = 0
    n_held_experts: int | None = None   # None = the preset's share
    remat: bool = True

    #: ``__call__(obs, with_stats=True)`` also returns routing scalars
    #: (``models.learner_apply_fn``)
    COUNTS_STATS = True
    #: leaves the forward pass reads in float32 (``models.acting_params``):
    #: gains, the router, rows read without a product, and what the delta
    #: rule and the convolution compute in float32
    ACTING_KEEPS_FLOAT32 = ("scale", "router_kernel", "embedding", "A_log",
                            "dt_bias", "norm_scale", "conv_kernel")

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
        c, held = self.cfg, held_widths(self.cfg)
        return {
            "pattern": pattern(c),
            "key_heads": f"{held['key_heads']}/{c['linear_num_key_heads']}",
            "value_heads":
                f"{held['value_heads']}/{c['linear_num_value_heads']}",
            "attn_heads": f"{held['attn_heads']}/{c['num_attention_heads']}",
            "kv_heads": f"{held['kv_heads']}/{c['num_key_value_heads']}",
            "experts": f"{c['n_held_experts']}/{c['num_experts']}",
            "expert_rank": self.expert_rank,
            "chunk": c["chunk_size"], "inverse": INVERSE,
            "params": param_count(c)}

    @nn.compact
    def __call__(self, obs, with_stats: bool = False):
        c, dt = self.cfg, self.compute_dtype
        with jax.named_scope("embed"):
            emb = self.param("embedding", _normal(),
                             (c["vocab_held"], c["hidden_size"]))
            x = emb[token_ids(obs, c["vocab_held"])]
        frozen = tuple(sorted(c.items()))
        routing = []
        for i, kind in enumerate(pattern(c)):
            x, r = Layer(dt, frozen, kind, self.expert_rank, self.remat,
                         name=f"layers_{i}")(x)
            routing.append(r)
        with jax.named_scope("q_head"):
            last = ZeroCentredRMSNorm(c["rms_norm_eps"],
                                      name="final_norm")(x[:, -1])
            q = Linear(dt, c["vocab_held"], name="head")(last, jnp.float32)
        if not with_stats:
            return q
        pairs = (obs.shape[0] * (obs.shape[1] // 2) * c["num_hidden_layers"]
                 * c["num_experts_per_tok"])
        return q, routing_stats(routing, pairs)


def share_of_layer(kind: str, p: dict, c: dict, head_rank: int) -> dict:
    """A rank's share of an UNCUT layer's mixer parameters ``p``
    (``layers_<i>/mixer``; held = published in the preset ``c`` they were
    made with): its heads' columns
    of the in-projections, their channels of the convolution, their
    per-head constants and their rows of the out-projection; the norms
    (the per-head ones, which every head shares, too) whole."""
    held = held_widths(c)

    def cols(x, width, count, axis=-1):
        """``count`` blocks of ``width`` from block ``head_rank * count``."""
        lo = head_rank * count * width
        return jax.lax.slice_in_dim(x, lo, lo + count * width, axis=axis)

    if kind == "A":
        hd, a = c["head_dim"], p["attention"]
        cut = {"q": cols(a["q"]["kernel"], 2 * hd, held["attn_heads"]),
               "k": cols(a["k"]["kernel"], hd, held["kv_heads"]),
               "v": cols(a["v"]["kernel"], hd, held["kv_heads"]),
               "o": cols(a["o"]["kernel"], hd, held["attn_heads"], 0)}
        return {"norm": p["norm"], "attention": {
            "q_norm": a["q_norm"], "k_norm": a["k_norm"],
            **{k: {"kernel": v} for k, v in cut.items()}}}
    m = p["gdn"]
    hk_all, hv_all = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    hk, hv = held["key_heads"], held["value_heads"]
    r = hv_all // hk_all
    # the convolution's channels: ``[q | k | v]``, each all heads wide
    at, parts = 0, []
    for width, count, total in ((dk, hk, hk_all), (dk, hk, hk_all),
                                (dv, hv, hv_all)):
        block = jax.lax.slice_in_dim(m["conv_kernel"], at, at + total * width,
                                     axis=-1)
        parts.append(cols(block, width, count))
        at += total * width
    return {"norm": p["norm"], "gdn": {
        "in_proj_qkvz": {"kernel": cols(m["in_proj_qkvz"]["kernel"],
                                        2 * dk + 2 * r * dv, hk)},
        "in_proj_ba": {"kernel": cols(m["in_proj_ba"]["kernel"], 2 * r, hk)},
        "conv_kernel": jnp.concatenate(parts, -1),
        "A_log": cols(m["A_log"], 1, hv), "dt_bias": cols(m["dt_bias"], 1, hv),
        "norm_scale": m["norm_scale"],
        "out_proj": {"kernel": cols(m["out_proj"]["kernel"], dv, hv, 0)}}}


def param_count(c: dict) -> int:
    """Parameters of a preset ``c`` at its share, from its widths."""
    held, d = held_widths(c), c["hidden_size"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    hk, hv = held["key_heads"], held["value_heads"]
    gdn = (d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv
           + c["linear_conv_kernel_dim"] * (2 * hk * dk + hv * dv)
           + 2 * hv + dv + hv * dv * d)
    hd = c["head_dim"]
    attn = (d * held["attn_heads"] * 2 * hd + 2 * d * held["kv_heads"] * hd
            + held["attn_heads"] * hd * d + 2 * hd)
    f = c["moe_intermediate_size"]
    moe = (d * c["num_experts"] + 3 * d * c["shared_expert_intermediate_size"]
           + d + c["n_held_experts"] * 3 * d * f)
    kinds = pattern(c)
    return (kinds.count("D") * gdn + kinds.count("A") * attn
            + len(kinds) * (moe + 2 * d) + 2 * c["vocab_held"] * d + d)
