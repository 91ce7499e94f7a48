"""GLM-4.7-Flash (``glm4_moe_lite``) as a Q-network over token contexts.

The published block (https://huggingface.co/zai-org/GLM-4.7-Flash, config
``model_type: glm4_moe_lite``): pre-norm residual layers of multi-head
latent attention (MLA: low-rank query and key/value projections, a rope
part shared by all heads) followed by a SwiGLU feed-forward in the leading
dense layer and, in every later layer, one shared expert plus 4 of 64
routed experts picked by a sigmoid router with a selection-only bias
(``noaux_tc``).  Here it is the torso of Ape-X DQN's Q-network: a frame is
a whole context of ``T`` token ids, two bytes each, and ``Q(s, .)`` is the
output head at the last position, one value per id of the vocabulary held.

**One chip's share of an expert-parallel deployment.**  The layer is told
which routed experts it holds (``held = [rank * n_held, (rank + 1) *
n_held)``), routes over all ``n_routed_experts`` and adds nothing for the
absent ones: no stand-in for other chips, no exchange, no dropped pair and
no capacity factor.  The (token, expert) pairs that land on held experts are
sorted by expert and go through a grouped matrix product
(:func:`jax.lax.ragged_dot`) in rounds (:func:`expert_rounds`: the first
sized by the held experts' share of the pairs); a round past the first
runs only when routing filled the rounds before it (``lax.cond``), so
uneven routing costs time, never a pair.  Inside a
round the product goes the way :func:`apex_tpu.ops.grouped.plan` says: in
a program compiled for a TPU, at widths of 512 or more that 512 does not
divide, a kernel with tiles of its own is handed the operands with zeros
up to a multiple of each tile (exact zeros, cut off the layer's output and
off the weight gradients before they leave the round); everywhere else
``ragged_dot`` is handed the widths as they are.  The vocabulary is a slice
too: ids are taken ``mod vocab_held``.

The repo's idiom: float32 parameters, ``compute_dtype`` operands on the
MXU with float32 accumulation; norms, the router, softmax and the Q output
in float32.  Each layer is rematerialised (``nn.remat``): an update keeps
the residual stream between layers and one layer's internals, and that is
the only rematerialisation attention sees.  Its softmax runs in
:func:`apex_tpu.ops.attention.causal_attention`, once for the whole batch:
at the published widths in a program compiled for a TPU that is a fused
kernel whose scores never leave the chip's VMEM and whose backward pass
makes them again from ``q``, ``k`` and the saved row statistics; at the
toy's widths, and on a CPU, the plain path through float32 scores.

Not held: the multi-token-prediction module (it serves an auxiliary loss
this learner does not have) and a key/value cache (training and acting
both run whole contexts).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from apex_tpu.ops import attention, grouped

#: ``--torso`` presets: the published widths at one of 8 chips' share of
#: each layer, and the toy the CPU tests run.  ``context`` is the number of
#: ids a frame carries (``ApexTokens-v0`` emits ``u8[2 * context]``);
#: ``vocab_held`` is the vocabulary the CLI sizes the env to unless
#: ``--token-vocab`` says otherwise (the model holds ``num_actions`` ids).
PRESETS: dict[str, dict[str, Any]] = {
    "glm47_flash_ep8": dict(
        hidden_size=2048, num_heads=20, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        intermediate_size=10240, moe_intermediate_size=1536,
        n_routed_experts=64, n_held_experts=8, num_experts_per_tok=4,
        routed_scaling_factor=1.8, n_dense_layers=1, n_expert_layers=4,
        vocab_held=19360, rope_theta=1e6, rms_norm_eps=1e-5, context=1024,
        ffn_block=4096),
    "glm47_flash_tiny": dict(
        hidden_size=64, num_heads=2, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
        intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=8, n_held_experts=2, num_experts_per_tok=2,
        routed_scaling_factor=1.8, n_dense_layers=1, n_expert_layers=2,
        vocab_held=64, rope_theta=1e6, rms_norm_eps=1e-5, context=16),
}


def token_ids(obs_u8: jax.Array, vocab: int) -> jax.Array:
    """``u8[B, 2T]`` -> ``i32[B, T]``: two bytes an id, low byte first,
    ``mod vocab`` (the env emits ids below it; any other bytes fold in)."""
    b = obs_u8.reshape(obs_u8.shape[0], -1, 2).astype(jnp.int32)
    return (b[..., 0] + 256 * b[..., 1]) % vocab


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over the last axis of ``[..., T, d]``, positions
    ``0..T-1``, pairing dimension ``i`` with ``i + d/2`` (rotate-half)."""
    d, t = x.shape[-1], x.shape[-2]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (1,) * (x.ndim - 2) + (t, d // 2)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x = x.astype(jnp.float32)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _normal(std: float = 0.02):
    return nn.initializers.normal(std)


class _Base(nn.Module):
    """Shared helpers: a float32 kernel multiplied in the compute dtype."""

    compute_dtype: Any = jnp.bfloat16

    def kernel(self, name: str, shape: tuple[int, ...]) -> jax.Array:
        return self.param(name, _normal(), shape)

    def dot(self, x: jax.Array, w: jax.Array, out=None) -> jax.Array:
        dt = self.compute_dtype
        y = jnp.dot(x.astype(dt), w.astype(dt),
                    preferred_element_type=jnp.float32)
        return y.astype(out or dt)


class Linear(_Base):
    features: int = 0

    @nn.compact
    def __call__(self, x, out=None):
        return self.dot(x, self.kernel("kernel", (x.shape[-1],
                                                  self.features)), out)


class Weight(_Base):
    """A :class:`Linear`'s kernel alone, in the compute dtype, for a
    caller that multiplies it head by head (same leaf, same path)."""

    shape: tuple[int, ...] = ()

    @nn.compact
    def __call__(self):
        return self.kernel("kernel", self.shape).astype(self.compute_dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return rms_norm(x, scale, self.eps)


class FeedForward(_Base):
    """``(silu(h W_g) * (h W_u)) W_d`` (``act="swiglu"``: three matrices)
    or ``relu(h W_u)^2 W_d`` (``"relu2"``: two) -> float32,
    ``token_block`` tokens at a time where that is set and the input is
    larger: the wide products of a block are made again in the backward
    pass, so a layer of width 10,240 keeps 4 k tokens' worth of them, not
    16 k (2 GB less at the peak of the update, where every gradient is
    already live)."""

    width: int = 0
    token_block: int = 0
    act: str = "swiglu"

    @nn.compact
    def __call__(self, h):
        d, f = h.shape[-1], self.width
        if self.act == "swiglu":
            w_gate = self.kernel("gate", (d, f))
        w_up = self.kernel("up", (d, f))
        w_down = self.kernel("down", (f, d))

        def ffn(x):
            if self.act == "swiglu":
                g = self.dot(x, w_gate, jnp.float32)
                u = self.dot(x, w_up, jnp.float32)
                mid = jax.nn.silu(g) * u
            else:
                mid = jnp.square(jax.nn.relu(self.dot(x, w_up, jnp.float32)))
            return self.dot(mid, w_down, jnp.float32)

        x = h.reshape(-1, d)
        blk = self.token_block
        if blk and x.shape[0] > blk and x.shape[0] % blk == 0:
            y = jax.lax.map(jax.checkpoint(ffn), x.reshape(-1, blk, d))
        else:
            y = ffn(x)
        return y.reshape(h.shape)


#: this torso's feed-forward: the default ``act``
SwiGLU = FeedForward


class MLA(_Base):
    """Multi-head latent attention over whole contexts, causal, no cache.
    The up-projections write ``q``, ``k`` and ``v`` as ``[b, H, T, d]`` in
    the compute dtype (the rope part of ``k`` repeated for every head),
    the layout attention works in, and ``o`` reads it: no copy of a 168 MB
    activation stands between a product and the kernel (PERF.md, PR 30).
    :func:`~apex_tpu.ops.attention.causal_attention` takes the whole batch
    in one call: a fused kernel where platform and widths allow it
    (``qk_nope + qk_rope == v_head_dim``, multiples of 128), else the plain
    path.  No blocking over contexts and no checkpoint of its own."""

    num_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    eps: float = 1e-5

    @nn.compact
    def __call__(self, h):
        dt = self.compute_dtype
        b, t, d = h.shape
        nh, nope, rp, vd = (self.num_heads, self.qk_nope_head_dim,
                            self.qk_rope_head_dim, self.v_head_dim)
        mm = functools.partial(jnp.einsum,
                               preferred_element_type=jnp.float32)

        def heads(x, w):
            # an up-projection writes its heads apart, ``[b, H, t, d]``
            return mm("btr,rhd->bhtd", x.astype(dt), w).astype(dt)

        c_q = RMSNorm(self.eps, name="q_a_norm")(
            Linear(dt, self.q_lora_rank, name="q_a")(h, jnp.float32))
        kv = Linear(dt, self.kv_lora_rank + rp, name="kv_a")(h, jnp.float32)
        c_kv, k_r = kv[..., :self.kv_lora_rank], kv[..., self.kv_lora_rank:]
        c_kv = RMSNorm(self.eps, name="kv_a_norm")(c_kv)
        w_q = Weight(dt, (self.q_lora_rank, nh * (nope + rp)), name="q_b")()
        w_kv = Weight(dt, (self.kv_lora_rank, nh * (nope + vd)),
                      name="kv_b")().reshape(-1, nh, nope + vd)
        q = heads(c_q, w_q.reshape(-1, nh, nope + rp))
        # ``k``'s content part and ``v`` are two products of one kernel's
        # columns: a 448-wide head would be sliced, and copied, afterwards
        k_nope, v = heads(c_kv, w_kv[..., :nope]), heads(c_kv, w_kv[..., nope:])
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], self.rope_theta).astype(dt)],
            -1)
        k_r = rope(k_r, self.rope_theta).astype(dt)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r[:, None], (b, nh, t, rp))], -1)
        o = attention.causal_attention(q, k, v, (nope + rp) ** -0.5)
        w_o = Weight(dt, (nh * vd, d), name="o")()
        return mm("bhtd,hdf->btf", o, w_o.reshape(nh, vd, d))


def expert_rounds(pairs: int, held: int, routed: int,
                  quarters: int = 1) -> tuple[tuple[int, ...], ...]:
    """The rows of each round of :meth:`MoE.routed` over a call's
    ``pairs`` (token, expert) pairs, which lie sorted with the held
    experts' first, grouped by the ``lax.cond`` that runs them; together
    the rounds cover every pair.  Round 0 runs always: twice the held
    experts' even share of the pairs (``held / routed``), up to a multiple
    of a grouped product's best row tile (:data:`apex_tpu.ops.grouped.BEST`,
    so the tiled kernel keeps it), ``quarters`` quarters of the pairs at
    most (two where the held experts' even share is itself a quarter, as
    in LFM2: capped at one quarter, whether round 1 runs would turn on a
    few pairs either side of the mean, and with it an update's work).  The
    later rounds hold a quarter each, the last what is left; each group
    runs only when routing filled the rounds before it.  Every group hands
    back a gradient of each stacked expert kernel, so there are at most
    three later groups, as many as three later rounds of a quarter made,
    and the rounds past the fourth run in the last group, one after the
    other (PERF.md, PR 36: by the AOT compile for the chip, one more cond
    took 0.2-0.36 GB more of the update's temporaries, rounds larger than
    a quarter 0.02-0.14)."""
    quarter, tile = max(pairs // 4, 1), grouped.BEST
    rounds = [min(-(-2 * pairs * held // (routed * tile)) * tile,
                  max(pairs * quarters // 4, 1))]
    while sum(rounds) < pairs:
        rounds.append(min(quarter, pairs - sum(rounds)))
    groups = [(rows,) for rows in rounds[:3]]
    if rounds[3:]:
        groups.append(tuple(rounds[3:]))
    return tuple(groups)


def routing_stats(routing, pairs: int) -> dict:
    """A pass's routing counters over its expert layers' ``(counts,
    overflow)``: the (token, expert) pairs that landed on held experts,
    their share of all ``pairs`` (an even router gives held / routed), the
    busiest held expert's load over the mean, and the rounds past the
    first that ran (:func:`expert_rounds`)."""
    load = jnp.sum(jnp.stack([c for c, _ in routing]), 0).astype(jnp.float32)
    return {"moe_local_pairs": load.sum(),
            "moe_local_share": load.sum() / pairs,
            "moe_load_max_over_mean":
                load.max() / jnp.maximum(load.mean(), 1.0),
            "moe_overflow_rounds":
                jnp.sum(jnp.stack([o for _, o in routing])).astype(
                    jnp.float32)}


def no_routing(held: int):
    """What a layer without experts says of its routing."""
    return jnp.zeros(held, jnp.int32), jnp.zeros((), jnp.int32)


class MoE(_Base):
    """One shared expert (or none) and this chip's share of the routed
    experts, for every token torso: what differs between models is a field
    (``n_shared_experts``: 1, or 0 for a model without one; ``act``:
    gated three-matrix ``silu`` experts or two-matrix ``relu^2`` ones; the
    shared expert's width; the counts; the scaling; ``scoring``, the rule
    that turns the router's outputs into picks and weights, see
    :meth:`route`; ``shared_gate``, whether the shared expert's output is
    multiplied by ``sigmoid(h w_g)``, a learned scalar a token).  Returns
    the layer's output and its routing: the pairs that landed on each held
    expert and the rounds of :meth:`routed` past the first that ran."""

    width: int = 1536
    n_routed_experts: int = 64
    n_held_experts: int = 8
    expert_rank: int = 0
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    act: str = "swiglu"         # or "relu2" (:class:`FeedForward`)
    shared_width: int = 0       # 0 = the routed experts' width
    scoring: str = "sigmoid_bias"       # or "softmax" (:meth:`route`)
    shared_gate: bool = False   # the shared expert times sigmoid(h w_g)
    n_shared_experts: int = 1   # or 0: no shared expert, no such leaves
    round0_quarters: int = 1    # round 0's most rows (:func:`expert_rounds`)

    def grouped(self, xs, w, group_sizes, live, tiles=None):
        """``xs[i] @ w[g(i)]`` -> float32 for the rows the groups cover,
        by ``ragged_dot`` or, with ``tiles``, by the tiled kernel at the
        widths handed (:func:`apex_tpu.ops.grouped.product`).  Either
        leaves the rows past the last group unwritten, in its output and
        in the cotangent it hands back: both are masked here, so no stray
        bits reach a live row."""
        dt = self.compute_dtype
        xs = jnp.where(live, xs, 0).astype(dt)
        y = grouped.product(xs, w.astype(dt), group_sizes, tiles)
        return jnp.where(live, y, 0.0)

    def experts(self, xs, sizes, live, *kernels, tiles=None):
        """The held experts' products over a round's sorted rows ``xs`` ->
        ``f32[rows, D]``; ``tiles = (hidden's, width's)`` as
        :meth:`grouped` takes them.  A padded ``mid`` goes to the down
        product as it stands (``relu(0)^2`` and ``silu(0) * 0`` are zero);
        only the output is cut back."""
        up, down = (tiles, tiles[::-1]) if tiles else (None, None)
        if self.act == "swiglu":
            g = self.grouped(xs, kernels[0], sizes, live, up)
            u = self.grouped(xs, kernels[1], sizes, live, up)
            mid = jax.nn.silu(g) * u
        else:
            mid = jnp.square(jax.nn.relu(
                self.grouped(xs, kernels[0], sizes, live, up)))
        y = self.grouped(mid, kernels[-1], sizes, live, down)
        return y[:, :xs.shape[1]]

    def route(self, h32):
        """``(picks i32[N, k], weights f32[N, k])`` over ALL routed
        experts, by the layer's scoring rule, in float32.
        ``"sigmoid_bias"``: sigmoid scores, the ``k`` largest of score +
        bias, a learned vector that only selects.  ``"softmax"``: a softmax
        over all the router's outputs, its ``k`` largest, and no bias (the
        layer has no such leaf).  Either way the weights are ``scaling *
        s_i / sum_picked s_j``."""
        e = self.n_routed_experts
        w_r = self.param("router_kernel", _normal(), (h32.shape[-1], e))
        logits = jnp.dot(h32, w_r, precision=jax.lax.Precision.HIGHEST)
        if self.scoring == "softmax":
            s = ranked = jax.nn.softmax(logits, axis=-1)
        elif self.scoring == "sigmoid_bias":
            bias = self.param("router_bias", nn.initializers.zeros, (e,))
            s = jax.nn.sigmoid(logits)
            ranked = s + jax.lax.stop_gradient(bias)
        else:
            raise ValueError(f"scoring rule {self.scoring!r}")
        _, picks = jax.lax.top_k(ranked, self.num_experts_per_tok)
        picked = jnp.take_along_axis(s, picks, axis=-1)
        weights = (self.routed_scaling_factor * picked
                   / picked.sum(-1, keepdims=True))
        return picks.astype(jnp.int32), weights

    def rounds(self, pairs: int) -> list[tuple[int, tuple[int, ...]]]:
        """``(first pair, rows of each round)`` of each ``lax.cond`` of
        :meth:`routed` over a call's ``pairs`` (:func:`expert_rounds`)."""
        groups = expert_rounds(pairs, self.n_held_experts,
                               self.n_routed_experts, self.round0_quarters)
        return [(sum(map(sum, groups[:g])), group)
                for g, group in enumerate(groups)]

    def routed(self, h, picks, weights, *kernels):
        """The held experts' part of the layer output, ``f32[N, D]``, and
        the pairs that landed on each of them, ``i32[n_held]``.
        ``kernels``: the stacked experts' (gate, up, down) or (up, down)."""
        n, k = picks.shape
        held, lo = self.n_held_experts, self.expert_rank * self.n_held_experts
        local = (picks >= lo) & (picks < lo + held)
        key = jnp.where(local, picks - lo, held).reshape(-1)
        order = jnp.argsort(key, stable=True)       # held pairs first, by expert
        counts = (key[:, None] == jnp.arange(held)[None, :]).sum(
            0, dtype=jnp.int32)
        ends = jnp.cumsum(counts)
        starts, n_local = ends - counts, ends[-1]
        flat_w = weights.reshape(-1)

        def one_round(a, rows):
            # which kernel, by the platform the program is lowered for; where
            # no platform changes it, one path and no choice in the program
            on_tpu = grouped.plan(h.shape[-1], self.width, rows, "tpu")

            @jax.checkpoint
            def run(out, h, *kernels):
                idx = jax.lax.dynamic_slice_in_dim(order, a, rows)
                tok = idx // k
                sizes = jnp.clip(jnp.minimum(ends, a + rows)
                                 - jnp.maximum(starts, a), 0, rows)
                live = ((a + jnp.arange(rows)) < n_local)[:, None]
                xs = h[tok]
                with jax.named_scope("experts"):
                    y = self.experts(xs, sizes, live, *kernels) \
                        if on_tpu is None else jax.lax.platform_dependent(
                            xs, sizes, live, *kernels, default=self.experts,
                            tpu=functools.partial(self.experts,
                                                  tiles=on_tpu))
                y = y * flat_w[idx][:, None]
                return out.at[tok].add(y)
            return run

        def in_turn(a, group):
            # one cond's rounds, one after the other from pair ``a``
            def run(out, h, *kernels):
                for i, rows in enumerate(group):
                    out = one_round(a + sum(group[:i]), rows)(out, h, *kernels)
                return out
            return run

        out = jnp.zeros((n, h.shape[-1]), jnp.float32)
        for g, (a, group) in enumerate(self.rounds(n * k)):
            args = (out, h, *kernels)
            out = in_turn(a, group)(*args) if g == 0 else jax.lax.cond(
                n_local > a, in_turn(a, group), lambda out, *_: out, *args)
        return out, counts

    @nn.compact
    def __call__(self, h32):
        b, t, d = h32.shape
        dt = self.compute_dtype
        if self.n_shared_experts not in (0, 1):
            raise ValueError(f"{self.n_shared_experts} shared experts")
        y = None
        if self.n_shared_experts:
            with jax.named_scope("shared_expert"):
                y = FeedForward(dt, self.shared_width or self.width, 0,
                                self.act, name="shared")(h32)
                if self.shared_gate:
                    y = y * jax.nn.sigmoid(
                        Linear(dt, 1, name="shared_gate")(h32, jnp.float32))
        # router: scores, picks, and the sort / gather / scatter that take
        # pairs to their experts and back (``experts``: the products alone)
        with jax.named_scope("router"):
            picks, weights = self.route(h32.reshape(b * t, d))
        e, f = self.n_held_experts, self.width
        names = (("gate", "up", "down") if self.act == "swiglu"
                 else ("up", "down"))
        kernels = [self.param(f"experts_{name}", _normal(),
                              (e, f, d) if name == "down" else (e, d, f))
                   for name in names]
        with jax.named_scope("router"):
            part, counts = self.routed(
                h32.reshape(b * t, d).astype(dt), picks, weights, *kernels)
        # the rounds past the first that ran: those whose first pair
        # routing reached, as :meth:`routed` decides it
        n_local = counts.sum()
        overflow = jnp.zeros((), jnp.int32)
        for a, group in self.rounds(picks.size)[1:]:
            overflow += len(group) * (n_local > a).astype(jnp.int32)
        part = part.reshape(b, t, d)
        return part if y is None else y + part, (counts, overflow)


class Block(_Base):
    """``x += MLA(norm(x))``; ``x += FFN(norm(x))``, dense or experts."""

    cfg: Any = None             # the preset's dict, frozen
    dense: bool = True
    expert_rank: int = 0

    @nn.compact
    def __call__(self, x):
        c, dt = dict(self.cfg), self.compute_dtype
        eps = c["rms_norm_eps"]
        with jax.named_scope("mla"):
            x = x + MLA(dt, c["num_heads"], c["q_lora_rank"],
                        c["kv_lora_rank"], c["qk_nope_head_dim"],
                        c["qk_rope_head_dim"], c["v_head_dim"],
                        c["rope_theta"], eps,
                        name="mla")(RMSNorm(eps, name="attn_norm")(x))
        h = RMSNorm(eps, name="ffn_norm")(x)
        if self.dense:
            with jax.named_scope("dense_ffn"):
                y = SwiGLU(dt, c["intermediate_size"],
                           c.get("ffn_block", 0), name="mlp")(h)
            routing = no_routing(c["n_held_experts"])
        else:
            y, routing = MoE(dt, c["moe_intermediate_size"],
                             c["n_routed_experts"], c["n_held_experts"],
                             self.expert_rank, c["num_experts_per_tok"],
                             c["routed_scaling_factor"], name="moe")(h)
        return x + y, routing


class Glm4MoeLiteQ(nn.Module):
    """``Q(s, .) = RMSNorm(x_T) W_head`` over the ids held, float32."""

    num_actions: int
    preset: str = "glm47_flash_tiny"
    compute_dtype: Any = jnp.bfloat16
    expert_rank: int = 0
    n_held_experts: int | None = None   # None = the preset's share
    remat: bool = True

    #: ``__call__(obs, with_stats=True)`` also returns routing scalars
    #: (``models.learner_apply_fn``)
    COUNTS_STATS = True
    #: leaves the forward pass reads in float32 (``models.acting_params``)
    ACTING_KEEPS_FLOAT32 = ("scale", "router_kernel", "router_bias",
                            "embedding")

    @property
    def cfg(self) -> dict[str, Any]:
        c = dict(PRESETS[self.preset], vocab_held=self.num_actions)
        if self.n_held_experts is not None:
            c["n_held_experts"] = self.n_held_experts
        return c

    def attention_path(self, platform: str) -> dict:
        """Which implementation attention takes at this preset's widths in
        a program compiled for ``platform``
        (:func:`apex_tpu.ops.attention.attention_path`)."""
        c = self.cfg
        return attention.attention_path(
            c["context"], c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
            c["v_head_dim"], platform)

    def grouped_path(self, platform: str) -> dict:
        """What the expert layers hand the grouped kernel at this preset's
        widths in a program compiled for ``platform``
        (:func:`apex_tpu.ops.grouped.grouped_path`)."""
        c = self.cfg
        return grouped.grouped_path(c["hidden_size"],
                                    c["moe_intermediate_size"], platform)

    @nn.compact
    def __call__(self, obs, with_stats: bool = False):
        c, dt = self.cfg, self.compute_dtype
        with jax.named_scope("embed"):
            emb = self.param("embedding", _normal(),
                             (c["vocab_held"], c["hidden_size"]))
            x = emb[token_ids(obs, c["vocab_held"])]
        block = nn.remat(Block) if self.remat else Block
        frozen = tuple(sorted(c.items()))
        routing = []
        for i in range(c["n_dense_layers"] + c["n_expert_layers"]):
            x, r = block(dt, frozen, i < c["n_dense_layers"],
                         self.expert_rank, name=f"layers_{i}")(x)
            routing.append(r)
        with jax.named_scope("q_head"):
            last = RMSNorm(c["rms_norm_eps"], name="final_norm")(x[:, -1])
            q = Linear(dt, c["vocab_held"], name="head")(last, jnp.float32)
        if not with_stats:
            return q
        pairs = (obs.shape[0] * (obs.shape[1] // 2) * c["n_expert_layers"]
                 * c["num_experts_per_tok"])
        return q, routing_stats(routing, pairs)


def param_count(preset: str) -> int:
    """Parameters of a preset, from its widths (norm gains and the
    router's bias counted)."""
    c = PRESETS[preset]
    d, nh = c["hidden_size"], c["num_heads"]
    mla = (d * c["q_lora_rank"] + c["q_lora_rank"]
           + c["q_lora_rank"] * nh * (c["qk_nope_head_dim"]
                                      + c["qk_rope_head_dim"])
           + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
           + c["kv_lora_rank"]
           + c["kv_lora_rank"] * nh * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
           + nh * c["v_head_dim"] * d + 2 * d)
    dense = mla + 3 * d * c["intermediate_size"]
    f = c["moe_intermediate_size"]
    moe = (mla + d * c["n_routed_experts"] + c["n_routed_experts"]
           + 3 * d * f * (1 + c["n_held_experts"]))
    return (c["n_dense_layers"] * dense + c["n_expert_layers"] * moe
            + 2 * c["vocab_held"] * d + d)

