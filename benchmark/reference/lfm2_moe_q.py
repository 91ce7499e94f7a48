"""Plain reference of the Ape-X DQN learner step over the layer stack of
LFM2-8B-A1B (``model_type: lfm2_moe``;
https://huggingface.co/LiquidAI/LFM2-8B-A1B ``config.json``; the layers as
the dense LFM2's modelling code in transformers has them: ``Lfm2ShortConv``,
``Lfm2Attention``, ``Lfm2DecoderLayer``, ``embedding_norm`` after the
stack).

The forward pass: ids from two bytes each (``mod`` the vocabulary held),
embedding, then a layer ``x <- x + mixer(operator_norm(x))``; ``x <- x +
ffn(ffn_norm(x))``, the mixer by the layer's own parameters: a
double-gated short convolution (``short_conv``) or QK-norm attention
(``self_attn``), the feed-forward dense SwiGLU or the experts (a
``router_kernel`` says which); then ``embedding_norm`` at the last
position and the TIED head, ``Q(s, .) = x E^T`` over the ids held.  Every
norm is ``w * x / sqrt(mean(x^2) + eps)``.  Then the learner step of
``reference/qwen3_next_q.py`` (n-step double-DQN Huber loss with
importance weights, global-norm clip, centred RMSprop, mixed-max
priorities).  Float32 at ``HIGHEST``; ``mode`` lowers the operands of
every matrix product and of the convolution's input (see ``common``).
Imports nothing of the program; the parameter tree uses the program's
names so leaves can be set side by side.

Departures from the published description, each for the reason given:

* **The convolution is the three-tap sum itself**, ``z[t] = sum_i w[i]
  u[t + i - (K - 1)]`` over zero history: what PyTorch's depthwise
  ``Conv1d`` with ``K - 1`` of padding computes, cut to the context.
* **The chip's share.**  The file is given the experts held (the first
  ``n_held`` of the router's outputs: rank 0) and a slice of the
  vocabulary, and reads the counts off the shapes.  What the absent
  experts would have added is left out, and that partial sum goes on to the
  next layer.  Mixers, router and dense feed-forward are whole.
* **Experts as a plain loop** over the held experts, each run on every
  token and weighted by the token's routing weight for it.  **Routing**
  (the ``lfm2_moe`` module is not public in the installed transformers,
  so from the config's keys): sigmoid scores over all 32 outputs in
  float32, the 4 largest of score + bias (``use_expert_bias``: the bias
  only selects), weights the picks' scores over their sum
  (``norm_topk_prob``) times ``routed_scaling_factor`` 1; no shared expert.
* **No dueling head** (the published model has none), no cache.
* **The batch one context at a time**, each layer rematerialised, as the
  other token references do and for their reason.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import common as c
from .glm4_moe_lite_q import (init_opt, mm, rms_norm, rope,  # noqa: F401
                              step_keys, swiglu)

#: the constants the weights' shapes do not show, by hidden size: the
#: published model's (``configs/lfm2_8b_a1b_q_ep4.json``) and those of the
#: toy the CPU rehearsal and the tests run
MODELS = {
    2048: dict(head_dim=64, num_experts_per_tok=4),
    64: dict(head_dim=16, num_experts_per_tok=4),
}
SHARED = dict(rope_theta=1e6, norm_eps=1e-5, routed_scaling_factor=1.0,
              expert_rank=0)


def model_of(params) -> tuple:
    """The model's constants as a hashable tuple (a static argument)."""
    hidden = params["params"]["embedding"].shape[1]
    return tuple(sorted({**SHARED, **MODELS[hidden]}.items()))


def init_rule(path, shape):
    """Gains at one (the norms are ``w * x / rms(x)``); stacked experts
    ``[E, in, out]`` by their own fan-in; the router's bias small but not
    nought, so that selection depends on it.  **The embedding is a unit
    table times ``sqrt(hidden)``**, the other token families' rule and for
    their reason (a router then reads the token's own vector before all
    else); tied to the head, it makes ``Q`` of the order of ``hidden``.
    Every other leaf by the general rule (the convolution's taps ``N(0, 2 /
    K)``)."""
    name = path[-1]
    if name == "embedding":
        return ("normal", math.sqrt(shape[1]))
    if name == "scale":
        return ("const", 1.0)
    if name.startswith("experts_"):
        return ("normal", math.sqrt(2.0 / shape[1]))
    if name == "router_bias":
        return ("normal", 0.01)
    return None


# -- the forward pass ---------------------------------------------------------

def norm(x, p, m):
    return rms_norm(x, p["scale"], m["norm_eps"])


def short_conv(u, p, m, mode):
    """One context ``u [T, D]``: ``[B | C | x] = u W_in``; ``z = conv(B *
    x)``; ``(C * z) W_out``."""
    del m
    t = u.shape[0]
    b_gate, c_gate, xs = jnp.split(mm(u, p["in_proj"]["kernel"], mode), 3,
                                   axis=-1)
    w = p["conv_kernel"]
    k = w.shape[0]
    padded = jnp.pad(c.rnd(b_gate * xs, mode), ((k - 1, 0), (0, 0)))
    z = sum(padded[i:i + t] * w[i] for i in range(k))
    return mm(c_gate * z, p["out_proj"]["kernel"], mode)


def attention(u, p, m, mode):
    """One context ``u [T, D]``: query head ``j`` reads key/value head ``j
    // (heads / kv heads)``; RMSNorm a head on ``q`` and ``k``, then RoPE
    over the whole head."""
    t, hd = u.shape[0], m["head_dim"]
    q = mm(u, p["q_proj"]["kernel"], mode).reshape(t, -1, hd)
    k = mm(u, p["k_proj"]["kernel"], mode).reshape(t, -1, hd)
    v = mm(u, p["v_proj"]["kernel"], mode).reshape(t, -1, hd)
    q = rope(norm(q, p["q_layernorm"], m), m["rope_theta"])
    k = rope(norm(k, p["k_layernorm"], m), m["rope_theta"])
    per = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, per, axis=1), jnp.repeat(v, per, axis=1)
    s = jnp.einsum("qhd,khd->hqk", c.rnd(q, mode), c.rnd(k, mode),
                   precision=c.HIGHEST) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", c.rnd(jax.nn.softmax(s, -1), mode),
                   c.rnd(v, mode), precision=c.HIGHEST)
    return mm(o.reshape(t, -1), p["out_proj"]["kernel"], mode)


def moe(h, p, m, mode):
    """The held experts' part for one context; no shared expert."""
    s = jax.nn.sigmoid(jnp.matmul(h, p["router_kernel"],
                                  precision=c.HIGHEST))
    _, picks = jax.lax.top_k(s + p["router_bias"], m["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, picks, axis=-1)
    w = m["routed_scaling_factor"] * picked / picked.sum(-1, keepdims=True)
    n_held = p["experts_up"].shape[0]
    lo = m["expert_rank"] * n_held
    y = jnp.zeros_like(h)
    for e in range(n_held):                  # absent experts add nothing
        w_e = jnp.sum(jnp.where(picks == lo + e, w, 0.0), -1)
        y = y + w_e[:, None] * swiglu(
            h, dict(gate=p["experts_gate"][e], up=p["experts_up"][e],
                    down=p["experts_down"][e]), mode)
    return y


def ffn(h, p, m, mode):
    return moe(h, p, m, mode) if "router_kernel" in p else swiglu(h, p, mode)


MIXERS = {"short_conv": short_conv, "self_attn": attention}


def layer(x, p, m, mode):
    kind = next(k for k in MIXERS if k in p)
    x = x + MIXERS[kind](norm(x, p["operator_norm"], m), p[kind], m, mode)
    return x + ffn(norm(x, p["ffn_norm"], m), p["feed_forward"], m, mode)


def forward_one(params, obs_u8, m, mode, remat=True):
    """``Q(s, .)`` of one context ``u8[2T]`` -> ``f32[V]``."""
    p = params["params"]
    emb = p["embedding"]
    b = obs_u8.reshape(-1, 2).astype(jnp.int32)
    x = emb[(b[:, 0] + 256 * b[:, 1]) % emb.shape[0]]
    n_layers = sum(1 for name in p if name.startswith("layers_"))
    # a lowered mode rounds a layer's weights when the layer runs, not all
    # at once (the GLM reference's ``held``, for its reason)
    held = (lambda x, w: (x, w)) if mode == "f32" else (
        lambda x, w: jax.lax.optimization_barrier((x, w)))
    for i in range(n_layers):
        f = functools.partial(layer, m=m, mode=mode)
        x, p_i = held(x, p[f"layers_{i}"])
        x = (jax.checkpoint(f) if remat else f)(x, p_i)
    last = norm(x[-1], p["embedding_norm"], m)
    last, head = held(last, emb)
    return mm(last, head.T, mode)


def forward(params, obs_u8, mode: str = "f32"):
    """``Q`` rows of a batch ``u8[B, 2T]``, one context after another."""
    m = dict(model_of(params))
    return jax.lax.map(lambda o: forward_one(params, o, m, mode), obs_u8)


# -- the learner step ---------------------------------------------------------

def context_loss(params, target_params, row, n_total, m, mode):
    """One transition's share of the batch's loss, its TD error and the Q
    of the action taken."""
    q = forward_one(params, row["obs"], m, mode)
    # the next-state passes carry no gradient
    next_q = forward_one(jax.lax.stop_gradient(params), row["next_obs"], m,
                         mode)
    tgt_next_q = forward_one(target_params, row["next_obs"], m, mode)
    q_taken = q[row["action"].astype(jnp.int32)]
    target = row["reward"] + row["discount"] * tgt_next_q[next_q.argmax()]
    td = jax.lax.stop_gradient(target) - q_taken
    return c.huber(td) * row["weight"] / n_total, (jnp.abs(td), q_taken)


@functools.partial(jax.jit, donate_argnums=(0, 2), static_argnames=(
    "model", "mode", "clip", "decay", "eps"))
def _update(params, target_params, opt, batch, weights, lr, *, model, mode,
            clip, decay, eps):
    m = dict(model)
    n = weights.shape[0]

    def one(acc, row):
        (loss, (td_abs, q_taken)), g = jax.value_and_grad(
            context_loss, has_aux=True)(params, target_params, row, n, m,
                                        mode)
        return jax.tree.map(jnp.add, acc, g), (loss, td_abs, q_taken)

    grads, (loss, td_abs, q_taken) = jax.lax.scan(
        one, jax.tree.map(jnp.zeros_like, params),
        dict(batch, weight=weights))
    grads = c.clip_by_global_norm(grads, clip)
    params, opt = c.rmsprop_centered(grads, opt, params, lr, decay, eps)
    return (params, opt, loss.sum(), grads, c.mixed_max_priorities(td_abs),
            q_taken.mean(), jnp.abs(q_taken).mean())


def step(state: dict, batch: dict, weights, key, hp: dict, mode: str):
    """One update; donates the parameters and moments it is given."""
    del key
    lr = hp["lr"]
    if hp.get("lr_decay_steps"):
        lr = lr * hp["lr_decay_rate"] ** (state["step"]
                                          // hp["lr_decay_steps"])
    params, opt, loss, grads, prios, q_mean, q_abs = _update(
        state["params"], state["target_params"], state["opt"], batch,
        weights, jnp.float32(lr), model=model_of(state["params"]), mode=mode,
        clip=hp["max_grad_norm"], decay=hp["rmsprop_decay"],
        eps=hp["rmsprop_eps"])
    n_step = state["step"] + 1
    target = state["target_params"]
    if n_step % hp["target_update_interval"] == 0:
        target = jax.tree.map(jnp.copy, params)
    new = dict(params=params, target_params=target, opt=opt, step=n_step)
    return new, dict(loss=loss, grads=grads, priorities=prios,
                     q_mean=q_mean, q_abs=q_abs)
