"""Plain reference of the Ape-X DQN learner step over the layer stack of
Qwen3-Next-80B-A3B-Instruct (``model_type: qwen3_next``;
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``;
Gated DeltaNet: Yang et al. 2024, arXiv:2412.06464).

The forward pass: ids from two bytes each (``mod`` the vocabulary held),
embedding, then a layer ``x <- x + mixer(norm(x))``; ``x <- x +
moe(norm(x))``, the mixer by the layer's own parameters: a Gated DeltaNet
(``gdn``) or gated softmax attention (``attention``); a final norm and the
output head at the last position, which is ``Q(s, .)`` over the ids held.
Every norm is ``x / sqrt(mean(x^2) + eps) * (1 + w)`` except the DeltaNet's
gated norm, whose gain is ``w``.  Then the learner step of
``reference/nemotron_h_q.py`` (n-step double-DQN Huber loss with importance
weights, global-norm clip, centred RMSprop, mixed-max priorities).  Float32
at ``HIGHEST``; ``mode`` lowers the operands of every matrix product and of
the recurrence's outer and inner products (see ``common``).  Imports
nothing of the program; the parameter tree uses the program's names so
leaves can be set side by side.

Departures from the published description, each for the reason given:

* **The delta rule is the recurrence itself**, one ``lax.scan`` over the
  positions of one context, a value head's state a ``d_k x d_v`` matrix
  from nought: ``S <- exp(g_t) S``; ``u = beta_t (v_t - S^T k_t)``; ``S <-
  S + k_t u^T``; ``o_t = S^T q_t``.  Not the chunked algebra with its
  triangular inverse that the published kernels use: that is what is under
  test.  Checkpointed in segments of ``SEGMENT`` positions.
* **The chip's share.**  The file is given the heads held (key heads with
  their value heads, the columns of both in-projections, the channels of
  the convolution and the rows of the out-projection that belong to them;
  query heads with their key/value heads), the experts held (the first
  ``n_held`` of the router's outputs: rank 0) and a slice of the
  vocabulary, and reads the counts off the shapes.  What the absent heads
  and experts would have added is left out, and that partial sum goes on
  to the next layer.
* **Experts as a plain loop** over the held experts, each run on every
  token and weighted by the token's routing weight for it.  **Scoring** is
  a float32 softmax over all the router's outputs, the ``k`` largest,
  renormalised; no bias, no scaling.
* **No multi-token prediction** (a Q-network generates nothing), no
  dueling head, no cache.
* **The batch one context at a time**, each layer rematerialised, as the
  other token references do and for their reason.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common as c
from .glm4_moe_lite_q import (init_opt, mm, rms_norm, rope,  # noqa: F401
                              step_keys, swiglu)

#: the constants the weights' shapes do not show, by hidden size: the
#: published model's (``configs/qwen3_next_q_ep16.json``) and those of the
#: toy the CPU rehearsal and the tests run
MODELS = {
    2048: dict(linear_key_head_dim=128, linear_value_head_dim=128,
               head_dim=256, rotary_dim=64, num_experts_per_tok=10),
    64: dict(linear_key_head_dim=16, linear_value_head_dim=16, head_dim=16,
             rotary_dim=4, num_experts_per_tok=4),
}
SHARED = dict(rope_theta=1e7, rms_norm_eps=1e-6, expert_rank=0)
#: positions a checkpointed segment of the recurrence spans
SEGMENT = 64


def model_of(params) -> tuple:
    """The model's constants as a hashable tuple (a static argument)."""
    hidden = params["params"]["embedding"].shape[1]
    return tuple(sorted({**SHARED, **MODELS[hidden]}.items()))


def init_rule(path, shape):
    """The zero-centred gains ``w`` small about nought (``1 + w`` about
    one), the gated norm's gain at one; stacked experts ``[E, in, out]``
    by their own fan-in.  The recurrence's own constants as the published
    model initialises them (Mamba-2's rule), spread evenly over the heads
    held so that no decay vanishes and none sticks at one: ``A = 1 ..
    16``, steps ``softplus(dt_bias)`` over 0.001 .. 0.1.  **The embedding
    is a unit table times ``sqrt(hidden)``**, ``nemotron_h_q``'s final
    rule and for its reason: the general rule takes the vocabulary for a
    fan-in and leaves a token's own vector far under what the first part
    adds to it, so every router would score the parts' sum and not the
    token.  Every other leaf by the general rule."""
    name = path[-1]
    if name == "embedding":
        return ("normal", math.sqrt(shape[1]))
    if name == "scale":
        return ("normal", 0.01)
    if name == "norm_scale":
        return ("const", 1.0)
    if name.startswith("experts_"):
        return ("normal", math.sqrt(2.0 / shape[1]))
    if name == "A_log":
        return ("const", np.log(np.linspace(1.0, 16.0, shape[0])))
    if name == "dt_bias":
        dt = np.geomspace(1e-3, 1e-1, shape[0])
        return ("const", dt + np.log(-np.expm1(-dt)))
    return None


# -- the forward pass ------------------------------------------------------------

def norm(x, p, m):
    """The zero-centred RMSNorm: gain ``1 + w``."""
    return rms_norm(x, 1.0 + p["scale"], m["rms_norm_eps"])


def recurrence(q, k, v, g, beta, mode):
    """``q``, ``k`` ``[T, Hk, dk]``, ``v [T, Hv, dv]``, ``g``, ``beta``
    ``[T, Hv]`` -> ``o [T, Hv, dv]``: each value head's state ``[dk, dv]``
    stepped one position at a time from nought; value head ``j`` reads key
    head ``j // (Hv / Hk)``."""
    t, hk, dk = q.shape
    hv, dv = v.shape[1:]
    per = hv // hk

    def one(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        q_h, k_h = (c.rnd(jnp.repeat(x, per, axis=0), mode)
                    for x in (q_t, k_t))
        s = jnp.exp(g_t)[:, None, None] * s
        held = jnp.einsum("hkd,hk->hd", c.rnd(s, mode), k_h,
                          precision=c.HIGHEST)
        u = b_t[:, None] * (v_t - held)
        s = s + k_h[:, :, None] * c.rnd(u, mode)[:, None, :]
        return s, jnp.einsum("hkd,hk->hd", c.rnd(s, mode), q_h,
                             precision=c.HIGHEST)

    seg = math.gcd(t, SEGMENT)

    @jax.checkpoint
    def segment(s, inp):
        return jax.lax.scan(one, s, inp)

    _, o = jax.lax.scan(
        segment, jnp.zeros((hv, dk, dv), jnp.float32),
        jax.tree.map(lambda x: x.reshape(t // seg, seg, *x.shape[1:]),
                     (q, k, v, g, beta)))
    return o.reshape(t, hv, dv)


def gdn(u, p, m, mode):
    """One context ``u [T, D]`` through the key heads held."""
    t = u.shape[0]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    hv = p["A_log"].shape[0]
    hk = (p["in_proj_qkvz"]["kernel"].shape[1] - 2 * hv * dv) // (2 * dk)
    r = hv // hk
    # a key head's columns together: [q | k | v of its value heads | z]
    proj = mm(u, p["in_proj_qkvz"]["kernel"], mode).reshape(t, hk, -1)
    q, k, v, z = jnp.split(proj, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    ba = mm(u, p["in_proj_ba"]["kernel"], mode).reshape(t, hk, 2 * r)
    beta = jax.nn.sigmoid(ba[..., :r]).reshape(t, hv)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
        ba[..., r:].reshape(t, hv) + p["dt_bias"])
    # the convolution's channels: all of q, all of k, all of v; no bias
    qkv = jnp.concatenate([x.reshape(t, -1) for x in (q, k, v)], -1)
    n = p["conv_kernel"].shape[0]
    padded = jnp.pad(qkv, ((n - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[i:i + t] * p["conv_kernel"][i]
                          for i in range(n)))
    q = qkv[:, :hk * dk].reshape(t, hk, dk)
    k = qkv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(t, hv, dv)
    q, k = (x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
            for x in (q, k))
    o = recurrence(q / math.sqrt(dk), k, v, g, beta, mode)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + m["rms_norm_eps"])
    y = p["norm_scale"] * o * jax.nn.silu(z.reshape(t, hv, dv))
    return mm(y.reshape(t, hv * dv), p["out_proj"]["kernel"], mode)


def gated_attention(u, p, m, mode):
    """One context ``u [T, D]``: query head ``j`` reads key/value head
    ``j // (heads / kv heads)``; the query kernel gives ``[q | gate]`` a
    head."""
    t, hd, rd = u.shape[0], m["head_dim"], m["rotary_dim"]
    qg = mm(u, p["q"]["kernel"], mode).reshape(t, -1, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = mm(u, p["k"]["kernel"], mode).reshape(t, -1, hd)
    v = mm(u, p["v"]["kernel"], mode).reshape(t, -1, hd)

    def turned(x, gain):
        x = norm(x, gain, m)
        return jnp.concatenate([rope(x[..., :rd], m["rope_theta"]),
                                x[..., rd:]], -1)

    q, k = turned(q, p["q_norm"]), turned(k, p["k_norm"])
    per = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, per, axis=1), jnp.repeat(v, per, axis=1)
    s = jnp.einsum("qhd,khd->hqk", c.rnd(q, mode), c.rnd(k, mode),
                   precision=c.HIGHEST) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", c.rnd(jax.nn.softmax(s, -1), mode),
                   c.rnd(v, mode), precision=c.HIGHEST)
    return mm((o * jax.nn.sigmoid(gate)).reshape(t, -1), p["o"]["kernel"],
              mode)


def moe(h, p, m, mode):
    """Gated shared expert + the held experts' part for one context."""
    y = swiglu(h, p["shared"], mode) * jax.nn.sigmoid(
        mm(h, p["shared_gate"]["kernel"], mode))
    s = jax.nn.softmax(jnp.matmul(h, p["router_kernel"],
                                  precision=c.HIGHEST), -1)
    picked, picks = jax.lax.top_k(s, m["num_experts_per_tok"])
    w = picked / picked.sum(-1, keepdims=True)
    n_held = p["experts_up"].shape[0]
    lo = m["expert_rank"] * n_held
    for e in range(n_held):                  # absent experts add nothing
        w_e = jnp.sum(jnp.where(picks == lo + e, w, 0.0), -1)
        y = y + w_e[:, None] * swiglu(
            h, dict(gate=p["experts_gate"][e], up=p["experts_up"][e],
                    down=p["experts_down"][e]), mode)
    return y


MIXERS = {"gdn": gdn, "attention": gated_attention}


def layer(x, p, m, mode):
    mixer, experts = p["mixer"], p["experts"]
    kind = next(k for k in MIXERS if k in mixer)
    x = x + MIXERS[kind](norm(x, mixer["norm"], m), mixer[kind], m, mode)
    return x + moe(norm(x, experts["norm"], m), experts["moe"], m, mode)


def forward_one(params, obs_u8, m, mode, remat=True):
    """``Q(s, .)`` of one context ``u8[2T]`` -> ``f32[V]``."""
    p = params["params"]
    vocab = p["embedding"].shape[0]
    b = obs_u8.reshape(-1, 2).astype(jnp.int32)
    x = p["embedding"][(b[:, 0] + 256 * b[:, 1]) % vocab]
    n_layers = sum(1 for name in p if name.startswith("layers_"))
    # a lowered mode rounds a layer's weights when the layer runs, not all
    # at once (the GLM reference's ``held``, for its reason)
    held = (lambda x, w: (x, w)) if mode == "f32" else (
        lambda x, w: jax.lax.optimization_barrier((x, w)))
    for i in range(n_layers):
        f = functools.partial(layer, m=m, mode=mode)
        x, p_i = held(x, p[f"layers_{i}"])
        x = (jax.checkpoint(f) if remat else f)(x, p_i)
    last = norm(x[-1], p["final_norm"], m)
    last, head = held(last, p["head"]["kernel"])
    return mm(last, head, mode)


def forward(params, obs_u8, mode: str = "f32"):
    """``Q`` rows of a batch ``u8[B, 2T]``, one context after another."""
    m = dict(model_of(params))
    return jax.lax.map(lambda o: forward_one(params, o, m, mode), obs_u8)


# -- the learner step --------------------------------------------------------------

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
