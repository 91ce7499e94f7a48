"""Plain reference of the Ape-X DQN learner step over a GLM-4.7-Flash
torso (``glm4_moe_lite``; https://huggingface.co/zai-org/GLM-4.7-Flash,
``config.json`` and the ``Glm4MoeLite`` modelling code of transformers).

The forward pass: ids from two bytes each (``mod`` the vocabulary held),
embedding, pre-norm residual layers of multi-head latent attention then a
SwiGLU feed-forward (layer 0) or one shared expert plus the routed experts
held here (later layers), a final norm and the output head at the last
position, which is ``Q(s, .)`` over the ids held.  Then the n-step
double-DQN Huber loss with importance weights, global-norm clip, centred
RMSprop and mixed-max priorities, as ``reference/dqn.py`` has them.
Float32 at ``HIGHEST`` (the harness sets the default precision; the
products here name it too); ``mode`` lowers the operands of every matrix
product (see ``common``).  Imports nothing of the program; the parameter
tree uses the program's names so leaves can be set side by side.

Departures from the published description, each for the reason given:

* **The chip's share.**  The file is given the held experts only
  (``experts_* [n_held, ...]``, the first ``n_held`` of the router's
  ``n_routed_experts`` outputs: rank 0) and a slice of the vocabulary; the
  router keeps its published width and its experts per token, and what the
  absent experts would have added is left out.  That partial result goes on
  to the next layer, as in an 8-way expert deployment before its exchange.
* **Experts as a plain loop** over the held experts, each run on every
  token and weighted by the token's routing weight for it (nought where it
  did not pick it).  No sort, no grouped product, no capacity.
* **Scoring** is ``sigmoid`` with a selection-only correction bias
  (``topk_method: noaux_tc``); the config has no ``scoring_func`` key.
  ``n_group = topk_group = 1``: group-limited routing is the identity.
* **RoPE** pairs dimension ``i`` with ``i + d/2`` of the 64 rope
  dimensions (rotate-half), positions from 0.
* **No dueling head** (the published model has none), no multi-token
  prediction module (``num_nextn_predict_layers`` 1 -> 0: an auxiliary loss
  this learner does not have), no cache (whole contexts).
* **The batch one context at a time** inside one jitted loop that
  accumulates the gradient, each layer rematerialised in the backward pass:
  the same sums in another order, so that 24 bytes a parameter (state 16,
  accumulator 4, a context's gradient 4) and one context's float32
  activations fit a 16 GB chip at the published widths.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import common as c

#: the constants the weights' shapes do not show, by hidden size: the
#: published model's (``configs/glm47_flash_q_ep8.json``, ``model``) and
#: those of the toy the CPU rehearsal and the tests run
MODELS = {
    2048: dict(num_heads=20, qk_nope_head_dim=192, qk_rope_head_dim=64,
               v_head_dim=256, num_experts_per_tok=4),
    64: dict(num_heads=2, qk_nope_head_dim=12, qk_rope_head_dim=4,
             v_head_dim=16, num_experts_per_tok=2),
}
SHARED = dict(routed_scaling_factor=1.8, rope_theta=1e6, rms_norm_eps=1e-5,
              expert_rank=0)


def model_of(params) -> tuple:
    """The model's constants as a hashable tuple (a static argument)."""
    hidden = params["params"]["embedding"].shape[1]
    return tuple(sorted({**SHARED, **MODELS[hidden]}.items()))


def init_rule(path, shape):
    """Gains at one; stacked experts ``[E, in, out]`` by their own fan-in;
    the router's bias small but not nought, so that selection depends on
    it; every other leaf by the general rule."""
    name = path[-1]
    if name == "scale":
        return ("const", 1.0)
    if name.startswith("experts_"):
        return ("normal", math.sqrt(2.0 / shape[1]))
    if name == "router_bias":
        return ("normal", 0.01)
    return None


# -- the forward pass ------------------------------------------------------------

def mm(x, w, mode):
    return jnp.matmul(c.rnd(x, mode), c.rnd(w, mode), precision=c.HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """``x [T, ..., d]``: rotate (x_i, x_{i + d/2}) by ``t * theta^(-2i/d)``."""
    d, t = x.shape[-1], x.shape[0]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def swiglu(h, p, mode):
    return mm(jax.nn.silu(mm(h, p["gate"], mode)) * mm(h, p["up"], mode),
              p["down"], mode)


def mla(h, p, m, mode):
    """One context ``h [T, D]``."""
    t = h.shape[0]
    nh, nope, rp, vd = (m["num_heads"], m["qk_nope_head_dim"],
                        m["qk_rope_head_dim"], m["v_head_dim"])
    eps = m["rms_norm_eps"]
    c_q = rms_norm(mm(h, p["q_a"]["kernel"], mode), p["q_a_norm"]["scale"],
                   eps)
    q = mm(c_q, p["q_b"]["kernel"], mode).reshape(t, nh, nope + rp)
    kv = mm(h, p["kv_a"]["kernel"], mode)
    c_kv, k_r = kv[:, :-rp], kv[:, -rp:]
    kv = mm(rms_norm(c_kv, p["kv_a_norm"]["scale"], eps),
            p["kv_b"]["kernel"], mode).reshape(t, nh, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:],
                                             m["rope_theta"])], -1)
    k_r = jnp.broadcast_to(rope(k_r, m["rope_theta"])[:, None, :],
                           (t, nh, rp))
    k = jnp.concatenate([k_nope, k_r], -1)
    s = jnp.einsum("qhd,khd->hqk", c.rnd(q, mode), c.rnd(k, mode),
                   precision=c.HIGHEST) / math.sqrt(nope + rp)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", c.rnd(jax.nn.softmax(s, -1), mode),
                   c.rnd(v, mode), precision=c.HIGHEST)
    return mm(o.reshape(t, nh * vd), p["o"]["kernel"], mode)


def moe(h, p, m, mode):
    """Shared expert + the held experts' part for one context."""
    y = swiglu(h, p["shared"], mode)
    s = jax.nn.sigmoid(jnp.matmul(h, p["router_kernel"],
                                  precision=c.HIGHEST))
    k = m["num_experts_per_tok"]
    _, picks = jax.lax.top_k(s + p["router_bias"], k)
    picked = jnp.take_along_axis(s, picks, -1)
    w = m["routed_scaling_factor"] * picked / picked.sum(-1, keepdims=True)
    n_held = p["experts_gate"].shape[0]
    lo = m["expert_rank"] * n_held
    for e in range(n_held):                  # absent experts add nothing
        w_e = jnp.sum(jnp.where(picks == lo + e, w, 0.0), -1)
        g = mm(h, p["experts_gate"][e], mode)
        u = mm(h, p["experts_up"][e], mode)
        y = y + w_e[:, None] * mm(jax.nn.silu(g) * u,
                                  p["experts_down"][e], mode)
    return y


def layer(x, p, m, mode):
    eps = m["rms_norm_eps"]
    x = x + mla(rms_norm(x, p["attn_norm"]["scale"], eps), p["mla"], m, mode)
    h = rms_norm(x, p["ffn_norm"]["scale"], eps)
    if "mlp" in p:
        return x + swiglu(h, p["mlp"], mode)
    return x + moe(h, p["moe"], m, mode)


def forward_one(params, obs_u8, m, mode, remat=True):
    """``Q(s, .)`` of one context ``u8[2T]`` -> ``f32[V]``."""
    p = params["params"]
    vocab = p["embedding"].shape[0]
    b = obs_u8.reshape(-1, 2).astype(jnp.int32)
    x = p["embedding"][(b[:, 0] + 256 * b[:, 1]) % vocab]
    n_layers = sum(1 for name in p if name.startswith("layers_"))
    # A lowered mode rounds every weight where it is used.  Nothing orders
    # those roundings, so XLA made them all at once and kept a rounded copy
    # of the parameters and of the target (the fp8 control's program wanted
    # 4.86 GB of temporaries where 4.73 were free, my chip runs, PR 29).
    # ``held`` ties a layer's weights to the activations that reach it, so
    # they are rounded when the layer runs, a layer's worth at a time.
    held = (lambda x, w: (x, w)) if mode == "f32" else (
        lambda x, w: jax.lax.optimization_barrier((x, w)))
    for i in range(n_layers):
        f = functools.partial(layer, m=m, mode=mode)
        x, p_i = held(x, p[f"layers_{i}"])
        x = (jax.checkpoint(f) if remat else f)(x, p_i)
    last = rms_norm(x[-1], p["final_norm"]["scale"], m["rms_norm_eps"])
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


def init_opt(params, hp):
    # buffers of their own for each moment: the step donates them
    del hp
    return {name: jax.tree.map(jnp.zeros_like, params)
            for name in ("mu", "nu")}


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


def step_keys(key):
    """(sampling key, update key): the step samples with the key as it is
    and its update draws nothing."""
    return key, None


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
