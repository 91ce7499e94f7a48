"""Plain reference of the Ape-X DQN learner step over the causal tower of
Nemotron-Labs-TwoTower-30B-A3B (``model_type: nemotron_h``;
https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16
``config.json``; Mamba-2: Dao & Gu 2024, arXiv:2405.21060; Nemotron-H:
arXiv:2504.03624).

The forward pass: ids from two bytes each (``mod`` the vocabulary held),
embedding, then one pre-norm residual part a layer, ``x <- x + part(
RMSNorm(x))``, the part by the layer's own parameters: a Mamba-2 mixer
(``mamba``), grouped-query attention (``attention``) or one shared plus the
routed ``relu^2`` experts held here (``moe``); a final norm and the output
head at the last position, which is ``Q(s, .)`` over the ids held.  Then
the learner step of ``reference/glm4_moe_lite_q.py`` (n-step double-DQN
Huber loss with importance weights, global-norm clip, centred RMSprop,
mixed-max priorities).  Float32 at ``HIGHEST``; ``mode`` lowers the
operands of every matrix product and of the recurrence's outer and inner
products (see ``common``).  Imports nothing of the program; the parameter
tree uses the program's names so leaves can be set side by side.

Departures from the published description, each for the reason given:

* **The scan is the recurrence itself**, one ``lax.scan`` over the positions
  of one context: ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t =
  S_t C_t + D x_t`` a head, ``S_0 = 0``.  Not the chunked algebra the
  published kernels use: that is what is under test.  Checkpointed in
  segments of ``SEGMENT`` positions, so its backward pass keeps a state a
  segment and one segment's states, not one a position.
* **The chip's share.**  The file is given the heads held (Mamba-2 heads
  with their groups, the channels of the convolution and of the gated norm
  and the rows of the out-projection that belong to them; query heads with
  their key/value heads), the experts held (the first ``n_held`` of the
  router's outputs: rank 0) and a slice of the vocabulary, and reads the
  counts off the shapes.  What the absent heads and experts would have
  added is left out, and that partial sum goes on to the next layer.
* **No position embedding** in attention (Nemotron-H has none; the
  config's ``rope_theta`` is inert).  Scores and softmax in float32.
* **Experts as a plain loop** over the held experts, each run on every
  token and weighted by the token's routing weight for it.  **Scoring** is
  ``sigmoid`` with a selection-only bias; ``n_group = topk_group = 1``.
* **No second tower**, no adaLN, no block-diffusion decoding: the config
  has no sizes for them and a Q-network generates nothing.  No dueling
  head, no cache.
* **The batch one context at a time**, each layer rematerialised, as the
  GLM reference does and for its reason.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common as c
from .glm4_moe_lite_q import init_opt, mm, rms_norm, step_keys  # noqa: F401

#: the constants the weights' shapes do not show, by hidden size: the
#: published model's (``configs/nemotron_twotower_q_ep16.json``) and those
#: of the toy the CPU rehearsal and the tests run
MODELS = {
    2688: dict(mamba_head_dim=64, ssm_state_size=128, head_dim=128,
               num_experts_per_tok=6),
    64: dict(mamba_head_dim=16, ssm_state_size=16, head_dim=16,
             num_experts_per_tok=2),
}
SHARED = dict(routed_scaling_factor=2.5, norm_eps=1e-5, expert_rank=0)
#: positions a checkpointed segment of the recurrence spans
SEGMENT = 64


def model_of(params) -> tuple:
    """The model's constants as a hashable tuple (a static argument)."""
    hidden = params["params"]["embedding"].shape[1]
    return tuple(sorted({**SHARED, **MODELS[hidden]}.items()))


def init_rule(path, shape):
    """Gains at one; stacked experts ``[E, in, out]`` by their own fan-in;
    the router's bias small but not nought, so that selection depends on
    it.  The scan's own constants as Mamba-2 initialises them, spread
    evenly over the heads held so that no decay vanishes and none sticks
    at one: ``A = 1 .. 16``, steps ``softplus(dt_bias)`` over
    ``time_step_min .. time_step_max`` = 0.001 .. 0.1, ``D = 1``.  Every
    other leaf by the general rule.

    **The embedding is a unit table times ``sqrt(hidden)``** (Vaswani et
    al. 2017, section 3.4), not the general rule's ``N(0, 2 / rows)``,
    which takes the vocabulary for a fan-in and leaves a token's own
    vector 1/700 of what the first part adds to it.  A ``relu^2`` part
    drawn at random adds the SAME vector to every token (``E[relu(z)^2] >
    0``, so ``W_down^T E[r]`` is one direction; a gated ``silu(g) u`` has
    none), every router then scores that direction, and the busiest of
    128 experts took 3-7.6 times the mean and drifted under training
    (PR 33, ``PERF.md`` section 6).  The published model's routers are
    balanced by its trained selection bias; seeded weights are balanced
    by what a router reads being, before all else, the token: with the
    parts' sum (an RMS of some 7.5 over the nine layers) beside rows of
    RMS 51.8 the mean of the unit token vectors is 0.03-0.06 long and
    the held experts' share stays within a tenth of even.  ``RMSNorm``
    before every part makes the scale itself free."""
    name = path[-1]
    if name == "embedding":
        return ("normal", math.sqrt(shape[1]))
    if name in ("scale", "norm_scale", "D"):
        return ("const", 1.0)
    if name.startswith("experts_"):
        return ("normal", math.sqrt(2.0 / shape[1]))
    if name == "router_bias":
        return ("normal", 0.01)
    if name == "A_log":
        return ("const", np.log(np.linspace(1.0, 16.0, shape[0])))
    if name == "dt_bias":
        dt = np.geomspace(1e-3, 1e-1, shape[0])
        return ("const", dt + np.log(-np.expm1(-dt)))
    return None


# -- the forward pass ------------------------------------------------------------

def relu2_ffn(h, p, mode):
    return mm(jnp.square(jax.nn.relu(mm(h, p["up"], mode))), p["down"], mode)


def recurrence(x, dt, a, b_in, c_in, mode):
    """``x [T, H, P]``, ``dt [T, H]``, ``a [H]``, ``b_in``, ``c_in``
    ``[T, G, N]`` -> ``y [T, H, P]``: the state ``[H, P, N]`` stepped one
    position at a time from nought."""
    t, h, p = x.shape
    g, n = b_in.shape[1:]
    per = h // g

    def one(s, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h, c_h = (jnp.repeat(v, per, axis=0) for v in (b_t, c_t))
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + c.rnd(dt_t[:, None] * x_t, mode)[:, :, None]
             * c.rnd(b_h, mode)[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", c.rnd(s, mode), c.rnd(c_h, mode),
                             precision=c.HIGHEST)

    seg = math.gcd(t, SEGMENT)

    @jax.checkpoint
    def segment(s, inp):
        return jax.lax.scan(one, s, inp)

    _, y = jax.lax.scan(
        segment, jnp.zeros((h, p, n), jnp.float32),
        jax.tree.map(lambda v: v.reshape(t // seg, seg, *v.shape[1:]),
                     (x, dt, b_in, c_in)))
    return y.reshape(t, h, p)


def mamba(u, p, m, mode):
    """One context ``u [T, D]`` through the heads held."""
    t = u.shape[0]
    pd, n = m["mamba_head_dim"], m["ssm_state_size"]
    h = p["A_log"].shape[0]
    inner = h * pd
    g = (p["conv_bias"].shape[0] - inner) // (2 * n)
    proj = mm(u, p["in_proj"]["kernel"], mode)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * g * n],
                  proj[:, 2 * inner + 2 * g * n:])
    k = p["conv_kernel"].shape[0]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[i:i + t] * p["conv_kernel"][i]
                          for i in range(k)) + p["conv_bias"])
    x = xbc[:, :inner].reshape(t, h, pd)
    y = recurrence(x, jax.nn.softplus(dt + p["dt_bias"]),
                   -jnp.exp(p["A_log"]),
                   xbc[:, inner:inner + g * n].reshape(t, g, n),
                   xbc[:, inner + g * n:].reshape(t, g, n), mode)
    y = (y + p["D"][:, None] * x).reshape(t, inner) * jax.nn.silu(z)
    y = y.reshape(t, g, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + m["norm_eps"])
    return mm(y.reshape(t, inner) * p["norm_scale"], p["out_proj"]["kernel"],
              mode)


def gqa(u, p, m, mode):
    """One context ``u [T, D]``: query head ``j`` reads key/value head
    ``j // (heads / kv heads)``."""
    t, hd = u.shape[0], m["head_dim"]
    q = mm(u, p["q"]["kernel"], mode).reshape(t, -1, hd)
    k = mm(u, p["k"]["kernel"], mode).reshape(t, -1, hd)
    v = mm(u, p["v"]["kernel"], mode).reshape(t, -1, hd)
    per = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, per, axis=1), jnp.repeat(v, per, axis=1)
    s = jnp.einsum("qhd,khd->hqk", c.rnd(q, mode), c.rnd(k, mode),
                   precision=c.HIGHEST) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", c.rnd(jax.nn.softmax(s, -1), mode),
                   c.rnd(v, mode), precision=c.HIGHEST)
    return mm(o.reshape(t, -1), p["o"]["kernel"], mode)


def moe(h, p, m, mode):
    """Shared expert + the held experts' part for one context."""
    y = relu2_ffn(h, p["shared"], mode)
    s = jax.nn.sigmoid(jnp.matmul(h, p["router_kernel"],
                                  precision=c.HIGHEST))
    _, picks = jax.lax.top_k(s + p["router_bias"], m["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, picks, -1)
    w = m["routed_scaling_factor"] * picked / picked.sum(-1, keepdims=True)
    n_held = p["experts_up"].shape[0]
    lo = m["expert_rank"] * n_held
    for e in range(n_held):                  # absent experts add nothing
        w_e = jnp.sum(jnp.where(picks == lo + e, w, 0.0), -1)
        y = y + w_e[:, None] * relu2_ffn(
            h, dict(up=p["experts_up"][e], down=p["experts_down"][e]), mode)
    return y


PARTS = {"mamba": mamba, "attention": gqa, "moe": moe}


def layer(x, p, m, mode):
    kind = next(k for k in PARTS if k in p)
    u = rms_norm(x, p["norm"]["scale"], m["norm_eps"])
    return x + PARTS[kind](u, p[kind], m, mode)


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
    last = rms_norm(x[-1], p["final_norm"]["scale"], m["norm_eps"])
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
