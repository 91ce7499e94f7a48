"""Plain reference of the Ape-X DQN learner step (Horgan et al. 2018;
upstream ``model.py:14-107``, ``utils.py:64-97``, ``ApeX.py:37``).

Dueling Nature-CNN over uint8 NHWC stacks, n-step double-DQN Huber loss
with importance weights, global-norm clip, centred RMSprop, mixed-max
priorities.  Float32 at ``HIGHEST``; ``mode`` lowers the operands (see
``common``).  Imports nothing of the program; its parameter tree uses the
names flax gives the program's module so leaves can be set side by side.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import common as c


def forward(params: dict, obs_u8, mode: str):
    p = params["params"]
    x = obs_u8.astype(jnp.float32) / 255.0
    for name, stride in (("Conv_0", 4), ("Conv_1", 2), ("Conv_2", 1)):
        x = jax.nn.relu(c.conv(x, p[name], stride, mode))
    x = x.reshape(x.shape[0], -1)

    def head(name):
        h = jax.nn.relu(c.dense(x, p[f"{name}_hidden"], mode))
        return c.dense(h, p[f"{name}_out"], mode)

    adv, val = head("advantage"), head("value")
    return val + adv - adv.mean(axis=1, keepdims=True)


def loss_fn(params, target_params, batch, weights, mode):
    both = jnp.concatenate([batch["obs"], batch["next_obs"]], axis=0)
    q, next_q = jnp.split(forward(params, both, mode), 2, axis=0)
    tgt_next_q = forward(target_params, batch["next_obs"], mode)
    a = batch["action"].astype(jnp.int32)[:, None]
    q_taken = jnp.take_along_axis(q, a, axis=1)[:, 0]
    next_a = next_q.argmax(axis=1)[:, None]
    boot = jnp.take_along_axis(tgt_next_q, next_a, axis=1)[:, 0]
    target = batch["reward"] + batch["discount"] * boot
    td = jax.lax.stop_gradient(target) - q_taken
    loss = (c.huber(td) * weights).mean()
    return loss, (jnp.abs(td), q_taken)


def init_opt(params, hp):
    del hp
    return c.rmsprop_centered_init(params)


@functools.partial(jax.jit, static_argnames=("mode", "clip", "decay", "eps"))
def _update(params, target_params, opt, batch, weights, lr, *, mode, clip,
            decay, eps):
    (loss, (td_abs, q_taken)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, target_params, batch, weights, mode)
    grads = c.clip_by_global_norm(grads, clip)
    params, opt = c.rmsprop_centered(grads, opt, params, lr, decay, eps)
    return (params, opt, loss, grads, c.mixed_max_priorities(td_abs),
            q_taken.mean(), jnp.abs(q_taken).mean())


def step_keys(key):
    """(sampling key, update key) of one step's key: the published step
    samples with the key as it is and its update draws nothing."""
    return key, None


def step(state: dict, batch: dict, weights, key, hp: dict, mode: str):
    """One update.  ``state``: params, target_params, opt, step.  Returns
    ``(state, out)`` with the loss, the clipped gradient the optimizer
    got, and the priorities to write back."""
    del key
    lr = hp["lr"]
    if hp.get("lr_decay_steps"):
        lr = lr * hp["lr_decay_rate"] ** (state["step"]
                                          // hp["lr_decay_steps"])
    params, opt, loss, grads, prios, q_mean, q_abs = _update(
        state["params"], state["target_params"], state["opt"], batch,
        weights, jnp.float32(lr), mode=mode, clip=hp["max_grad_norm"],
        decay=hp["rmsprop_decay"], eps=hp["rmsprop_eps"])
    n = state["step"] + 1
    target = params if n % hp["target_update_interval"] == 0 \
        else state["target_params"]
    new = dict(params=params, target_params=target, opt=opt, step=n)
    return new, dict(loss=loss, grads=grads, priorities=prios,
                     q_mean=q_mean, q_abs=q_abs)
