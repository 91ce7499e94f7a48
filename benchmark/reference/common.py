"""Plain float32 pieces shared by the family references.

Nothing here imports the program (``apex_tpu``).  Everything is a
straightforward ``jax.numpy`` / ``numpy`` statement of the published
algorithm: prioritized stratified sampling over a sum tree (Schaul et al.
2016, as the reference ``memory.py`` does it), importance weights, Huber,
global-norm clipping, centred RMSprop.

``mode`` selects the arithmetic of every matrix product and convolution:

* ``"f32"``  — float32 operands at ``precision=HIGHEST`` (the reference);
* ``"bf16"`` — operands rounded to bfloat16 (what the configuration states);
* ``"fp8"``  — operands rounded to float8_e4m3fn, the nearest precision
  below the configuration's bfloat16 (the control that must fail).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: (exponent bits, mantissa bits) of the mode's storage type
_ROUND = {"f32": None, "bf16": (8, 7), "fp8": (4, 3)}


def rnd(x, mode: str):
    """Round an operand to the mode's storage type, in float32.
    ``reduce_precision`` and not a pair of casts: XLA elides a float32 ->
    bfloat16 -> float32 round trip (``xla_allow_excess_precision``).  The
    gradient passes straight through: a cotangent rounded to fp8 would
    underflow to nought, which no one would ship; rounded operands under a
    float32 backward pass is the step that tempts."""
    bits = _ROUND[mode]
    if bits is None:
        return x
    return x + jax.lax.stop_gradient(
        jax.lax.reduce_precision(x, *bits) - x)


def dense(x, layer: dict, mode: str):
    y = jnp.dot(rnd(x, mode), rnd(layer["kernel"], mode), precision=HIGHEST)
    return y + rnd(layer["bias"], mode)


def conv(x, layer: dict, stride: int, mode: str):
    y = jax.lax.conv_general_dilated(
        rnd(x, mode), rnd(layer["kernel"], mode), (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return y + rnd(layer["bias"], mode)


def huber(x):
    a = jnp.abs(x)
    return jnp.where(a < 1.0, 0.5 * x * x, a - 0.5)


def mixed_max_priorities(td_abs):
    """``0.9 max|td| + 0.1 |td| + 1e-6`` (reference ``utils.py:77``)."""
    return 0.9 * td_abs.max() + 0.1 * td_abs + 1e-6


# -- prioritized sampling (numpy; float32 like the device tree) -------------

def stratified_indices(sum_tree: np.ndarray, key, batch: int,
                       size: int) -> np.ndarray:
    """One index per stratum ``[i, i+1) * total / B`` by root-to-leaf
    descent (reference ``memory.py:106-129, 242-250``), in float32 so that
    it lands on the same leaf as a float32 tree on the device does."""
    tree = np.asarray(sum_tree, np.float32)
    cap = tree.shape[0] // 2
    offsets = np.asarray(jax.random.uniform(key, (batch,), jnp.float32))
    u = (np.arange(batch, dtype=np.float32) + offsets) * np.float32(
        tree[1] / np.float32(batch))
    node = np.ones(batch, np.int64)
    for _ in range(cap.bit_length() - 1):
        left = tree[2 * node]
        right = u >= left
        u = np.where(right, u - left, u).astype(np.float32)
        node = 2 * node + right
    return np.clip(node - cap, 0, max(size - 1, 0)).astype(np.int64)


def tree_leaves(priorities, alpha: float, eps: float) -> np.ndarray:
    """``max(p, eps) ** alpha`` in float32: what an ingest writes."""
    p = np.maximum(np.asarray(priorities, np.float32), np.float32(eps))
    return np.power(p, np.float32(alpha)).astype(np.float32)


def with_leaves(sum_tree: np.ndarray, idx: np.ndarray,
                leaves: np.ndarray) -> np.ndarray:
    """A copy of the float32 sum tree with ``leaves`` at rows ``idx`` and
    every ancestor summed anew from its two children, level by level."""
    tree = np.array(sum_tree, np.float32)
    nodes = np.asarray(idx, np.int64) + tree.shape[0] // 2
    tree[nodes] = leaves
    while nodes[0] > 1:
        nodes = np.unique(nodes // 2)
        tree[nodes] = tree[2 * nodes] + tree[2 * nodes + 1]
    return tree


def is_weights(leaves: np.ndarray, size: int, idx: np.ndarray,
               beta: float) -> np.ndarray:
    """``(p_i N)^-beta / max_j (p_j N)^-beta`` over the live leaves."""
    live = np.asarray(leaves[:size], np.float64)
    total = live.sum()
    max_w = (live.min() / total * size) ** (-beta)
    return ((live[idx] / total * size) ** (-beta) / max_w).astype(np.float32)


# -- optimizers --------------------------------------------------------------

def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return jax.tree.map(lambda g: g * scale, grads)


def rmsprop_centered_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": zeros}


def rmsprop_centered(grads, state, params, lr, decay: float, eps: float):
    """torch ``RMSprop(centered=True)``: ``g / sqrt(E[g^2] - E[g]^2 + eps)``."""
    mu = jax.tree.map(lambda m, g: decay * m + (1 - decay) * g,
                      state["mu"], grads)
    nu = jax.tree.map(lambda n, g: decay * n + (1 - decay) * g * g,
                      state["nu"], grads)
    params = jax.tree.map(
        lambda p, g, m, n: p - lr * g * jax.lax.rsqrt(n - m * m + eps),
        params, grads, mu, nu)
    return params, {"mu": mu, "nu": nu}


# -- the replay as the reference sees it --------------------------------------

class ReplayView:
    """The rows the harness fed, addressed by transition index: stacks are
    rebuilt from single frames, oldest first on the channel axis."""

    def __init__(self, rows: dict, frame_shape: tuple, stack: int):
        self.rows = rows
        self.frame_shape = tuple(frame_shape)
        self.stack = stack

    def _stacks(self, ids: np.ndarray) -> np.ndarray:
        b, s = ids.shape
        fr = self.rows["frames"][ids.reshape(-1)]
        fr = fr.reshape(b, s, *self.frame_shape)
        fr = np.moveaxis(fr, 1, -2)
        return fr.reshape(b, *self.frame_shape[:-1],
                          s * self.frame_shape[-1])

    def batch(self, idx: np.ndarray) -> dict:
        r = self.rows
        return dict(obs=self._stacks(r["obs_ids"][idx]),
                    next_obs=self._stacks(r["next_ids"][idx]),
                    action=r["action"][idx], reward=r["reward"][idx],
                    discount=r["discount"][idx])
