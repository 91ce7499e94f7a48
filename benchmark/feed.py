"""What the benchmark makes from ``--seed``: the weights the trainer starts
from and the rows the checked steps sample.  One general generator; its
parameters come from the configuration's and the traffic mix's files.

The weights are drawn on the device in one jitted call, in float32 (the
type the program holds them in).  The program only lends the shapes: the
reference gets the very same numbers from here, not from the program.
"""

from __future__ import annotations

import math

import numpy as np


def _seed_words(seed: int) -> tuple[int, int]:
    seed = int(seed)
    return seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF


def program_seed(seed: int) -> int:
    """``--seed`` folded into the 31 bits the program's ``--seed`` takes."""
    lo, hi = _seed_words(seed)
    return (lo ^ (hi * 2654435)) % (2 ** 31 - 1)


def make_weights(shape_tree, seed: int, init_rule=None):
    """Float32 weights with the tree and shapes of ``shape_tree``.

    The family's reference module may say how a leaf is drawn:
    ``init_rule(path, shape)``, ``path`` the leaf's keys from the root,
    returns ``("normal", std)``, ``("const", value)`` or ``None``.  Where
    there is no rule, or it returns ``None``: kernels ``N(0, 2 / fan_in)``
    with the fan-in every axis but the last; biases and other vectors
    ``N(0, 0.01^2)``; NoisyNet ``*_sigma`` leaves the constant
    ``0.4 / sqrt(fan_in)`` of Fortunato et al. (fan-in read from the
    sibling ``w_sigma``).  A leaf's key is ``fold_in(key, i)`` by its place
    in flattening order whatever rule draws it."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shape_tree)
    shapes = [tuple(leaf.shape) for _, leaf in flat]
    names = [str(getattr(path[-1], "key", path[-1])) for path, _ in flat]
    parents = [tuple(str(getattr(k, "key", k)) for k in path[:-1])
               for path, _ in flat]
    fan_of_parent = {par: shp[0] for par, name, shp
                     in zip(parents, names, shapes) if name == "w_sigma"}
    rules = [init_rule(par + (name,), shape) if init_rule else None
             for par, name, shape in zip(parents, names, shapes)]
    for rule, par, name in zip(rules, parents, names):
        if rule is not None and rule[0] not in ("normal", "const"):
            raise ValueError(f"init_rule gave {rule!r} for "
                             f"{'/'.join(par + (name,))}")

    @jax.jit
    def draw(lo, hi):
        # the seed is an argument, not a constant of the program: one
        # program for every seed, found in the compile cache from the
        # second run on
        key = jax.random.fold_in(jax.random.key(lo), hi)
        out = []
        for i, (shape, name, par) in enumerate(zip(shapes, names, parents)):
            k = jax.random.fold_in(key, i)
            if rules[i] is not None:
                kind, value = rules[i]
                out.append(jnp.full(shape, value, jnp.float32)
                           if kind == "const" else
                           jax.random.normal(k, shape, jnp.float32) * value)
            elif name.endswith("_sigma"):
                fan = fan_of_parent.get(par, shape[0])
                out.append(jnp.full(shape, 0.4 / math.sqrt(fan),
                                    jnp.float32))
            elif len(shape) >= 2:
                fan = math.prod(shape[:-1])
                out.append(jax.random.normal(k, shape, jnp.float32)
                           * math.sqrt(2.0 / fan))
            else:
                out.append(jax.random.normal(k, shape, jnp.float32) * 0.01)
        return out

    lo, hi = _seed_words(seed)
    return jax.tree_util.tree_unflatten(
        treedef, draw(jnp.uint32(lo), jnp.uint32(hi)))


def make_chunks(seed: int, *, n_chunks: int, k: int, kf: int,
                frame_dim: int, stack: int, n_steps: int, gamma: float,
                action_count: int):
    """``n_chunks`` self-contained ingest chunks in the shape the actors
    ship (``K`` transitions over ``Kf`` single frames, chunk-relative
    refs), every row different: random frames, actions, rewards,
    priorities, a tenth of the rows terminal.

    Returns ``(messages, rows)``: the messages go to the program, ``rows``
    is the same data flat by transition index for the reference."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    if k + n_steps + stack - 1 > kf:
        raise ValueError(f"chunk of {k} transitions needs "
                         f"{k + n_steps + stack - 1} frames > Kf={kf}")
    t = np.arange(k)[:, None]
    obs_ref = (t + np.arange(stack)[None, :]).astype(np.int32)
    next_ref = obs_ref + np.int32(n_steps)
    messages, rows = [], {key: [] for key in
                          ("frames", "obs_ids", "next_ids", "action",
                           "reward", "discount", "priority")}
    for c in range(n_chunks):
        frames = rng.integers(0, 256, (kf, frame_dim), dtype=np.uint8)
        action = rng.integers(0, action_count, k).astype(np.int32)
        reward = rng.normal(0.0, 0.5, k).astype(np.float32)
        discount = np.where(rng.random(k) < 0.1, 0.0,
                            gamma ** n_steps).astype(np.float32)
        prios = rng.uniform(0.5, 2.0, k).astype(np.float32)
        payload = dict(frames=frames, n_frames=np.int32(kf),
                       n_trans=np.int32(k), action=action, reward=reward,
                       discount=discount, obs_ref=obs_ref,
                       next_ref=next_ref)
        messages.append(dict(payload=payload, priorities=prios, n_trans=k))
        rows["frames"].append(frames)
        rows["obs_ids"].append(obs_ref + c * kf)
        rows["next_ids"].append(next_ref + c * kf)
        rows["action"].append(action)
        rows["reward"].append(reward)
        rows["discount"].append(discount)
        rows["priority"].append(prios)
    return messages, {key: np.concatenate(val) for key, val in rows.items()}
