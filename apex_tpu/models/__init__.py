"""Q-networks, and the one factory every DQN-family call site builds them
through.

A ``model_spec`` is the plain dict that travels to worker processes and
into checkpoint metadata.  Without a ``torso`` key it is today's dueling
network's keyword arguments, unchanged; with one it names a preset of
:mod:`apex_tpu.models.glm4_moe_lite`.
"""

from __future__ import annotations

import sys

import jax

DEFAULT_TORSO = "dueling"


def torso_names() -> list[str]:
    from apex_tpu.models.glm4_moe_lite import PRESETS
    return [DEFAULT_TORSO, *PRESETS]


def q_model_spec(torso: str, *, num_actions: int, obs_is_image: bool,
                 compute_dtype, scale_uint8: bool) -> dict:
    """The spec of a DQN-family Q-network over an env's observations."""
    if torso == DEFAULT_TORSO:
        return dict(num_actions=num_actions, obs_is_image=obs_is_image,
                    compute_dtype=compute_dtype, scale_uint8=scale_uint8)
    return dict(torso=torso, num_actions=num_actions,
                compute_dtype=compute_dtype)


def make_q_network(model_spec: dict):
    """The flax module of a spec: ``apply(params, obs) -> f32[B, A]``."""
    spec = dict(model_spec)
    torso = spec.pop("torso", DEFAULT_TORSO)
    if torso == DEFAULT_TORSO:
        from apex_tpu.models.dueling import DuelingDQN
        return DuelingDQN(**spec)
    from apex_tpu.models.glm4_moe_lite import Glm4MoeLiteQ
    return Glm4MoeLiteQ(preset=torso, **spec)


def note_attention_path(model, site: str) -> None:
    """Which implementation a token torso's attention takes in this
    process's programs (:func:`apex_tpu.ops.attention.attention_path`:
    decided by platform and widths when a program is lowered, so once a
    program's builder is enough): one ``attention_path`` instant in the
    trace ring and one start-up line on stderr, from the ``site`` that
    built the model (``trainer``, ``rollout``).  A model without attention
    says nothing."""
    path_of = getattr(model, "attention_path", None)
    if path_of is None:
        return
    from apex_tpu.obs.trace import get_ring
    args = {"site": site, "torso": model.preset,
            **path_of(jax.default_backend())}
    get_ring().instant("attention_path", None, args)
    print("torso: attention_path "
          + " ".join(f"{k}={v}" for k, v in args.items()),
          file=sys.stderr, flush=True)


def learner_apply_fn(model):
    """``apply_fn(params, obs)`` as the learner's loss calls it: ``q``, or
    ``(q, stats)`` for a model that counts something inside its forward
    pass and says so (``COUNTS_STATS``: the expert layers' routing)."""
    if getattr(model, "COUNTS_STATS", False):
        return lambda params, obs: model.apply(params, obs, with_stats=True)
    return model.apply


def acting_params(model, params):
    """``params`` as the policy multiplies them: every leaf the model casts
    to its compute dtype before use, cast once, so a policy fed this tree
    computes the same bits from half the bytes.  A model may keep leaves in
    float32 by name (``ACTING_KEEPS_FLOAT32``: gains, the router, rows read
    without a product)."""
    dt = model.compute_dtype
    keep = getattr(model, "ACTING_KEEPS_FLOAT32", ())

    def cast(path, x):
        name = str(getattr(path[-1], "key", path[-1]))
        return x if name in keep else x.astype(dt)

    return jax.tree_util.tree_map_with_path(cast, params)
