"""Q-networks, and the one factory every DQN-family call site builds them
through.

A ``model_spec`` is the plain dict that travels to worker processes and
into checkpoint metadata.  Without a ``torso`` key it is today's dueling
network's keyword arguments, unchanged; with one it names a preset of a
token-torso family (:mod:`apex_tpu.models.glm4_moe_lite`,
:mod:`apex_tpu.models.nemotron_h`, :mod:`apex_tpu.models.qwen3_next`,
:mod:`apex_tpu.models.lfm2_moe`), found by the preset's name.  The four
families share ONE expert layer
(:class:`apex_tpu.models.glm4_moe_lite.MoE`); what a model's differs by
is a field of it, among them the **scoring rule** (how the router's
outputs become picks and weights: ``sigmoid_bias``, sigmoid scores ranked
with a selection-only learned bias, or ``softmax`` over all outputs, no
bias), the **shared gate** (whether the shared expert's output is
multiplied by ``sigmoid(h w_g)``, one learned scalar a token) and
``n_shared_experts`` (LFM2's layer has none).
"""

from __future__ import annotations

import sys

import jax

DEFAULT_TORSO = "dueling"


def _token_torsos() -> dict[str, tuple[type, dict]]:
    """``{preset name: (module class, preset)}`` over the token-torso
    families: a family is a module with ``PRESETS`` and the class that
    reads them."""
    from apex_tpu.models import (glm4_moe_lite, lfm2_moe, nemotron_h,
                                 qwen3_next)
    return {name: (cls, preset) for module, cls in (
        (glm4_moe_lite, glm4_moe_lite.Glm4MoeLiteQ),
        (nemotron_h, nemotron_h.NemotronHQ),
        (qwen3_next, qwen3_next.Qwen3NextQ),
        (lfm2_moe, lfm2_moe.Lfm2MoeQ))
        for name, preset in module.PRESETS.items()}


def torso_names() -> list[str]:
    return [DEFAULT_TORSO, *_token_torsos()]


def token_preset(torso: str) -> dict:
    """A token torso's preset: ``context`` ids a frame and ``vocab_held``
    ids are what an env under it is sized to."""
    return _token_torsos()[torso][1]


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
    return _token_torsos()[torso][0](preset=torso, **spec)


def note_torso(model, site: str) -> None:
    """What a token torso says of itself where a ``site`` (``trainer``,
    ``rollout``) builds it, each as one instant in the trace ring and one
    start-up line on stderr: ``attention_path``, which implementation its
    attention takes in this process's programs
    (:func:`apex_tpu.ops.attention.attention_path`: decided by platform
    and widths when a program is lowered, so once a program's builder is
    enough), ``grouped_path``, the widths its expert layers hand the
    grouped kernel (:func:`apex_tpu.ops.grouped.grouped_path`: decided the
    same way), and ``torso_layout``, what this chip holds of
    each layer.  A model says nothing of what it does not have."""
    from apex_tpu.obs.trace import get_ring
    platform = jax.default_backend()
    for name, args in (("attention_path", (platform,)),
                       ("grouped_path", (platform,)),
                       ("torso_layout", ())):
        said = getattr(model, name, None)
        if said is None:
            continue
        said = {"site": site, "torso": model.preset, **said(*args)}
        get_ring().instant(name, None, said)
        print(f"torso: {name} "
              + " ".join(f"{k}={v}" for k, v in said.items()),
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
