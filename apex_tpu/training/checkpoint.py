"""Whole-state checkpointing + eval-from-checkpoint (reference C16/A4).

The reference persists weights only — ``torch.save(model.state_dict())``
every ``save_interval`` (``origin_repo/learner.py:166-168``, ``DQN.py:112-114``)
— so a resumed run restarts the optimizer, replay, and RNG from scratch.
Here the learner state is ONE pytree by construction
(:mod:`apex_tpu.training.state`), so a checkpoint is the full bundle:

    train_state (params + target + optimizer + step) as one tree
    replay_state (HBM ring, sum/min trees, cursors) — optional, large
    RNG key, host counters (frames ingested, param version)
    config + model spec as JSON metadata

which makes kill/restore resume *bit-exact* on the learner side, and lets
``evaluate_checkpoint`` rebuild the policy with no trainer object at all
(the ``enjoy.py:29-48`` path).

Format: one msgpack file (flax.serialization) with the state-dict tree plus
a JSON metadata string; writes are atomic (tmp + rename) and pruned to the
newest ``keep`` files, so a crash mid-save can never corrupt the newest
restorable checkpoint.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass
from typing import Any

import jax
import numpy as np
from flax import serialization


def _to_host_state_dict(bundle: Any) -> dict:
    return jax.tree.map(np.asarray,
                        serialization.to_state_dict(jax.device_get(bundle)))


def save_bundle(path: str, bundle: Any, meta: dict | None = None) -> str:
    """Atomically serialize ``bundle`` (any pytree of arrays/scalars) plus
    JSON-able ``meta`` to ``path``."""
    payload = {
        "state": _to_host_state_dict(bundle),
        "meta": json.dumps(meta or {}),
    }
    blob = serialization.msgpack_serialize(payload)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)
    return path


def load_raw(path: str) -> tuple[dict, dict]:
    """Read a checkpoint as (raw nested state dict, metadata dict) — no
    target structure needed (the ``enjoy`` path)."""
    with open(path, "rb") as f:
        payload = serialization.msgpack_restore(f.read())
    return payload["state"], json.loads(payload["meta"])


def restore_bundle(path: str, target: Any) -> tuple[Any, dict]:
    """Impose the saved state onto ``target`` (a freshly-constructed bundle
    with matching structure); returns ``(restored_bundle, meta)``."""
    raw, meta = load_raw(path)
    return serialization.from_state_dict(target, raw), meta


@dataclass
class Checkpointer:
    """Directory of ``ckpt_<step>.msgpack`` files, newest ``keep`` retained."""

    directory: str
    keep: int = 3

    def __post_init__(self):
        os.makedirs(self.directory, exist_ok=True)

    def _step_of(self, name: str) -> int:
        return int(name[len("ckpt_"):-len(".msgpack")])

    def _all(self) -> list[str]:
        names = [n for n in os.listdir(self.directory)
                 if n.startswith("ckpt_") and n.endswith(".msgpack")]
        return sorted(names, key=self._step_of)

    def save(self, step: int, bundle: Any, meta: dict | None = None) -> str:
        path = os.path.join(self.directory, f"ckpt_{step}.msgpack")
        save_bundle(path, bundle, meta)
        for stale in self._all()[:-self.keep]:
            os.remove(os.path.join(self.directory, stale))
        return path

    def latest_path(self) -> str | None:
        names = self._all()
        return os.path.join(self.directory, names[-1]) if names else None

    def restore_latest(self, target: Any) -> tuple[Any, dict, int] | None:
        """``(bundle, meta, step)`` from the newest checkpoint, or None."""
        path = self.latest_path()
        if path is None:
            return None
        bundle, meta = restore_bundle(path, target)
        step = self._step_of(os.path.basename(path))
        return bundle, meta, step


class CheckpointableTrainer:
    """Shared save/restore plumbing for every trainer class.

    A trainer mixes this in and provides: ``cfg``, ``model_spec``,
    ``train_state``, ``replay_state``, ``key``, ``checkpointer``
    (``Checkpointer | None``), ``steps_rate``, and ``_counters()`` /
    ``_apply_counters(meta)`` for its host-side progress counters — one
    checkpoint format, one implementation, no per-trainer drift.
    """

    def _counters(self) -> dict:
        raise NotImplementedError

    def _apply_counters(self, meta: dict) -> None:
        raise NotImplementedError

    def _bundle(self) -> dict:
        return dict(train_state=self.train_state,
                    replay_state=self.replay_state,
                    key=jax.random.key_data(self.key))

    def _meta(self) -> dict:
        spec = dict(self.model_spec)
        spec["compute_dtype"] = str(np.dtype(spec["compute_dtype"]))
        return dict(config=config_to_meta(self.cfg), model_spec=spec,
                    **self._counters())

    def save_checkpoint(self) -> str:
        if self.checkpointer is None:
            raise ValueError("no checkpoint directory configured "
                             "(pass checkpoint_dir)")
        return self.checkpointer.save(self.steps_rate.total, self._bundle(),
                                      self._meta())

    def restore(self, path: str | None = None):
        """Restore the full learner bundle (params, target, optimizer,
        replay contents, RNG) + host counters; the learner side of a resumed
        run continues bit-exactly."""
        if path is None:
            if self.checkpointer is None:
                raise ValueError("no checkpoint directory configured "
                                 "(pass checkpoint_dir)")
            path = self.checkpointer.latest_path()
            if path is None:
                raise FileNotFoundError(
                    f"no checkpoint found in "
                    f"{self.checkpointer.directory!r}")
        bundle, meta = restore_bundle(path, self._bundle())
        self.train_state = bundle["train_state"]
        self.replay_state = bundle["replay_state"]
        self.key = jax.random.wrap_key_data(bundle["key"])
        self._apply_counters(meta)
        return self


# -- config/meta round-tripping -------------------------------------------

def config_to_meta(cfg) -> dict:
    """ApexConfig -> JSON-able nested dict."""
    return dataclasses.asdict(cfg)


def config_from_meta(meta_cfg: dict):
    """Rebuild an ApexConfig from :func:`config_to_meta` output."""
    from apex_tpu.config import (ActorConfig, ApexConfig, AQLConfig,
                                 CommsConfig, EnvConfig, LearnerConfig,
                                 R2D2Config, ReplayConfig)

    def build(cls, d):
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in d.items() if k in fields}
        return cls(**kw)

    return ApexConfig(
        env=build(EnvConfig, meta_cfg["env"]),
        replay=build(ReplayConfig, meta_cfg["replay"]),
        learner=build(LearnerConfig, meta_cfg["learner"]),
        actor=build(ActorConfig, meta_cfg["actor"]),
        aql=build(AQLConfig, meta_cfg["aql"]),
        # older checkpoints predate the r2d2 section: default it
        r2d2=build(R2D2Config, meta_cfg.get("r2d2", {})),
        comms=build(CommsConfig, meta_cfg["comms"]),
    )


def run_policy_episodes(env, step_fn, key, episodes: int, epsilon: float,
                        max_steps: int, seed_base: int,
                        reset_hook=None, render_hook=None) -> list[float]:
    """The one greedy-eval episode loop (``eval.py:49-87`` semantics)
    shared by trainer ``evaluate`` methods and
    :func:`evaluate_checkpoint` — env reset seeding, key splitting, step
    accounting, and render flushing live here exactly once.

    ``step_fn(obs_batch, epsilon, key) -> action`` hides the family
    (params binding, recurrent carry); ``reset_hook()`` runs per episode
    (recurrent policies reset their carry)."""
    import jax
    import jax.numpy as jnp

    rewards = []
    for ep in range(episodes):
        obs, _ = env.reset(seed=seed_base + ep)
        if reset_hook is not None:
            reset_hook()
        total, done, steps = 0.0, False, 0
        while not done and steps < max_steps:
            key, k = jax.random.split(key)
            a = step_fn(np.asarray(obs)[None], jnp.float32(epsilon), k)
            obs, r, term, trunc, _ = env.step(a)
            if render_hook is not None:
                render_hook(obs)
            total += float(r)
            done = term or trunc
            steps += 1
        rewards.append(total)
        flush = getattr(render_hook, "flush_episode", None)
        if flush is not None:      # save-mode hooks write one file/episode
            flush()
    return rewards


# -- eval-from-checkpoint (the reference's `enjoy` role) -------------------

def evaluate_checkpoint(path: str, episodes: int = 10, epsilon: float = 0.0,
                        max_steps: int = 10_000, seed: int = 7,
                        render_hook=None) -> float:
    """Rebuild env + model purely from checkpoint metadata, load params, and
    run unclipped epsilon-greedy episodes (``enjoy.py:29-48``;
    ``DQN.py:124-149``).  No trainer object is constructed.

    ``render_hook(obs) -> None``, if given, is called every step with the
    raw observation (the reference renders to screen; headless hosts log or
    record instead).
    """
    import jax.numpy as jnp

    from apex_tpu.envs.registry import make_eval_env

    raw, meta = load_raw(path)
    cfg = config_from_meta(meta["config"])
    spec = dict(meta["model_spec"])
    spec["compute_dtype"] = jnp.dtype(spec["compute_dtype"])
    params = raw["train_state"]["params"]

    # family dispatch by spec shape: AQL specs carry action_dim (Box
    # actions), recurrent specs carry lstm_features, DQN specs carry
    # num_actions only
    reset_policy = None
    if "action_dim" in spec:
        from apex_tpu.models.aql import AQLNetwork, make_aql_policy_fn
        model = AQLNetwork(**spec, noisy_deterministic=True)
        aql_policy = jax.jit(make_aql_policy_fn(model))

        def policy(params, obs, eps, key):
            a, _, _, _ = aql_policy(params, obs, eps, key)
            return np.asarray(a[0])
    elif "lstm_features" in spec:
        from apex_tpu.models.recurrent import (RecurrentDuelingDQN,
                                               make_recurrent_policy_fn)
        model = RecurrentDuelingDQN(**spec)
        rec_policy = jax.jit(make_recurrent_policy_fn(model))
        carry_box = [model.initial_state(1)]

        def policy(params, obs, eps, key):
            a, _, carry_box[0] = rec_policy(params, obs, carry_box[0],
                                            eps, key)
            return int(a[0])

        def reset_policy():       # fresh carry each episode
            carry_box[0] = model.initial_state(1)
    else:
        from apex_tpu.models import make_q_network
        from apex_tpu.models.dueling import make_policy_fn
        model = make_q_network(spec)
        dqn_policy = jax.jit(make_policy_fn(model))

        def policy(params, obs, eps, key):
            a, _ = dqn_policy(params, obs, eps, key)
            return int(a[0])

    env = make_eval_env(cfg.env.env_id, cfg.env, seed=seed)
    rewards = run_policy_episodes(
        env, lambda obs, eps, k: policy(params, obs, eps, k),
        jax.random.key(seed), episodes, epsilon, max_steps,
        seed_base=seed, reset_hook=reset_policy, render_hook=render_hook)
    env.close()
    return float(np.mean(rewards))
