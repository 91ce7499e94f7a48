"""Env construction: the reference's two composers re-expressed.

``make_atari(env_id)`` (``origin_repo/wrapper.py:255-262``) and
``wrap_atari_dqn(env, args)`` (``wrapper.py:316-329``) become one
``make_env(env_id, cfg)`` that dispatches on the id:

* ``Apex*`` ids -> numpy-native envs (no emulator needed; see
  :mod:`apex_tpu.envs.toy`).  Pixel envs still get FrameStack so the
  observation contract matches Atari exactly.
* ``*NoFrameskip*`` ids -> the full DeepMind wrapper stack; requires
  ``ale_py``, which this image does not ship — gated with a clear error.
"""

from __future__ import annotations

from typing import Any

import gymnasium as gym

from apex_tpu.config import EnvConfig
from apex_tpu.envs import toy, wrappers


def _ale_available() -> bool:
    try:
        import ale_py  # noqa: F401
        return True
    except ImportError:
        return False


def make_atari(env_id: str, skip: int = 4,
               max_episode_steps: int | None = None) -> gym.Env:
    """Base Atari env + Noop + MaxAndSkip (reference: wrapper.py:255-262)."""
    if not _ale_available():
        raise ImportError(
            "ale_py is not installed; Atari envs are unavailable in this "
            "image. Use 'ApexCartPole-v0' or 'ApexCatch-v0' instead.")
    import ale_py
    gym.register_envs(ale_py)
    env = gym.make(env_id)
    env = wrappers.NoopResetEnv(env, noop_max=30)
    env = wrappers.MaxAndSkipEnv(env, skip=skip)
    if max_episode_steps is not None:
        env = wrappers.TimeLimit(env, max_episode_steps)
    return env


def wrap_atari_dqn(env: gym.Env, cfg: EnvConfig,
                   stack_frames: bool = True) -> gym.Env:
    """DeepMind preprocessing stack (reference: wrapper.py:316-329)."""
    if cfg.episodic_life:
        env = wrappers.EpisodicLifeEnv(env)
    if "FIRE" in env.unwrapped.get_action_meanings():
        env = wrappers.FireResetEnv(env)
    env = wrappers.WarpFrame(env)
    if cfg.clip_rewards:
        env = wrappers.ClipRewardEnv(env)
    if stack_frames and cfg.frame_stack > 1:
        env = wrappers.FrameStack(env, cfg.frame_stack)
    return env


def make_env(env_id: str | None = None, cfg: EnvConfig | None = None,
             seed: int | None = None,
             max_episode_steps: int | None = None,
             stack_frames: bool = True) -> gym.Env:
    """One-stop constructor used by every role (actor/evaluator/driver).

    ``stack_frames=False`` omits the FrameStack wrapper: actors feeding the
    frame-pool replay consume SINGLE frames (stacking happens on device at
    sample time; the acting stack lives in FrameChunkBuilder).
    """
    cfg = cfg or EnvConfig()
    env_id = env_id or cfg.env_id

    if env_id.startswith("ApexCartPole"):
        env = (toy.CartPoleEnv(max_episode_steps=max_episode_steps)
               if max_episode_steps is not None else toy.CartPoleEnv())
        if "PO" in env_id:      # ApexCartPolePO-v0: velocities hidden
            env = toy.VelocityMask(env)
    elif env_id.startswith("ApexContinuousNav"):
        env = (toy.ContinuousNavEnv(max_episode_steps=max_episode_steps)
               if max_episode_steps is not None else toy.ContinuousNavEnv())
    elif env_id.startswith("ApexTokens"):
        # token contexts as byte frames (toy.TokensEnv): 1-D uint8, so no
        # FrameStack and frame stack 1; sized by the config (the CLI sets
        # both numbers from the --torso preset that reads such frames)
        env = toy.TokensEnv(cfg.token_context, cfg.token_vocab)
        if max_episode_steps is not None:
            env = wrappers.TimeLimit(env, max_episode_steps)
    elif env_id.startswith(("ApexCatch", "ApexRally")):
        # Pixel toy envs.  Catch — Small: 7x7 grid rendered to 42x42
        # (smallest input the Nature conv geometry accepts), 3 balls (a
        # 6-step credit horizon); Medium: 11x11 at 44x44, 4 balls (a
        # 10-step horizon, the harder pixel certificate standing in for
        # ALE, absent from this image).  Rally — the
        # Pong-shaped ADVERSARIAL task (scripted opponent, edge-shot
        # mechanic — toy.RallyEnv); Small: 14-cell court at 42x42, 2
        # points (the CI-scale certificate); full: 21 at 84x84, 3 points
        # (the flagship-geometry stand-in for ALE Pong).
        if env_id.startswith("ApexCatch"):
            if "Small" in env_id:
                env = toy.CatchEnv(grid=7, pixels=42, balls=3)
            elif "Medium" in env_id:
                env = toy.CatchEnv(grid=11, pixels=44, balls=4)
            else:
                env = toy.CatchEnv()
        else:
            # Small: wide agent paddle + 0.45-speed opponent — the two
            # levers calibration showed matter for a CI-budget DQN
            # (reward density from reliable catches; a grid-10 big-ball
            # variant measured WORSE).  Ladder: random -0.68 / tracking
            # +1.65 / edge +2.0.  The full variant keeps the symmetric
            # speed-1 duel (ladder measured on the same 14-cell 2-point
            # court WITHOUT the Small handicaps: random -1.45 / tracking
            # +0.57 / edge +2.0 — the 21-cell 3-point full env scales
            # these, it has not been separately calibrated).
            env = (toy.RallyEnv(grid=14, pixels=42, points=2,
                                agent_half=2, opp_speed=0.45)
                   if "Small" in env_id else toy.RallyEnv())
        # ONE copy of the pixel wrapper tail for every toy pixel env
        if max_episode_steps is not None:
            env = wrappers.TimeLimit(env, max_episode_steps)
        if stack_frames and cfg.frame_stack > 1:
            env = wrappers.FrameStack(env, cfg.frame_stack)
    else:
        env = make_atari(env_id, skip=cfg.frame_skip,
                         max_episode_steps=max_episode_steps)
        env = wrap_atari_dqn(env, cfg, stack_frames=stack_frames)

    if seed is not None:
        env.reset(seed=seed)
        env.action_space.seed(seed)
    return env


def jittable_env(env_id: str) -> bool:
    """Capability flag: True when :func:`make_jax_env` can build a pure-JAX
    port of ``env_id`` for on-device Anakin rollouts
    (:mod:`apex_tpu.training.anakin`).  Catch/Rally are integer/float32
    grid worlds and Tokens an integer context that run inside the
    accelerator; everything else (ALE, CartPole-family float dynamics,
    continuous nav) stays on the host pipeline."""
    return env_id.startswith(("ApexCatch", "ApexRally", "ApexTokens"))


def make_jax_env(env_id: str | None = None, cfg: EnvConfig | None = None):
    """Jittable functional twin of :func:`make_env` for the on-device
    rollout engine — same env-id -> variant-geometry table as the numpy
    dispatch above, returning an :class:`apex_tpu.envs.jax_envs.JaxEnv`
    (pure reset/step over array states, auto-reset inside step).  Raises
    ``ValueError`` naming the env id for non-jittable envs — the
    ``--rollout ondevice`` / ``--role loadgen`` guard."""
    from apex_tpu.envs import jax_envs

    cfg = cfg or EnvConfig()
    env_id = env_id or cfg.env_id
    if not jittable_env(env_id):
        raise ValueError(
            f"env {env_id!r} has no jittable port — on-device rollouts "
            f"(--rollout ondevice / --role loadgen) serve the "
            f"ApexCatch*/ApexRally*/ApexTokens* families only; use the "
            f"host actor pipeline for this env")
    if env_id.startswith("ApexTokens"):
        return jax_envs.make_tokens(cfg.token_context, cfg.token_vocab,
                                    env_id=env_id)
    if env_id.startswith("ApexCatch"):
        if "Small" in env_id:
            return jax_envs.make_catch(grid=7, pixels=42, balls=3,
                                       env_id=env_id)
        if "Medium" in env_id:
            return jax_envs.make_catch(grid=11, pixels=44, balls=4,
                                       env_id=env_id)
        return jax_envs.make_catch(env_id=env_id)
    if "Small" in env_id:
        return jax_envs.make_rally(grid=14, pixels=42, points=2,
                                   agent_half=2, opp_speed=0.45,
                                   env_id=env_id)
    return jax_envs.make_rally(env_id=env_id)


def unstacked_env_spec(env: gym.Env,
                       cfg: EnvConfig) -> tuple[tuple[int, ...], Any, int]:
    """(frame_shape, frame_dtype, frame_stack) for an env built with
    ``stack_frames=False`` — the FrameChunkBuilder/FramePoolReplay spec.
    Vector (1-D) observations use frame_stack=1."""
    space = env.observation_space
    shape = tuple(space.shape)
    stack = cfg.frame_stack if len(shape) == 3 else 1
    return shape, space.dtype, stack


def make_eval_env(env_id: str | None = None, cfg: EnvConfig | None = None,
                  seed: int | None = None) -> gym.Env:
    """Evaluation env: UNCLIPPED rewards, full episodes (no EpisodicLife) —
    the reference evaluator measures true game score this way
    (``origin_repo/eval.py:52``)."""
    import dataclasses
    cfg = cfg or EnvConfig()
    eval_cfg = dataclasses.replace(cfg, clip_rewards=False,
                                   episodic_life=False)
    return make_env(env_id, eval_cfg, seed=seed)


def obs_spec(env: gym.Env) -> tuple[tuple[int, ...], Any]:
    space = env.observation_space
    return tuple(space.shape), space.dtype


def num_actions(env: gym.Env) -> int:
    return int(env.action_space.n)
