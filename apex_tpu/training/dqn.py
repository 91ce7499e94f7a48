"""Single-process DQN driver — the minimum end-to-end slice.

Capability parity with ``DQN.py`` (reference C10): inline act -> step -> add ->
sample -> loss -> update loop with exponential epsilon decay (``DQN.py:41``),
linear beta anneal (``DQN.py:40``), periodic target sync (``DQN.py:108-110``),
checkpointing, and an evaluation mode replaying a checkpoint
(``DQN.py:124-149``).

This driver defines the numerical contract every distributed variant must
match (SURVEY.md §3.3).  TPU shape: the env + epsilon-greedy actor run on the
host; transitions accumulate through the n-step window and are ingested into
the HBM replay in fixed-size chunks (fixed shapes = no retrace); the learner
update is the fused XLA step from :mod:`apex_tpu.training.learner`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.config import ApexConfig
from apex_tpu.envs.registry import make_env, make_eval_env, num_actions
from apex_tpu.models import make_q_network, note_torso, q_model_spec
from apex_tpu.models.dueling import make_policy_fn
from apex_tpu.replay.nstep import NStepAccumulator
from apex_tpu.training import learner as learner_lib
from apex_tpu.training.checkpoint import (CheckpointableTrainer,
                                          Checkpointer)
from apex_tpu.utils.metrics import MetricLogger, RateCounter
from apex_tpu.utils.seeding import set_global_seeds


@dataclass
class EpsilonSchedule:
    """eps_final + (eps_start - eps_final) * exp(-frame / decay)  (DQN.py:41)."""

    start: float = 1.0
    final: float = 0.01
    decay: float = 30_000.0

    def __call__(self, frame: int) -> float:
        return self.final + (self.start - self.final) * math.exp(
            -frame / self.decay)


@dataclass
class BetaSchedule:
    """Linear anneal of the IS exponent toward 1 (DQN.py:40)."""

    start: float = 0.4
    frames: int = 100_000

    def __call__(self, frame: int) -> float:
        return min(1.0, self.start + (1.0 - self.start) * frame / self.frames)


class DQNTrainer(CheckpointableTrainer):
    """train_DQN equivalent (``DQN.py:15-75``)."""

    def __init__(self, config: ApexConfig | None = None,
                 logdir: str | None = None, verbose: bool = False,
                 train_every: int = 1, checkpoint_dir: str | None = None):
        self.cfg = config or ApexConfig()
        self.key = set_global_seeds(self.cfg.env.seed)
        self.env = make_env(self.cfg.env.env_id, self.cfg.env,
                            seed=self.cfg.env.seed,
                            max_episode_steps=self.cfg.actor.max_episode_length)
        obs_shape = self.env.observation_space.shape
        self.model_spec = q_model_spec(
            self.cfg.learner.torso,
            num_actions=num_actions(self.env),
            obs_is_image=len(obs_shape) == 3,
            compute_dtype=jnp.dtype(self.cfg.learner.compute_dtype),
            scale_uint8=self.env.observation_space.dtype == np.uint8)
        self.model = make_q_network(self.model_spec)
        note_torso(self.model, "trainer")

        lc = self.cfg.learner
        example_obs = jnp.zeros((1,) + obs_shape,
                                self.env.observation_space.dtype)
        self.key, init_key = jax.random.split(self.key)
        self.core, self.train_state, self.replay_state = \
            learner_lib.build_learner(
                self.model, self.cfg.replay.capacity, example_obs, init_key,
                alpha=self.cfg.replay.alpha, batch_size=lc.batch_size,
                lr=lc.lr, max_grad_norm=lc.max_grad_norm,
                lr_decay_steps=lc.lr_decay_steps,
                lr_decay_rate=lc.lr_decay_rate,
                rmsprop_decay=lc.rmsprop_decay, rmsprop_eps=lc.rmsprop_eps,
                rmsprop_centered=lc.rmsprop_centered,
                replay_eps=self.cfg.replay.eps,
                target_update_interval=lc.target_update_interval,
                hbm_budget_gb=self.cfg.replay.hbm_budget_gb)
        self._train_step = self.core.jit_train_step()
        self._ingest = self.core.jit_ingest()
        self._policy = jax.jit(make_policy_fn(self.model))

        self.accumulator = NStepAccumulator(lc.n_steps, lc.gamma)
        self.ingest_chunk = lc.ingest_chunk
        self.train_every = train_every
        self.epsilon = EpsilonSchedule()
        self.beta = BetaSchedule(start=self.cfg.replay.beta)
        self.log = MetricLogger("learner", logdir, verbose=verbose)
        self.frames_rate = RateCounter()
        self.steps_rate = RateCounter()
        self.ingested = 0
        self._pending: list[tuple[dict, np.ndarray]] = []
        self._pending_count = 0
        self.checkpointer = (Checkpointer(checkpoint_dir)
                             if checkpoint_dir else None)

    # -- checkpointing (A4): format/IO in CheckpointableTrainer ------------

    def _counters(self) -> dict:
        return dict(ingested=self.ingested, frames=self.frames_rate.total,
                    steps=self.steps_rate.total)

    def _apply_counters(self, meta: dict) -> None:
        self.ingested = meta["ingested"]
        self.frames_rate.total = meta["frames"]
        self.steps_rate.total = meta["steps"]

    # -- data plane --------------------------------------------------------

    def _flush_accumulator(self) -> None:
        if len(self.accumulator) == 0:
            return
        batch, prios = self.accumulator.make_batch()
        self._pending.append((batch, prios))
        self._pending_count += len(prios)
        while self._pending_count >= self.ingest_chunk:
            self._ingest_chunk()

    def _ingest_chunk(self) -> None:
        """Ingest exactly ``ingest_chunk`` transitions (fixed shape, no retrace)."""
        merged = {k: np.concatenate([b[k] for b, _ in self._pending])
                  for k in self._pending[0][0]}
        prios = np.concatenate([p for _, p in self._pending])
        take = self.ingest_chunk
        chunk = {k: v[:take] for k, v in merged.items()}
        rest = {k: v[take:] for k, v in merged.items()}
        self.replay_state = self._ingest(self.replay_state, chunk,
                                         jnp.asarray(prios[:take]))
        self.ingested += take
        self._pending = ([(rest, prios[take:])]
                         if len(prios) > take else [])
        self._pending_count = len(prios) - take

    # -- main loop ---------------------------------------------------------

    def train(self, total_frames: int, log_every: int = 1000):
        """Run ``total_frames`` MORE env frames.  On a restored trainer the
        frame counter (and with it the epsilon/beta schedules) continues
        from the checkpoint instead of rewinding to frame 1."""
        cfg = self.cfg
        obs, _ = self.env.reset(seed=cfg.env.seed)
        episode_reward, episode_len, episode_idx = 0.0, 0, 0
        start = self.frames_rate.total

        for frame in range(start + 1, start + total_frames + 1):
            eps = self.epsilon(frame)
            self.key, act_key = jax.random.split(self.key)
            obs_np = np.asarray(obs)
            actions, q = self._policy(self.train_state.params,
                                      obs_np[None], jnp.float32(eps), act_key)
            action = int(actions[0])

            next_obs, reward, terminated, truncated, _ = self.env.step(action)
            done = terminated or truncated
            # q materializes at its use site, after the env step (J008)
            q_np = np.asarray(q[0])
            self.accumulator.add(obs_np, action, float(reward), q_np,
                                 terminated=bool(terminated),
                                 truncated=bool(truncated),
                                 final_obs=(np.asarray(next_obs)
                                            if truncated else None))
            obs = next_obs
            episode_reward += float(reward)
            episode_len += 1
            self.frames_rate.tick()

            if done:
                self._flush_accumulator()
                obs, _ = self.env.reset()
                self.log.scalars({"episode_reward": episode_reward,
                                  "episode_length": episode_len}, episode_idx)
                episode_reward, episode_len = 0.0, episode_len * 0
                episode_idx += 1
            elif len(self.accumulator) >= cfg.actor.send_interval:
                self._flush_accumulator()

            warm = self.ingested >= cfg.replay.warmup
            if warm and frame % self.train_every == 0:
                self.key, step_key = jax.random.split(self.key)
                self.train_state, self.replay_state, metrics = \
                    self._train_step(self.train_state, self.replay_state,
                                     step_key, jnp.float32(self.beta(frame)))
                self.steps_rate.tick()
                if (self.checkpointer is not None and self.steps_rate.total
                        % cfg.learner.save_interval == 0):
                    self.save_checkpoint()
                # host-side counter for the log gate: reading
                # train_state.step would sync the async device step
                if self.steps_rate.total % log_every == 0:
                    self.log.scalars(
                        {k: float(v) for k, v in metrics.items()}
                        | {"bps": self.steps_rate.rate,
                           "fps": self.frames_rate.rate},
                        self.steps_rate.total)
        return self

    # -- evaluation (DQN.py:124-149 equivalent) ----------------------------

    def evaluate(self, episodes: int = 10, epsilon: float = 0.0,
                 max_steps: int = 10_000) -> float:
        """True-score evaluation on a dedicated unclipped/full-episode env
        (reference: eval.py:52 evaluates on the unclipped env)."""
        from apex_tpu.training.checkpoint import run_policy_episodes

        if not hasattr(self, "_eval_env"):
            self._eval_env = make_eval_env(self.cfg.env.env_id, self.cfg.env,
                                           seed=self.cfg.env.seed + 999)
        self.key, eval_key = jax.random.split(self.key)
        rewards = run_policy_episodes(
            self._eval_env,
            lambda obs, eps, k: int(self._policy(
                self.train_state.params, obs, eps, k)[0][0]),
            eval_key, episodes, epsilon, max_steps,
            seed_base=self.cfg.env.seed + 1000)
        return float(np.mean(rewards))
