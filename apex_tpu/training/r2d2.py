"""Recurrent DQN (R2D2-style) driver — the reference's unfinished TODO.

The reference lists "recurrent DQN" as future work (``README.md:5``) and
ships nothing; this module implements the family end to end, TPU-first:

* **Model**: :class:`apex_tpu.models.recurrent.RecurrentDuelingDQN` —
  same Nature trunk / dueling heads as the DQN family with an LSTM
  between them, unrolled by ``lax.scan`` inside one compiled step.
* **Replay**: sequences ARE replay items.  :class:`DeviceReplay` is
  generic over item pytrees, so a prioritized SEQUENCE buffer is just
  items with ``[T, ...]`` leaves (obs/action/reward/discount/mask per
  step + the stored recurrent state) — no new storage layout, and the
  fused ingest/sample/update machinery applies unchanged.
* **Actor side**: :class:`SequenceBuilder` splits episodes into
  overlapping fixed-length sequences (R2D2's stride = unroll/2) and
  records the policy's recurrent state at each sequence start (the
  "stored state" strategy).
* **Loss**: :func:`apex_tpu.ops.losses.r2d2_loss` — burn-in prefix
  warms the state gradient-free, then n-step double-DQN over the unroll
  with per-sequence mixed max/mean priorities.

The long-context story of this framework (SURVEY.md §5.7's n-step
windows + frame stacking) extends here to genuinely recurrent sequence
replay: the memory horizon is the LSTM's, not the frame stack's.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
import optax

from apex_tpu.actors.r2d2 import (drain_grouped, pooled_sequence_message,
                                  sequence_message)
from apex_tpu.config import ApexConfig
from apex_tpu.envs.registry import make_env, make_eval_env, num_actions
from apex_tpu.models.recurrent import (RecurrentDuelingDQN,
                                       make_recurrent_policy_fn)
from apex_tpu.ops.losses import PRIORITY_ETA, make_optimizer, r2d2_loss
from apex_tpu.replay.base import check_hbm_budget
from apex_tpu.replay.device import DeviceReplay
from apex_tpu.training.apex import ConcurrentTrainer
from apex_tpu.training.checkpoint import (CheckpointableTrainer,
                                          Checkpointer)
from apex_tpu.training.dqn import BetaSchedule, EpsilonSchedule
from apex_tpu.training.learner import scan_fused_steps, td_update
from apex_tpu.training.state import TrainState
from apex_tpu.utils.metrics import MetricLogger, RateCounter
from apex_tpu.utils.seeding import set_global_seeds


class SequenceBuilder:
    """Host-side episode-to-sequence splitter (R2D2 overlapping windows).

    Per step the caller provides the observation, action, reward,
    termination flag, and the policy's recurrent state BEFORE acting (the
    carry that produced the action).  Episodes are cut into sequences of
    ``t_total = burn_in + unroll + n_steps`` steps starting every
    ``stride`` steps; short tails are zero-padded with ``mask=0`` (padded
    ``discount=0`` also truncates every n-step product crossing the
    boundary, see :func:`r2d2_loss`).  A sequence is emitted only if its
    loss region (positions ``burn_in..``) contains at least one real
    step.
    """

    def __init__(self, burn_in: int, unroll: int, n_steps: int,
                 gamma: float, stride: int | None = None,
                 pooled: bool = False):
        self.burn_in, self.unroll, self.n_steps = burn_in, unroll, n_steps
        self.t_total = burn_in + unroll + n_steps
        self.stride = stride or max(1, unroll // 2)
        if pooled and self.stride > self.t_total:
            # The pooled message packer ships each episode's union
            # coverage [min start, max end) as ONE contiguous block sized
            # for OVERLAPPING windows (<= t_total rows per sequence,
            # actors/r2d2.py:pooled_sequence_message); stride > t_total
            # leaves gaps inside that block and overflows the fixed
            # [G*T+1] frame buffer.  Raised HERE, where the pooled layout
            # is selected — a ValueError survives `python -O`, unlike the
            # bare assert that used to catch this at pack time.
            raise ValueError(
                f"pooled sequence layout requires stride <= t_total "
                f"(burn_in + unroll + n_steps = {self.t_total}), got "
                f"stride={self.stride}")
        self.gamma = gamma
        # pooled: emit frame REFERENCES for the dedup sequence frame-pool
        # layout (apex_tpu/replay/seq_pool.py) — sequences share one
        # episode frame array instead of each copying its padded window;
        # pooled_sequence_message packs the shared frames once per message
        self.pooled = pooled
        self._obs: list = []
        self._action: list = []
        self._reward: list = []
        self._discount: list = []
        self._carry: list = []
        self._q: list = []
        self._out: list[dict] = []

    @property
    def needs_carry(self) -> bool:
        """True when the NEXT ``add_step`` starts a sequence window (a
        stride boundary): only those carries are ever read back, so the
        caller can skip the device->host carry transfer everywhere else
        (two blocking syncs per frame otherwise)."""
        return len(self._obs) % self.stride == 0

    def add_step(self, obs, action: int, reward: float, terminated: bool,
                 carry_c: np.ndarray | None, carry_h: np.ndarray | None,
                 q_values: np.ndarray | None = None) -> None:
        """``carry_c``/``carry_h`` may be None except when
        :attr:`needs_carry` was True before this call.  ``q_values`` (the
        acting-time Q vector) feeds the insert-priority heuristic; omit it
        and sequences insert at priority 1."""
        if len(self._obs) % self.stride == 0 and carry_c is None:
            raise ValueError("sequence-start step needs its carry "
                             "(check builder.needs_carry before acting)")
        self._obs.append(np.asarray(obs))
        self._action.append(int(action))
        self._reward.append(float(reward))
        self._discount.append(0.0 if terminated else self.gamma)
        self._carry.append(
            None if carry_c is None
            else (np.asarray(carry_c), np.asarray(carry_h)))
        self._q.append(None if q_values is None
                       else np.asarray(q_values, np.float32))

    def end_episode(self, truncated: bool = False) -> None:
        """Cut the finished episode into sequences; clears step buffers.

        ``truncated``: the episode ended by time limit, not termination.
        Loss positions whose n-step window crosses a TRUNCATION boundary
        would bootstrap from padded all-zero observations at full weight
        ``gamma^n`` (a terminated boundary is safe: its ``discount=0``
        kills the product) — those positions get ``mask=0``, excluding
        them from the loss entirely.  The DQN family's analogue stores
        ``final_obs`` and bootstraps truncation-correctly
        (:mod:`apex_tpu.replay.nstep`); for sequences, dropping the last
        ``n_steps`` loss positions is the standard unbiased treatment.
        """
        n = len(self._obs)
        if n == 0:
            return
        mask_full = np.ones(n, np.float32)
        if truncated:
            mask_full[max(0, n - self.n_steps):] = 0.0
        td_full = self._acting_time_tds(n)
        obs = np.stack(self._obs)
        emitted: list[dict] = []
        starts: list[int] = []
        start = 0
        while start + self.burn_in < n:
            end = min(start + self.t_total, n)
            pad = self.t_total - (end - start)
            m = _pad(mask_full[start:end], pad)
            lm = m[self.burn_in:self.burn_in + self.unroll]
            if not lm.any():
                break            # loss region entirely padded/masked
            c, h = self._carry[start]
            seq = dict(
                action=_pad(np.asarray(self._action[start:end], np.int32),
                            pad),
                reward=_pad(np.asarray(self._reward[start:end], np.float32),
                            pad),
                discount=_pad(np.asarray(self._discount[start:end],
                                         np.float32), pad),
                mask=m,
                state_c=c.astype(np.float32),
                state_h=h.astype(np.float32),
            )
            if self.pooled:
                # the episode array is SHARED by every window over it —
                # the message packer ships each referenced frame once
                seq["ep_frames"], seq["start"], seq["end"] = obs, start, end
            else:
                seq["obs"] = _pad(obs[start:end], pad)
            if td_full is not None:
                td = _pad(td_full[start:end], pad)[
                    self.burn_in:self.burn_in + self.unroll] * lm
                nv = max(lm.sum(), 1.0)
                seq["priority"] = np.float32(
                    PRIORITY_ETA * td.max()
                    + (1.0 - PRIORITY_ETA) * td.sum() / nv + 1e-6)
            else:
                seq["priority"] = np.float32(1.0)
            emitted.append(seq)
            starts.append(start)
            start += self.stride
        # n_new: NEW env transitions this sequence contributes vs its
        # overlapping predecessors — step t counts exactly once across the
        # episode, so transition-denominated gates (warmup, replay ratio)
        # stay honest despite the stride overlap
        for i, (seq, s) in enumerate(zip(emitted, starts)):
            nxt = starts[i + 1] if i + 1 < len(starts) else n
            seq["n_new"] = int(min(nxt, n) - s)
        self._out.extend(emitted)
        self._obs, self._action, self._reward = [], [], []
        self._discount, self._carry, self._q = [], [], []

    def _acting_time_tds(self, n: int) -> np.ndarray | None:
        """Per-step 1-step |TD| from the acting-time Q vectors — the
        sequence analogue of the DQN actors' priorities-without-rerunning
        (``memory.py:451-464``): ``|r + disc * max q' - q[a]|``, bootstrap
        0 past the episode end.  The learner's unrolled n-step write-back
        replaces these after the first sample; they only order the replay
        until then.  None when any step lacked its Q vector."""
        if any(q is None for q in self._q):
            return None
        maxq = np.asarray([float(q.max()) for q in self._q] + [0.0],
                          np.float32)
        td = np.empty(n, np.float32)
        for t in range(n):
            td[t] = abs(self._reward[t]
                        + self._discount[t] * maxq[t + 1]
                        - float(self._q[t][self._action[t]]))
        return td

    def drain(self) -> list[dict]:
        out, self._out = self._out, []
        return out


def _pad(arr: np.ndarray, pad: int) -> np.ndarray:
    if pad == 0:
        return arr
    widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, widths)


@dataclass(frozen=True)
class R2D2Core:
    """Static wiring of the recurrent model/replay/optimizer into jitted
    steps — the recurrent sibling of :class:`LearnerCore`/:class:`AQLCore`
    (same ``ingest``/``train_step`` signature, so
    :func:`apex_tpu.training.learner.scan_fused_steps` applies)."""

    model: RecurrentDuelingDQN
    replay: object          # DeviceReplay | SequenceFramePoolReplay
    optimizer: optax.GradientTransformation
    batch_size: int = 64
    target_update_interval: int = 2500
    burn_in: int = 8
    n_steps: int = 3

    def update_from_batch(self, ts: TrainState, batch, weights,
                          axis_name: str | None = None):
        def loss_fn(params):
            return r2d2_loss(self.model.apply, params, ts.target_params,
                             batch, weights, burn_in=self.burn_in,
                             n_steps=self.n_steps)

        return td_update(self.optimizer, self.target_update_interval,
                         ts, loss_fn, axis_name)

    def train_step(self, ts, rs, key, beta):
        batch, weights, idx = self.replay.sample(rs, key, self.batch_size,
                                                 beta)
        ts, priorities, metrics = self.update_from_batch(ts, batch, weights)
        rs = self.replay.update_priorities(rs, idx, priorities)
        return ts, rs, metrics

    def ingest(self, rs, batch, priorities):
        return self.replay.add(rs, batch, priorities)

    def fused_step(self, ts, rs, ingest_batch, ingest_prios, key, beta):
        rs = self.ingest(rs, ingest_batch, ingest_prios)
        return self.train_step(ts, rs, key, beta)

    def fused_multi_step(self, ts, rs, ingest_batches, ingest_prios, keys,
                         beta):
        """K fused steps in one dispatch — see
        :func:`apex_tpu.training.learner.scan_fused_steps`."""
        return scan_fused_steps(self, ts, rs, ingest_batches, ingest_prios,
                                keys, beta)

    def jit_train_step(self):
        return jax.jit(self.train_step, donate_argnums=(0, 1))

    def jit_ingest(self):
        return jax.jit(self.ingest, donate_argnums=(0,))

    def jit_fused_step(self):
        return jax.jit(self.fused_step, donate_argnums=(0, 1))

    def jit_fused_multi_step(self):
        return jax.jit(self.fused_multi_step, donate_argnums=(0, 1))


def r2d2_env_specs(cfg: ApexConfig):
    """(model_spec, obs_shape, obs_dtype) for the recurrent family —
    single-frame observations (the LSTM is the memory).  Shared by the
    drivers and the socket roles."""
    import dataclasses as _dc

    cfg1 = cfg.replace(env=_dc.replace(cfg.env, frame_stack=1))
    probe = make_env(cfg1.env.env_id, cfg1.env, seed=cfg1.env.seed)
    obs_shape = probe.observation_space.shape
    obs_dtype = probe.observation_space.dtype
    spec = dict(
        num_actions=num_actions(probe),
        obs_is_image=len(obs_shape) == 3,
        compute_dtype=jnp.dtype(cfg.learner.compute_dtype),
        scale_uint8=obs_dtype == np.dtype(np.uint8),
        lstm_features=cfg.r2d2.lstm_features)
    probe.close()
    return spec, obs_shape, obs_dtype


def r2d2_model_spec(cfg: ApexConfig) -> dict:
    return r2d2_env_specs(cfg)[0]


def r2d2_uses_frame_pool(cfg: ApexConfig, obs_shape) -> bool:
    """THE one predicate deciding the recurrent family's storage layout —
    shared by :func:`build_r2d2` and the worker families so the learner's
    replay spec and the actors' message format cannot diverge.  Pooled
    storage dedups pixel frames; vector observations stay on the stacked
    layout (rows too small for the ring economics to matter)."""
    return bool(cfg.replay.frame_pool) and len(obs_shape) == 3


def r2d2_frame_capacity(cfg: ApexConfig) -> int:
    """Frame-ring rows for the pooled sequence layout.  Each live
    sequence references ~``stride`` frames new to it plus its share of
    the cross-message window reshipping (``(t_total - stride)/group``
    rows, :func:`apex_tpu.actors.r2d2.pooled_sequence_message`); 1.5x
    headroom keeps the staleness redirect a measure-zero event under
    episode-boundary jitter."""
    rc, lc = cfg.r2d2, cfg.learner
    t_total = rc.burn_in + rc.unroll + lc.n_steps
    stride = rc.stride or max(1, rc.unroll // 2)
    per_seq = stride + -(-(t_total - stride + 1) // rc.sequence_group)
    return max(2 * t_total, int(1.5 * cfg.replay.capacity * per_seq))


def build_r2d2(cfg: ApexConfig, key: jax.Array):
    """(model_spec, obs_shape, obs_dtype, model, replay, example_item,
    train_state, core) — THE one definition of the family's replay item
    schema and core wiring, shared by the single-process and concurrent
    drivers (two hand-kept copies would let checkpoint bundles and replay
    layouts silently diverge between them).  Nothing replay-sized is
    allocated here: the driver builds the state with
    ``replay.init(example_item)``, or — on a dp>1 mesh — directly under
    the sharding (:meth:`ShardedLearner.init_replay`)."""
    rc, lc = cfg.r2d2, cfg.learner
    model_spec, obs_shape, obs_dtype = r2d2_env_specs(cfg)
    model = RecurrentDuelingDQN(**model_spec)

    t_total = rc.burn_in + rc.unroll + lc.n_steps
    if r2d2_uses_frame_pool(cfg, obs_shape):
        from apex_tpu.replay.seq_pool import SequenceFramePoolReplay
        replay = SequenceFramePoolReplay(
            capacity=cfg.replay.capacity, t_total=t_total,
            lstm_features=rc.lstm_features, frame_shape=tuple(obs_shape),
            frame_capacity=r2d2_frame_capacity(cfg),
            frame_dtype=str(np.dtype(obs_dtype)),
            alpha=cfg.replay.alpha, eps=cfg.replay.eps)
        check_hbm_budget(replay.hbm_bytes(), cfg.replay.hbm_budget_gb,
                         "R2D2 replay (pooled sequence storage)",
                         cfg.replay.capacity)
        example_item = None             # shapes come from the pool spec
    else:
        replay = DeviceReplay(capacity=cfg.replay.capacity,
                              alpha=cfg.replay.alpha, eps=cfg.replay.eps)
        example_item = dict(
            obs=jnp.zeros((t_total,) + obs_shape, obs_dtype),
            action=jnp.zeros(t_total, jnp.int32),
            reward=jnp.zeros(t_total, jnp.float32),
            discount=jnp.zeros(t_total, jnp.float32),
            mask=jnp.zeros(t_total, jnp.float32),
            state_c=jnp.zeros(rc.lstm_features, jnp.float32),
            state_h=jnp.zeros(rc.lstm_features, jnp.float32))
        check_hbm_budget(replay.hbm_bytes(example_item),
                         cfg.replay.hbm_budget_gb,
                         "R2D2 replay (sequence storage)",
                         cfg.replay.capacity)

    optimizer = make_optimizer(
        lr=lc.lr, decay=lc.rmsprop_decay, eps=lc.rmsprop_eps,
        centered=lc.rmsprop_centered, max_grad_norm=lc.max_grad_norm,
        lr_decay_steps=lc.lr_decay_steps, lr_decay_rate=lc.lr_decay_rate)
    params = model.init(key, jnp.zeros((1, t_total) + obs_shape, obs_dtype),
                        model.initial_state(1))
    train_state = TrainState(
        params=params, target_params=jax.tree.map(jnp.copy, params),
        opt_state=optimizer.init(params), step=jnp.int32(0))
    core = R2D2Core(model=model, replay=replay, optimizer=optimizer,
                    batch_size=lc.batch_size,
                    target_update_interval=lc.target_update_interval,
                    burn_in=rc.burn_in, n_steps=lc.n_steps)
    return (model_spec, obs_shape, obs_dtype, model, replay, example_item,
            train_state, core)


def _r2d2_evaluate(self, episodes: int = 10, epsilon: float = 0.0,
                   max_steps: int = 10_000) -> float:
    """Greedy recurrent eval shared by both R2D2 drivers: the carry
    threads within each episode and resets between them."""
    from apex_tpu.training.checkpoint import run_policy_episodes

    if not hasattr(self, "_eval_env"):
        self._eval_env = make_eval_env(self.cfg.env.env_id, self.cfg.env,
                                       seed=self.cfg.env.seed + 999)
    carry_box = [self.model.initial_state(1)]

    def step_fn(obs, eps, k):
        a, _, carry_box[0] = self._policy(self.train_state.params, obs,
                                          carry_box[0], eps, k)
        return int(a[0])

    self.key, eval_key = jax.random.split(self.key)
    rewards = run_policy_episodes(
        self._eval_env, step_fn, eval_key, episodes, epsilon, max_steps,
        seed_base=self.cfg.env.seed + 1000,
        reset_hook=lambda: carry_box.__setitem__(
            0, self.model.initial_state(1)))
    return float(np.mean(rewards))


class R2D2Trainer(CheckpointableTrainer):
    """Single-process recurrent driver, mirroring :class:`DQNTrainer`'s
    loop with a stateful policy: the recurrent carry threads through the
    episode and resets at boundaries; each env step feeds the
    SequenceBuilder with the carry that produced the action."""

    def __init__(self, config: ApexConfig | None = None,
                 logdir: str | None = None, verbose: bool = False,
                 train_every: int = 4, checkpoint_dir: str | None = None):
        import dataclasses as _dc
        cfg = config or ApexConfig()
        # single frames for the recurrent family: the LSTM is the memory,
        # a frame stack would quadruple sequence-replay HBM for nothing
        # (models/recurrent.py module docstring); the replaced cfg is what
        # checkpoints save, so enjoy/eval rebuild the same env
        cfg = cfg.replace(env=_dc.replace(cfg.env, frame_stack=1))
        self.cfg = cfg
        self.key = set_global_seeds(cfg.env.seed)
        self.env = make_env(cfg.env.env_id, cfg.env, seed=cfg.env.seed,
                            max_episode_steps=cfg.actor.max_episode_length)
        rc, lc = cfg.r2d2, cfg.learner
        self.key, init_key = jax.random.split(self.key)
        (self.model_spec, _obs_shape, _obs_dtype, self.model, self.replay,
         example_item, self.train_state, self.core) = build_r2d2(
            cfg, init_key)
        self.replay_state = self.replay.init(example_item)
        self._train_step = self.core.jit_train_step()
        self._ingest = self.core.jit_ingest()
        self._policy = jax.jit(make_recurrent_policy_fn(self.model))

        from apex_tpu.replay.seq_pool import SequenceFramePoolReplay
        self.pooled = isinstance(self.replay, SequenceFramePoolReplay)
        self._message_fn = (pooled_sequence_message if self.pooled
                            else sequence_message)
        self.builder = SequenceBuilder(rc.burn_in, rc.unroll, lc.n_steps,
                                       lc.gamma, stride=rc.stride,
                                       pooled=self.pooled)
        self._pending: list[dict] = []
        self.transitions = 0
        self.ingest_group = rc.sequence_group
        self.train_every = train_every
        self.epsilon = EpsilonSchedule()
        self.beta = BetaSchedule(start=cfg.replay.beta)
        self.log = MetricLogger("learner", logdir, verbose=verbose)
        self.frames_rate = RateCounter()
        self.steps_rate = RateCounter()
        self.sequences = 0
        self.checkpointer = (Checkpointer(checkpoint_dir)
                             if checkpoint_dir else None)

    # -- checkpointing (A4) ------------------------------------------------

    def _counters(self) -> dict:
        return dict(sequences=self.sequences, frames=self.frames_rate.total,
                    steps=self.steps_rate.total, transitions=self.transitions)

    def _apply_counters(self, meta: dict) -> None:
        self.sequences = meta["sequences"]
        self.frames_rate.total = meta["frames"]
        self.steps_rate.total = meta["steps"]
        # absent in pre-round-5 checkpoints: fall back to the old
        # sequence-derived estimate so resumes stay monotonic
        self.transitions = meta.get(
            "transitions", meta["sequences"] * self.builder.t_total)

    # -- main loop ---------------------------------------------------------

    def train(self, total_frames: int, log_every: int = 1000,
              warmup_sequences: int | None = None):
        cfg = self.cfg
        # warmup gates on UNIQUE env transitions accumulated (sum of each
        # sequence's n_new), not sequence count: with stride < t_total the
        # windows overlap, so seq_count * t_total overstates coverage
        # ~t_total/stride-fold.  Matches the concurrent trainer's
        # ``ingested >= warmup`` semantics.  A sequence floor of one full
        # batch keeps early sampling from being all-duplicates.
        warmup_seqs = (warmup_sequences if warmup_sequences is not None
                       else cfg.learner.batch_size)
        warmup_trans = 0 if warmup_sequences is not None \
            else cfg.replay.warmup
        obs, _ = self.env.reset(seed=cfg.env.seed)
        carry = self.model.initial_state(1)
        episode_reward, episode_len, episode_idx = 0.0, 0, 0
        start = self.frames_rate.total

        for frame in range(start + 1, start + total_frames + 1):
            eps = self.epsilon(frame)
            self.key, act_key = jax.random.split(self.key)
            obs_np = np.asarray(obs)
            # materialize the pre-action carry only at sequence starts —
            # the builder reads nothing else, and each np.asarray is a
            # blocking device sync
            if self.builder.needs_carry:
                cc = np.asarray(carry[0][0])
                ch = np.asarray(carry[1][0])
            else:
                cc = ch = None
            actions, q, carry = self._policy(
                self.train_state.params, obs_np[None], carry,
                jnp.float32(eps), act_key)
            action = int(actions[0])

            next_obs, reward, terminated, truncated, _ = self.env.step(action)
            self.builder.add_step(obs_np, action, float(reward),
                                  bool(terminated), cc, ch,
                                  q_values=np.asarray(q[0]))
            obs = next_obs
            episode_reward += float(reward)
            episode_len += 1
            self.frames_rate.tick()

            if terminated or truncated:
                self.builder.end_episode(
                    truncated=bool(truncated and not terminated))
                # grouped fixed-shape ingest: stacks of exactly
                # ingest_group sequences -> one transfer + one dispatch,
                # no per-count retrace; remainders wait for the next
                # episode's drain
                self._pending.extend(self.builder.drain())
                for msg in drain_grouped(self._pending, self.ingest_group,
                                         self._message_fn):
                    self.replay_state = self._ingest(
                        self.replay_state, msg["payload"],
                        jnp.asarray(msg["priorities"]))
                    self.sequences += self.ingest_group
                    self.transitions += int(msg["n_trans"])
                obs, _ = self.env.reset()
                carry = self.model.initial_state(1)
                self.log.scalars({"episode_reward": episode_reward,
                                  "episode_length": episode_len}, episode_idx)
                episode_reward, episode_len = 0.0, 0
                episode_idx += 1

            if (self.sequences >= warmup_seqs
                    and self.transitions >= warmup_trans
                    and frame % self.train_every == 0):
                self.key, step_key = jax.random.split(self.key)
                self.train_state, self.replay_state, metrics = \
                    self._train_step(self.train_state, self.replay_state,
                                     step_key, jnp.float32(self.beta(frame)))
                self.steps_rate.tick()
                if (self.checkpointer is not None and self.steps_rate.total
                        % cfg.learner.save_interval == 0):
                    self.save_checkpoint()
                if self.steps_rate.total % log_every == 0:
                    self.log.scalars(
                        {k: float(v) for k, v in metrics.items()}
                        | {"bps": self.steps_rate.rate,
                           "fps": self.frames_rate.rate,
                           "sequences": self.sequences},
                        self.steps_rate.total)
        return self

    # -- evaluation (shared with the concurrent trainer) -------------------

    evaluate = _r2d2_evaluate


class R2D2ApexTrainer(ConcurrentTrainer):
    """Concurrent distributed R2D2 — the third family on the shared
    Ape-X machinery: worker processes act statefully through
    :class:`apex_tpu.actors.r2d2.R2D2WorkerFamily` (epsilon ladder,
    conflating param queues, respawn) and ship grouped sequence messages;
    the learner runs the fused sequence ingest+train step, optionally
    scan-dispatched (``config.scan_steps``) or dp-sharded
    (``config.learner.mesh_shape``).

    Unit note: the replay-ratio knobs (``train_ratio``/
    ``min_train_ratio``) compare learner SEQUENCES consumed (batch_size
    counts sequences) against TRANSITIONS ingested — set them with the
    sequence length in mind, or leave None (fully decoupled, the
    reference behavior).
    """

    def __init__(self, config: ApexConfig | None = None,
                 logdir: str | None = None, verbose: bool = False,
                 publish_min_seconds: float = 0.2,
                 train_ratio: float | None = None,
                 min_train_ratio: float | None = None,
                 checkpoint_dir: str | None = None,
                 pool=None, respawn_workers: bool = True):
        import dataclasses as _dc

        from apex_tpu.actors.pool import ActorPool
        from apex_tpu.actors.r2d2 import r2d2_worker_main

        cfg = config or ApexConfig()
        cfg = cfg.replace(env=_dc.replace(cfg.env, frame_stack=1))
        self.cfg = cfg
        self.key = set_global_seeds(cfg.env.seed)
        self.publish_min_seconds = publish_min_seconds
        self.train_ratio = train_ratio
        self.min_train_ratio = min_train_ratio
        self.respawn_workers = respawn_workers
        if (train_ratio is not None and min_train_ratio is not None
                and min_train_ratio > train_ratio):
            raise ValueError("min_train_ratio must be <= train_ratio")

        rc, lc = cfg.r2d2, cfg.learner
        self.key, init_key = jax.random.split(self.key)
        (self.model_spec, obs_shape, obs_dtype, self.model, self.replay,
         example_item, self.train_state, self.core) = build_r2d2(
            cfg, init_key)
        self._policy = jax.jit(make_recurrent_policy_fn(self.model))

        if pool is not None:
            self.pool = pool
        else:
            worker = r2d2_worker_main
            if cfg.actor.n_envs_per_actor > 1:
                from apex_tpu.actors.r2d2 import vector_r2d2_worker_main
                worker = vector_r2d2_worker_main
            group = rc.sequence_group
            t_total = rc.burn_in + rc.unroll + lc.n_steps
            obs_bytes = int(np.prod(obs_shape)) * np.dtype(obs_dtype).itemsize
            # covers BOTH layouts: stacked ships G*T obs windows; pooled
            # ships <= G*T+1 frame rows plus the i32 obs_ref table
            slot = (group * t_total + 1) * obs_bytes \
                + group * t_total * 24 \
                + group * 8 * rc.lstm_features + 65536
            self.pool = ActorPool(cfg, self.model_spec,
                                  chunk_transitions=group,
                                  worker_fn=worker,
                                  shm_slot_bytes=slot)

        self.n_dp = int(np.prod(lc.mesh_shape))
        if self.n_dp > 1:
            self._init_sharded(example_item)
        else:
            self.replay_state = self.replay.init(example_item)
            self._fused = self.core.jit_fused_step()
            self._train = self.core.jit_train_step()
            self._ingest = self.core.jit_ingest()
            if lc.scan_steps > 1:
                self.scan_steps = lc.scan_steps
                self._multi = self.core.jit_fused_multi_step()

        self.log = MetricLogger("learner", logdir, verbose=verbose)
        self.steps_rate = RateCounter()
        self.frames_rate = RateCounter()
        self.ingested = 0
        self.param_version = 0
        self.checkpointer = (Checkpointer(checkpoint_dir)
                             if checkpoint_dir else None)

    evaluate = _r2d2_evaluate
