"""AQL training: fused learner core + single-process driver.

Capability parity with the reference's single-process ``AQL.py`` (C12) on the
TPU architecture: the candidate-set Q loss and the proposal loss run as ONE
compiled XLA program per update (sample -> both losses -> two-group Adam ->
target sync -> priority write-back), against the generic HBM
:class:`~apex_tpu.replay.device.DeviceReplay` whose item pytree carries the
``a_mu`` candidate set (reference ``CustomPrioritizedReplayBuffer_AQL``,
``memory.py:364-391``).

Structural deltas from the reference (deliberate):

* Two ``value_and_grad`` passes share one params tree and merge by label —
  the reference's zero_grad/step interleaving (``AQL_dis.py:87-101``)
  expressed functionally; the proposal loss cannot leak into Q parameters
  (merge takes non-proposal leaves from the Q grads alone) and vice versa.
* NoisyNet/proposal/epsilon randomness all ride explicit PRNG keys.
* Initial priorities are 1-step TD errors computed from acting-time Q-values
  (the DQN path's actor-priority principle, ``memory.py:451-464``, applied
  to AQL — the reference inserts AQL transitions at max priority).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
import optax

from apex_tpu.config import ApexConfig
from apex_tpu.envs.registry import make_env, make_eval_env
from apex_tpu.models.aql import AQLNetwork, make_aql_policy_fn
from apex_tpu.ops.losses import (aql_proposal_loss, aql_q_loss,
                                 make_aql_optimizer)
from apex_tpu.replay.base import check_hbm_budget
from apex_tpu.replay.device import DeviceReplay, ReplayState
from apex_tpu.training.apex import ConcurrentTrainer
from apex_tpu.training.learner import scan_fused_steps
from apex_tpu.training.checkpoint import (CheckpointableTrainer,
                                          Checkpointer)
from apex_tpu.training.state import TrainState
from apex_tpu.utils.metrics import MetricLogger, RateCounter
from apex_tpu.utils.seeding import set_global_seeds


@dataclass(frozen=True)
class AQLCore:
    """Static wiring of the AQL model/replay/optimizer into jitted steps."""

    model: AQLNetwork
    replay: DeviceReplay
    optimizer: optax.GradientTransformation
    batch_size: int = 64
    target_update_interval: int = 500
    entropy_coef: float = 0.01
    # the update consumes a PRNG key (NoisyNet draws) — ShardedLearner
    # splits its per-chip key between sampling and the update
    update_needs_key = True

    # -- functional model hooks -------------------------------------------

    def _score(self, params, obs, a_mu, noise_key):
        return self.model.apply(params, obs, a_mu,
                                rngs={"noise": noise_key})

    def _log_prob(self, params, obs, actions):
        return self.model.apply(params, obs, actions,
                                method=AQLNetwork.proposal_log_prob)

    # -- update body -------------------------------------------------------

    @jax.named_scope("update")
    def update_from_batch(self, ts: TrainState, batch, weights,
                          key: jax.Array, axis_name: str | None = None):
        """The two-loss update, under the same trace scopes as
        :func:`apex_tpu.training.learner.td_update`: ``update`` with
        ``loss_grad``, ``optimizer`` and ``target_sync`` inside it."""
        k_online, k_target = jax.random.split(key)

        def q_loss_fn(params):
            return aql_q_loss(self._score, params, ts.target_params, batch,
                              weights, k_online, k_target)

        with jax.named_scope("loss_grad"):
            (loss_q, aux), q_grads = jax.value_and_grad(
                q_loss_fn, has_aux=True)(ts.params)
            # argmax-Q candidate under the same online noise draw, straight
            # from the loss pass — no second scoring of the candidate set
            best_idx = aux.best_idx

            def p_loss_fn(params):
                return aql_proposal_loss(self._log_prob, params, batch,
                                         best_idx, self.entropy_coef)

            loss_p, p_grads = jax.value_and_grad(p_loss_fn)(ts.params)

            # merge by label: proposal leaves from the proposal pass, the
            # rest from the Q pass — neither loss can touch the other group
            from apex_tpu.ops.losses import aql_param_labels
            labels = aql_param_labels(ts.params)
            grads = jax.tree.map(
                lambda lbl, qg, pg: pg if lbl == "proposal" else qg,
                labels, q_grads, p_grads)

            if axis_name is not None:
                grads = jax.lax.pmean(grads, axis_name)
                loss_q = jax.lax.pmean(loss_q, axis_name)
                loss_p = jax.lax.pmean(loss_p, axis_name)

        with jax.named_scope("optimizer"):
            updates, opt_state = self.optimizer.update(grads, ts.opt_state,
                                                       ts.params)
            params = optax.apply_updates(ts.params, updates)
        step = ts.step + 1
        with jax.named_scope("target_sync"):
            target_params = jax.lax.cond(
                step % self.target_update_interval == 0,
                lambda: jax.tree.map(jnp.copy, params),
                lambda: ts.target_params)

        q_mean, td_mean = aux.q_taken.mean(), aux.td_abs.mean()
        if axis_name is not None:
            q_mean = jax.lax.pmean(q_mean, axis_name)
            td_mean = jax.lax.pmean(td_mean, axis_name)
        metrics = {"loss": loss_q, "loss_proposal": loss_p,
                   "grad_norm": optax.global_norm(grads),
                   "q_mean": q_mean,
                   "td_mean": td_mean}
        ts = TrainState(params=params, target_params=target_params,
                        opt_state=opt_state, step=step)
        return ts, aux.priorities, metrics

    def train_step(self, ts: TrainState, rs: ReplayState, key: jax.Array,
                   beta: jax.Array):
        k_sample, k_update = jax.random.split(key)
        batch, weights, idx = self.replay.sample(rs, k_sample,
                                                 self.batch_size, beta)
        ts, priorities, metrics = self.update_from_batch(ts, batch, weights,
                                                         k_update)
        rs = self.replay.update_priorities(rs, idx, priorities)
        return ts, rs, metrics

    def ingest(self, rs: ReplayState, batch, priorities) -> ReplayState:
        return self.replay.add(rs, batch, priorities)

    def fused_step(self, ts, rs, ingest_batch, ingest_prios, key, beta):
        rs = self.ingest(rs, ingest_batch, ingest_prios)
        return self.train_step(ts, rs, key, beta)

    def fused_multi_step(self, ts, rs, ingest_batches, ingest_prios, keys,
                         beta):
        """K fused steps in one dispatch (the two-loss AQL update scans
        exactly like the DQN one) — see
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


class AQLTransitionBuilder:
    """Host-side 1-step transition buffer with acting-time TD priorities.

    The reference's AQL recorder stores raw transitions with no n-step
    window (``batchrecoder_AQL.py:43-59``).  Emission is delayed one step so
    the priority can use the NEXT state's candidate scores:
    ``|r + gamma * max q' - q[idx]|`` — fresher than the reference's
    max-priority inserts, same principle as the DQN actors.
    """

    def __init__(self, gamma: float):
        self.gamma = gamma
        self._pending = None          # (obs, idx, reward, next_obs, a_mu, q)
        self._rows: list[dict] = []

    def add_step(self, obs, idx, reward, next_obs, a_mu, q,
                 terminated: bool, truncated: bool) -> None:
        q_next_max = float(np.max(q))  # q is the CURRENT state's scores
        if self._pending is not None:
            self._emit(self._pending, bootstrap=q_next_max)
        self._pending = (np.asarray(obs), int(idx), float(reward),
                         np.asarray(next_obs), np.asarray(a_mu),
                         float(q[int(idx)]))
        if terminated:
            self._emit(self._pending, bootstrap=None, discount=0.0)
            self._pending = None
        elif truncated:
            # the learner will bootstrap Q(next_obs) (discount=gamma); the
            # final next state was never scored, so the PRIORITY uses the
            # current state's max-Q as the bootstrap proxy — close for
            # slowly-mixing states, and corrected at first write-back
            self._emit(self._pending, bootstrap=q_next_max,
                       discount=self.gamma)
            self._pending = None

    def _emit(self, t, bootstrap, discount=None) -> None:
        obs, idx, reward, next_obs, a_mu, q_taken = t
        disc = self.gamma if discount is None else discount
        boot = 0.0 if bootstrap is None else bootstrap
        prio = abs(reward + disc * boot - q_taken) + 1e-6
        self._rows.append(dict(obs=obs, action=np.int32(idx),
                               reward=np.float32(reward), next_obs=next_obs,
                               discount=np.float32(disc), a_mu=a_mu,
                               priority=np.float32(prio)))

    def __len__(self) -> int:
        return len(self._rows)

    def drain(self, count: int) -> tuple[dict, np.ndarray]:
        rows, self._rows = self._rows[:count], self._rows[count:]
        batch = {k: np.stack([r[k] for r in rows])
                 for k in ("obs", "action", "reward", "next_obs",
                           "discount", "a_mu")}
        prios = np.asarray([r["priority"] for r in rows], np.float32)
        return batch, prios


def aql_model_spec(cfg: ApexConfig, env) -> dict:
    """AQLNetwork constructor kwargs from config + env spaces — picklable,
    shippable to worker processes (the pool's ``model_spec``).

    Box spaces get the Gaussian proposal; Discrete spaces the Categorical
    one with ``uniform_sample`` clamped to the action count (reference
    ``model.py:176-184``)."""
    space = env.action_space
    common = dict(
        propose_sample=cfg.aql.propose_sample,
        uniform_sample=cfg.aql.uniform_sample,
        action_var=cfg.aql.action_var,
        obs_is_image=len(env.observation_space.shape) == 3,
        compute_dtype=jnp.dtype(cfg.learner.compute_dtype),
        scale_uint8=env.observation_space.dtype == np.uint8)
    if hasattr(space, "high"):                         # Box
        return dict(
            action_dim=int(np.prod(space.shape)),
            action_low=float(np.min(space.low)),
            action_high=float(np.max(space.high)),
            **common)
    if not hasattr(space, "n"):
        raise ValueError(f"AQL drives Box or Discrete action spaces, "
                         f"got {type(space).__name__}")
    n = int(space.n)
    common["uniform_sample"] = min(cfg.aql.uniform_sample, n)
    return dict(action_dim=n, discrete=True, **common)


def build_aql(cfg: ApexConfig, model_spec: dict, obs_shape, obs_dtype,
              key: jax.Array, cosine_steps: int | None = None,
              frame_spec: tuple | None = None):
    """(model, train_state, replay, example_item, core) for either driver.
    Nothing replay-sized is allocated here: the driver builds the state
    with ``replay.init(example_item)``, or — on a dp>1 mesh — directly
    under the sharding (:meth:`ShardedLearner.init_replay`).

    ``cosine_steps``: CosineAnnealingLR horizon for both Adam groups —
    the single-process driver passes ``cfg.aql.cosine_lr_steps``
    (``AQL.py:48-49``); the concurrent driver passes 0 (``AQL_dis``
    constructs no schedulers).

    ``frame_spec``: ``(frame_shape, frame_dtype, frame_stack)`` switches
    the replay to the frame-pool layout with the ``a_mu`` candidate set as
    a per-transition sidecar — pixel AQL with frame dedup instead of 2S
    stacked copies per transition (the concurrent driver passes this for
    image observations; ingest then expects FrameChunkBuilder chunks)."""
    model = AQLNetwork(**model_spec)
    t = model.total_sample
    # discrete candidates are index values on a singleton trailing axis
    a_dim = 1 if model.discrete else model.action_dim
    example_obs = jnp.zeros((1,) + tuple(obs_shape), obs_dtype)
    example_a_mu = jnp.zeros((1, t, a_dim), jnp.float32)
    init_key, noise_key, sample_key = jax.random.split(key, 3)
    optimizer = make_aql_optimizer(
        q_lr=cfg.aql.q_lr, proposal_lr=cfg.aql.proposal_lr,
        max_grad_norm=cfg.learner.max_grad_norm,
        cosine_steps=cosine_steps)
    params = model.init(
        {"params": init_key, "noise": noise_key, "sample": sample_key},
        example_obs, example_a_mu, method=AQLNetwork.full_init)
    train_state = TrainState(
        params=params,
        target_params=jax.tree.map(jnp.copy, params),
        opt_state=optimizer.init(params),
        step=jnp.int32(0))

    if frame_spec is not None:
        from apex_tpu.replay.frame_pool import FramePoolReplay
        frame_shape, frame_dtype, frame_stack = frame_spec
        replay = FramePoolReplay(
            capacity=cfg.replay.capacity, frame_shape=tuple(frame_shape),
            frame_stack=frame_stack,
            frame_dtype=np.dtype(frame_dtype).name,
            alpha=cfg.replay.alpha, eps=cfg.replay.eps,
            extra_spec=(("a_mu", (t, a_dim)),))
        check_hbm_budget(replay.hbm_bytes(), cfg.replay.hbm_budget_gb,
                         "AQL frame-pool replay (frames + a_mu sidecars)",
                         cfg.replay.capacity)
        example_item = None             # shapes come from the pool spec
    else:
        replay = DeviceReplay(capacity=cfg.replay.capacity,
                              alpha=cfg.replay.alpha, eps=cfg.replay.eps)
        example_item = dict(
            obs=jnp.zeros(tuple(obs_shape), obs_dtype),
            action=jnp.int32(0), reward=jnp.float32(0),
            next_obs=jnp.zeros(tuple(obs_shape), obs_dtype),
            discount=jnp.float32(0),
            a_mu=jnp.zeros((t, a_dim), jnp.float32))
        check_hbm_budget(replay.hbm_bytes(example_item),
                         cfg.replay.hbm_budget_gb,
                         "AQL replay (stacked obs + a_mu candidate sets)",
                         cfg.replay.capacity)

    core = AQLCore(model=model, replay=replay, optimizer=optimizer,
                   batch_size=cfg.learner.batch_size,
                   target_update_interval=cfg.learner.target_update_interval,
                   entropy_coef=cfg.aql.entropy_coef)
    return model, train_state, replay, example_item, core


class AQLTrainer(CheckpointableTrainer):
    """Single-process AQL driver (reference ``AQL.py:17-109``)."""

    def __init__(self, config: ApexConfig | None = None,
                 logdir: str | None = None, verbose: bool = False,
                 train_every: int = 1, checkpoint_dir: str | None = None):
        self.cfg = cfg = config or ApexConfig()
        self.key = set_global_seeds(cfg.env.seed)
        self.env = make_env(cfg.env.env_id, cfg.env, seed=cfg.env.seed)
        self.model_spec = aql_model_spec(cfg, self.env)
        self.key, build_key = jax.random.split(self.key)
        (self.model, self.train_state, self.replay, example_item,
         self.core) = build_aql(cfg, self.model_spec,
                                self.env.observation_space.shape,
                                self.env.observation_space.dtype, build_key,
                                cosine_steps=cfg.aql.cosine_lr_steps)
        self.replay_state = self.replay.init(example_item)
        self._train_step = self.core.jit_train_step()
        self._ingest = self.core.jit_ingest()
        self._policy = jax.jit(make_aql_policy_fn(self.model))
        eval_model = self.model.clone(noisy_deterministic=True)
        self._eval_policy = jax.jit(make_aql_policy_fn(eval_model))

        from apex_tpu.training.dqn import BetaSchedule, EpsilonSchedule
        self.builder = AQLTransitionBuilder(cfg.learner.gamma)
        self.epsilon = EpsilonSchedule(decay=4000.0)
        self.beta = BetaSchedule(start=cfg.replay.beta)
        self.ingest_chunk = cfg.learner.ingest_chunk
        self.train_every = train_every
        self.log = MetricLogger("learner", logdir, verbose=verbose)
        self.steps_rate = RateCounter()
        self.frames_rate = RateCounter()
        self.ingested = 0
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

    # -- main loop ---------------------------------------------------------

    def train(self, total_frames: int, log_every: int = 500):
        """Run ``total_frames`` MORE env frames (schedules continue from a
        restored checkpoint's frame counter)."""
        cfg = self.cfg
        obs, _ = self.env.reset(seed=cfg.env.seed)
        ep_reward, ep_idx = 0.0, 0
        start = self.frames_rate.total

        for frame in range(start + 1, start + total_frames + 1):
            self.key, k = jax.random.split(self.key)
            obs_np = np.asarray(obs)
            actions, idx, a_mu, q = self._policy(
                self.train_state.params, obs_np[None],
                jnp.float32(self.epsilon(frame)), k)
            next_obs, reward, term, trunc, _ = self.env.step(
                np.asarray(actions[0]))
            self.builder.add_step(obs_np, int(idx[0]), float(reward),
                                  np.asarray(next_obs), np.asarray(a_mu[0]),
                                  np.asarray(q[0]), bool(term), bool(trunc))
            ep_reward += float(reward)
            self.frames_rate.tick()

            if term or trunc:
                obs, _ = self.env.reset()
                self.log.scalars({"episode_reward": ep_reward}, ep_idx)
                ep_reward, ep_idx = 0.0, ep_idx + 1
            else:
                obs = next_obs

            while len(self.builder) >= self.ingest_chunk:
                batch, prios = self.builder.drain(self.ingest_chunk)
                self.replay_state = self._ingest(self.replay_state, batch,
                                                 jnp.asarray(prios))
                self.ingested += len(prios)

            warm = self.ingested >= cfg.replay.warmup
            if warm and frame % self.train_every == 0:
                self.key, sk = jax.random.split(self.key)
                self.train_state, self.replay_state, metrics = \
                    self._train_step(self.train_state, self.replay_state,
                                     sk, jnp.float32(self.beta(frame)))
                self.steps_rate.tick()
                if (self.checkpointer is not None and self.steps_rate.total
                        % cfg.learner.save_interval == 0):
                    self.save_checkpoint()
                if self.steps_rate.total % log_every == 0:
                    self.log.scalars(
                        {k: float(v) for k, v in metrics.items()}
                        | {"bps": self.steps_rate.rate,
                           "fps": self.frames_rate.rate},
                        self.steps_rate.total)
        return self

    # -- evaluation --------------------------------------------------------

    def evaluate(self, episodes: int = 10, epsilon: float = 0.0,
                 max_steps: int = 1000) -> float:
        """Greedy eval with deterministic (mu-only) NoisyNet heads."""
        return _aql_evaluate(self, episodes, epsilon, max_steps)


def _aql_evaluate(trainer, episodes: int, epsilon: float,
                  max_steps: int) -> float:
    if not hasattr(trainer, "_eval_env"):
        trainer._eval_env = make_eval_env(
            trainer.cfg.env.env_id, trainer.cfg.env,
            seed=trainer.cfg.env.seed + 999)
    rewards = []
    for ep in range(episodes):
        obs, _ = trainer._eval_env.reset(
            seed=trainer.cfg.env.seed + 1000 + ep)
        total, done, steps = 0.0, False, 0
        while not done and steps < max_steps:
            trainer.key, k = jax.random.split(trainer.key)
            a, _, _, _ = trainer._eval_policy(
                trainer.train_state.params, np.asarray(obs)[None],
                jnp.float32(epsilon), k)
            obs, r, term, trunc, _ = trainer._eval_env.step(
                np.asarray(a[0]))
            total += float(r)
            done = term or trunc
            steps += 1
        rewards.append(total)
    return float(np.mean(rewards))


class AQLApexTrainer(ConcurrentTrainer):
    """Distributed AQL driver (reference ``AQL_dis.py:18-135``, C12): the
    shared concurrent loop over an AQL actor pool.

    Unlike the reference's SYNCHRONOUS rounds — push weights, every worker
    runs exactly one episode, drain, train ``total_ep//batch_size`` times
    (``AQL_dis.py:112-126``) — workers explore continuously and the learner
    overlaps with acting, same as the DQN family; the replay-ratio band
    supplies the coupling the synchronous rounds provided.
    """

    def __init__(self, config: ApexConfig | None = None,
                 logdir: str | None = None, verbose: bool = False,
                 publish_min_seconds: float = 0.2,
                 train_ratio: float | None = None,
                 min_train_ratio: float | None = None,
                 checkpoint_dir: str | None = None,
                 pool=None):
        from apex_tpu.actors.aql import aql_worker_main
        from apex_tpu.actors.pool import ActorPool

        self.cfg = cfg = config or ApexConfig()
        self.key = set_global_seeds(cfg.env.seed)
        self.publish_min_seconds = publish_min_seconds
        self.train_ratio = train_ratio
        self.min_train_ratio = min_train_ratio
        self.respawn_workers = True
        if (train_ratio is not None and min_train_ratio is not None
                and min_train_ratio > train_ratio):
            raise ValueError("min_train_ratio must be <= train_ratio")

        # ONE un-stacked probe covers every case (env construction can be
        # expensive — ALE ROM loads): model_spec reads spaces that stacking
        # doesn't change, and the stacked obs shape is FrameStack's own
        # formula (wrappers.py:198-200) applied analytically.
        from apex_tpu.envs.registry import unstacked_env_spec
        probe = make_env(cfg.env.env_id, cfg.env, seed=cfg.env.seed,
                         stack_frames=False)
        self.model_spec = aql_model_spec(cfg, probe)
        frame_shape, frame_dtype, frame_stack = unstacked_env_spec(
            probe, cfg.env)
        probe.close()
        frame_spec = None
        if self.model_spec["obs_is_image"]:
            # pixel AQL rides the frame-pool layout: actor workers switch
            # to the chunk builder family and replay dedups frames
            frame_spec = (frame_shape, frame_dtype, frame_stack)
            obs_shape = frame_shape[:-1] + (frame_stack * frame_shape[-1],)
            obs_dtype = frame_dtype
        elif cfg.env.frame_stack > 1:
            # non-image envs are cheap (numpy toys): re-probe stacked so
            # declared spaces stay authoritative for the odd vector+stack
            # combination
            p2 = make_env(cfg.env.env_id, cfg.env, seed=cfg.env.seed)
            obs_shape = p2.observation_space.shape
            obs_dtype = p2.observation_space.dtype
            p2.close()
        else:
            obs_shape, obs_dtype = frame_shape, frame_dtype

        self.key, build_key = jax.random.split(self.key)
        (self.model, self.train_state, self.replay, example_item,
         self.core) = build_aql(cfg, self.model_spec, obs_shape, obs_dtype,
                                build_key, frame_spec=frame_spec)
        eval_model = self.model.clone(noisy_deterministic=True)
        self._eval_policy = jax.jit(make_aql_policy_fn(eval_model))

        if pool is not None:
            self.pool = pool
        else:
            # AQL chunks: K x (obs + next_obs + a_mu candidate set +
            # scalars) — size the ring slot from the actual spec
            k = cfg.actor.send_interval
            act_dim = (1 if self.model_spec.get("discrete")
                       else self.model_spec["action_dim"])
            t = (self.model_spec["propose_sample"]
                 + self.model_spec["uniform_sample"])
            if frame_spec is not None:
                # frame chunk (single frames + refs) + a_mu sidecar rows
                from apex_tpu.native.ring import chunk_slot_bytes
                from apex_tpu.replay.frame_chunks import FRAME_MARGIN
                frame_shape, frame_dtype, frame_stack = frame_spec
                slot = chunk_slot_bytes(
                    frame_dim=int(np.prod(frame_shape)),
                    frame_dtype_size=np.dtype(frame_dtype).itemsize,
                    kf=k + FRAME_MARGIN, k=k,
                    stack=frame_stack) + k * 4 * act_dim * t
            else:
                obs_bytes = (int(np.prod(obs_shape))
                             * np.dtype(obs_dtype).itemsize)
                slot = k * (2 * obs_bytes + 4 * act_dim * (t + 1) + 32) + 65536
            worker = aql_worker_main
            if cfg.actor.n_envs_per_actor > 1:
                from apex_tpu.actors.aql import vector_aql_worker_main
                worker = vector_aql_worker_main
            self.pool = ActorPool(
                cfg, self.model_spec,
                chunk_transitions=cfg.actor.send_interval,
                worker_fn=worker, shm_slot_bytes=slot)

        self.n_dp = int(np.prod(cfg.learner.mesh_shape))
        if self.n_dp > 1:
            self._init_sharded(example_item)
        else:
            self.replay_state = self.replay.init(example_item)
            self._fused = self.core.jit_fused_step()
            self._train = self.core.jit_train_step()
            self._ingest = self.core.jit_ingest()
            if cfg.learner.scan_steps > 1:
                self.scan_steps = cfg.learner.scan_steps
                self._multi = self.core.jit_fused_multi_step()
        self.log = MetricLogger("learner", logdir, verbose=verbose)
        self.steps_rate = RateCounter()
        self.frames_rate = RateCounter()
        self.ingested = 0
        self.param_version = 0
        self.checkpointer = (Checkpointer(checkpoint_dir)
                             if checkpoint_dir else None)

    # _init_sharded: ConcurrentTrainer (one multi-chip plan, both families)

    def evaluate(self, episodes: int = 10, epsilon: float = 0.0,
                 max_steps: int = 1000) -> float:
        return _aql_evaluate(self, episodes, epsilon, max_steps)
