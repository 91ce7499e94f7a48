"""Vectorized actor workers: B envs per process, one batched policy call.

The reference runs exactly one env per actor process (``batchrecorder.py:79``,
``origin_repo/actor.py:52-115``), so its "192 actors" cost 192 processes on
48 nodes (``terraform.tfvars:4-5``).  On the TPU topology the policy is a
jitted pure function that is *already batched* (``make_policy_fn`` vectorizes
over the leading axis), so one process can drive B envs with a single
forward per step — B actor slots for one interpreter, one model copy, and
1/B-th the per-call dispatch overhead.  The 256-actor north star
(BASELINE.json) becomes 8 processes x 32 envs instead of 256 processes.

Semantics per env slot are IDENTICAL to the scalar worker
(:mod:`apex_tpu.actors.pool`):

* each slot has its own env, seed, :class:`FrameChunkBuilder`, and its own
  epsilon from the global Ape-X ladder — the ladder spans ALL
  ``n_actors * n_envs`` slots, so exploration diversity matches a fleet of
  scalar actors (``batchrecorder.py:121``);
* n-step windows, truncation bootstrapping, and acting-time TD priorities
  are per-slot (one builder each);
* param refresh stays CONFLATE latest-wins, polled every
  ``update_interval`` *env* steps — i.e. every ``update_interval / B``
  vector steps, so policy staleness measured in env frames is unchanged
  (``actor.py:97-103``);
* episode stats carry the global slot id, so the learner's logs can still
  attribute rewards to an exploration level.

Chunks from all slots ship on the same bounded queue; backpressure applies
to the whole process (a full queue blocks all B slots — strictly stronger
than the scalar fleet's per-process blocking, preserving the end-to-end
flow control).

The vector hot loop is ALTERNATING DOUBLE-BUFFERED (Stooke & Abbeel,
*Accelerated Methods for Deep RL*): the B slots split into two half-groups
A/B, the per-step key derives one subkey per group via
``fold_in(step_key, group)``, and with ``ActorConfig.double_buffer`` on the
jitted policy for BOTH groups dispatches asynchronously before any result
is materialized — group A's env stepping then runs on the host while the
device still computes group B's inference.  The serial interleave
(``double_buffer=False``) dispatches, materializes, and steps one group at
a time with the SAME group split and the SAME per-group keys, so the two
modes are bit-identical per slot (actions, chunks, priorities — pinned in
``tests/test_vector.py``); the knob is a pure scheduling A/B.  Acting
stacks are assembled IN PLACE: one preallocated contiguous
``[B, *stacked]`` buffer whose rows the per-slot
:class:`~apex_tpu.replay.frame_chunks.FrameChunkBuilder`\\ s maintain
through bound views — the policy consumes buffer slices directly, no
per-step ``np.stack`` of B copied stacks.  Each step's wall time is split
into policy-wait / env-step / drain phases
(:class:`~apex_tpu.utils.profiling.PhaseTimer`) and shipped periodically
as :class:`~apex_tpu.actors.pool.ActorTimingStat`.
"""

from __future__ import annotations

import math
import queue as queue_lib

import numpy as np

from apex_tpu.config import ApexConfig
from apex_tpu.actors.pool import EpisodeStat


class VectorFamilyBase:
    """Shared scaffolding for B-env worker families: slot bookkeeping, the
    per-slot epsilon anneal, and episode accounting with auto-reset.  One
    implementation for every family — the reference maintains near-copy
    recorders per algorithm (``batchrecorder.py`` vs
    ``batchrecoder_AQL.py``), the defect this hierarchy exists to avoid.

    Subclasses provide ``_make_env(seed)``, ``_on_reset(i, obs)``, and the
    per-group hooks ``_policy_group``/``_step_group`` consumed by the
    shared double-buffered :meth:`step_all` template (module docstring);
    ``_step_group`` calls :meth:`_finish_step` per slot to get uniform
    accounting/reset behavior.
    """

    #: remote-policy client (apex_tpu/infer_service) — None = local
    #: acting; families that ship their half-groups to the infer server
    #: set ``supports_remote`` and route through it in ``_policy_group``
    infer = None
    supports_remote = False

    def __init__(self, cfg: ApexConfig, seeds, slot_ids, epsilons):
        from apex_tpu.utils.profiling import DispatchGapTimer, PhaseTimer

        self.cfg = cfg
        self.seeds = list(seeds)
        self.slot_ids = list(slot_ids)
        self.epsilons = np.asarray(epsilons, np.float32)
        self.n_envs = len(self.seeds)
        if not (self.n_envs == len(self.slot_ids) == len(self.epsilons)):
            # survives `python -O`, unlike the assert it replaces: a
            # mis-derived slot band would run the wrong exploration
            # spectrum for the whole process
            raise ValueError(
                f"vector worker slot arity mismatch: {len(self.seeds)} "
                f"seeds, {len(self.slot_ids)} slot_ids, "
                f"{len(self.epsilons)} epsilons — all three derive from "
                f"ActorConfig.n_envs_per_actor x ActorConfig.n_actors "
                f"(see worker_slots); check those knobs")
        self.envs = [self._make_env(s) for s in self.seeds]
        self.ep_reward = np.zeros(self.n_envs, np.float64)
        self.ep_len = np.zeros(self.n_envs, np.int64)
        self.slot_steps = np.zeros(self.n_envs, np.int64)
        # alternating double-buffer state: two half-groups (first takes
        # the odd slot), serial fallback when there is nothing to overlap
        half = (self.n_envs + 1) // 2
        self.groups = [sl for sl in (slice(0, half),
                                     slice(half, self.n_envs))
                       if sl.stop > sl.start]
        self.double_buffer = (
            bool(getattr(cfg.actor, "double_buffer", True))
            and self.n_envs >= 2)
        # per-group device epsilon cache (anneal off => the ladder is a
        # constant; re-uploading it every dispatch costs a host->device
        # conversion per group per step)
        self._eps_cache: list | None = None
        # actor-plane observability: per-phase wall fractions + the host
        # gap between policy dispatches (both pure host timing)
        self.phase = PhaseTimer()
        self.gap = DispatchGapTimer()

    # -- lifecycle ---------------------------------------------------------

    def reset_all(self) -> None:
        for i, (env, seed) in enumerate(zip(self.envs, self.seeds)):
            obs, _ = env.reset(seed=seed)
            self._on_reset(i, obs)

    def attach_infer(self, client) -> None:
        """Route this family's half-group policy calls through the
        inference plane (``ActorConfig.remote_policy``).  The local
        policy stays jitted as the fallback — remote and local are
        bit-identical for the same params + key chain, so attaching the
        client changes scheduling, never trajectories."""
        if not self.supports_remote:
            raise NotImplementedError(
                f"{type(self).__name__} has no remote-policy path — "
                f"ActorConfig.remote_policy currently serves the DQN "
                f"vector family only (see ROADMAP.md)")
        self.infer = client

    def close(self) -> None:
        for env in self.envs:
            env.close()
        if self.infer is not None:
            self.infer.close()

    # -- the double-buffered vector step -----------------------------------

    def step_all(self, params, key) -> list:
        """One vector step over all B slots.  Both modes derive one subkey
        per half-group (``fold_in(key, group)`` — folded INSIDE the jitted
        group call, so the derivation costs no extra dispatch) and run the
        policy per group; double-buffered, every group's inference
        dispatches BEFORE any result is materialized, so group A's env
        stepping overlaps group B's device compute.  Returns stats for
        slots whose episodes ended (those are auto-reset)."""
        stats: list = []
        eps = self._group_eps()
        if self.double_buffer:
            outs = []
            for g, sl in enumerate(self.groups):
                self.gap.about_to_dispatch()
                # apexlint: disable=J004 -- each group call folds key with its group id inside the jit: distinct subkeys, no reuse
                outs.append(self._policy_group(params, sl, eps[g], key, g))
                self.gap.dispatch_returned()
            for sl, out in zip(self.groups, outs):
                with self.phase.phase("policy_wait"):
                    host = self._materialize(out)
                with self.phase.phase("env_step"):
                    self._step_group(sl, host, stats)
        else:
            for g, sl in enumerate(self.groups):
                self.gap.about_to_dispatch()
                # apexlint: disable=J004 -- each group call folds key with its group id inside the jit: distinct subkeys, no reuse
                out = self._policy_group(params, sl, eps[g], key, g)
                self.gap.dispatch_returned()
                with self.phase.phase("policy_wait"):
                    host = self._materialize(out)
                with self.phase.phase("env_step"):
                    self._step_group(sl, host, stats)
        return stats

    def _group_eps(self) -> list:
        """Per-group epsilon arrays for this step — device-cached while
        the anneal is off (the ladder is constant), recomputed per step
        otherwise."""
        if not self.cfg.actor.eps_anneal_steps:
            if self._eps_cache is None:
                import jax.numpy as jnp
                self._eps_cache = [jnp.asarray(self.epsilons[sl])
                                   for sl in self.groups]
            return self._eps_cache
        eps = self._current_eps()
        return [eps[sl] for sl in self.groups]

    @staticmethod
    def _grouped_policy(policy_fn):
        """Jit ``policy_fn`` with the per-group key derivation fused in:
        the call receives the RAW per-step key plus its group id and folds
        inside the compiled program — bit-identical to a host-side
        ``fold_in`` at zero extra dispatches."""
        import jax

        def grouped(params, obs, eps, key, group):
            return policy_fn(params, obs, eps,
                             jax.random.fold_in(key, group))

        # group is structural (which half), not data: static avoids a
        # per-call scalar transfer at the cost of one compile per group
        return jax.jit(grouped, static_argnums=(4,))

    def _policy_group(self, params, sl: slice, eps, key, group: int):
        """Dispatch the jitted policy for the slots in ``sl``; must return
        device arrays WITHOUT materializing them (the double-buffered
        interleave defers every blocking host copy to the consumption
        site)."""
        raise NotImplementedError

    @staticmethod
    def _materialize(out) -> tuple:
        """The one blocking device->host sync per group, immediately before
        the group's envs consume the results.  A remote-policy pending
        handle (:class:`~apex_tpu.infer_service.client.PendingInfer`)
        blocks here on the reply — or the local fallback after
        ``infer_wait_s`` — at exactly the site the local path pays its
        ``np.asarray``."""
        mat = getattr(out, "materialize", None)
        if mat is not None:
            return mat()
        return tuple(np.asarray(x) for x in out)

    def _step_group(self, sl: slice, host: tuple, stats: list) -> None:
        """Step the envs in ``sl`` with the group's materialized policy
        outputs and record per-slot transitions."""
        raise NotImplementedError

    # -- shared stepping helpers -------------------------------------------

    def _current_eps(self) -> np.ndarray:
        anneal = self.cfg.actor.eps_anneal_steps
        if not anneal:
            return self.epsilons
        decay = np.exp(-self.slot_steps / anneal)
        return (self.epsilons + (1.0 - self.epsilons) * decay).astype(
            np.float32)

    def _finish_step(self, i: int, reward: float, done: bool,
                     stats: list) -> None:
        """Per-slot accounting + auto-reset; appends an EpisodeStat with
        the GLOBAL slot id when the episode ended."""
        self.ep_reward[i] += reward
        self.ep_len[i] += 1
        self.slot_steps[i] += 1
        if done:
            stats.append(EpisodeStat(self.slot_ids[i],
                                     float(self.ep_reward[i]),
                                     int(self.ep_len[i])))
            self.ep_reward[i] = 0.0
            self.ep_len[i] = 0
            obs, _ = self.envs[i].reset()
            self._on_reset(i, obs)


class VectorChunkFamilyBase(VectorFamilyBase):
    """Base for B-env families that record through per-slot
    :class:`~apex_tpu.replay.frame_chunks.FrameChunkBuilder`\\ s: un-stacked
    envs, builder-managed acting stacks, and chunk-message draining live
    here ONCE (the DQN and pixel-AQL vector families share them)."""

    builders: list            # set by subclass __init__

    def _make_env(self, seed: int):
        from apex_tpu.envs.registry import make_env
        return make_env(self.cfg.env.env_id, self.cfg.env, seed=seed,
                        max_episode_steps=self.cfg.actor.max_episode_length,
                        stack_frames=False)

    def _on_reset(self, i: int, obs) -> None:
        self.builders[i].begin_episode(obs)

    def _bind_acting_buffer(self) -> None:
        """Preallocate ONE contiguous ``[B, *stacked]`` acting buffer and
        hand each builder a row view to maintain in place — the policy
        consumes ``self._acting[group]`` slices directly, eliminating the
        per-step ``np.stack`` of B copied stacks (and each builder's
        per-call concatenate).  Group slices are contiguous and disjoint,
        so mutating one group's rows while the other group's dispatched
        policy call is still in flight can never touch that call's input."""
        stacked = self.builders[0].stacked_shape()
        self._acting = np.zeros((self.n_envs,) + stacked,
                                self.builders[0].frame_dtype)
        for i, builder in enumerate(self.builders):
            builder.bind_acting_view(self._acting[i])

    def poll_msgs(self) -> list[dict]:
        from apex_tpu.actors.pool import drain_builder_chunks
        out = []
        for builder in self.builders:
            out.extend(drain_builder_chunks(builder))
        return out


class VectorDQNWorkerFamily(VectorChunkFamilyBase):
    """B-env DQN acting/recording: the vector counterpart of
    :class:`apex_tpu.actors.pool.DQNWorkerFamily`."""

    supports_remote = True      # half-groups can ship to the infer server

    def __init__(self, cfg: ApexConfig, model_spec: dict, seeds,
                 slot_ids, epsilons, chunk_transitions: int):
        from apex_tpu.envs.registry import unstacked_env_spec
        from apex_tpu.models import make_q_network
        from apex_tpu.models.dueling import make_policy_fn
        from apex_tpu.replay.frame_chunks import FrameChunkBuilder

        super().__init__(cfg, seeds, slot_ids, epsilons)
        frame_shape, frame_dtype, frame_stack = unstacked_env_spec(
            self.envs[0], cfg.env)
        self.policy = self._grouped_policy(
            make_policy_fn(make_q_network(model_spec)))
        self.builders = [
            FrameChunkBuilder(
                cfg.learner.n_steps, cfg.learner.gamma, frame_stack,
                frame_shape, chunk_transitions=chunk_transitions,
                frame_dtype=frame_dtype)
            for _ in range(self.n_envs)
        ]
        self._bind_acting_buffer()

    def _policy_group(self, params, sl: slice, eps, key, group: int):
        if self.infer is not None:
            # remote policy: ship this half-group's stacked obs + ladder
            # slice + RAW step key + group id; fold_in happens server-
            # side in the same program the local jit runs.  The fallback
            # closure reads the SAME acting-buffer rows it shipped —
            # those rows only mutate in _step_group, which runs strictly
            # after this group's materialize, so remote timeout or not,
            # the inputs (hence outputs) are bit-identical.
            return self.infer.submit(
                self._acting[sl], np.asarray(eps), key, group,
                # apexlint: disable=J004 -- remote and fallback run the SAME fold_in(key, group) program; exactly one result is consumed, so the draw is used once
                fallback=lambda: self.policy(params, self._acting[sl],
                                             eps, key, group))
        return self.policy(params, self._acting[sl], eps, key, group)

    def _step_group(self, sl: slice, host: tuple, stats: list) -> None:
        actions, q = host
        for j, i in enumerate(range(sl.start, sl.stop)):
            a = int(actions[j])
            next_obs, reward, term, trunc, _ = self.envs[i].step(a)
            self.builders[i].add_step(a, float(reward), q[j], next_obs,
                                      bool(term), bool(trunc))
            self._finish_step(i, float(reward), bool(term or trunc), stats)


def _timing_stat(actor_id: int, family, steps_window: int):
    """One :class:`~apex_tpu.actors.pool.ActorTimingStat` from the family's
    phase/gap timers, resetting the phase window (``dropped_stats`` is
    stamped by the put loop, like every stat)."""
    from apex_tpu.actors.pool import ActorTimingStat

    w = family.phase.window(reset=True)
    fr = w["fracs"]
    return ActorTimingStat(
        actor_id=actor_id,
        frames_per_sec=round(steps_window * family.n_envs / w["wall_s"], 1),
        policy_wait_frac=round(fr.get("policy_wait", 0.0), 4),
        env_step_frac=round(fr.get("env_step", 0.0), 4),
        drain_frac=round(fr.get("drain", 0.0), 4),
        dispatch_gap_ms_p50=family.gap.snapshot()["dispatch_gap_ms_p50"],
        vector_steps=steps_window,
        double_buffer=bool(getattr(family, "double_buffer", False)))


def vector_worker_loop(actor_id: int, cfg: ApexConfig, family, chunk_queue,
                       param_queue, stat_queue, stop_event) -> None:
    """Vector counterpart of :func:`apex_tpu.actors.pool.worker_loop`: the
    same lifecycle (interruptible first-publish wait, CONFLATE param polls,
    chunk backpressure, clean shutdown) over B env slots, plus the
    actor-plane observability cadence (drain-phase timing and the periodic
    :class:`~apex_tpu.actors.pool.ActorTimingStat`)."""
    import jax

    from apex_tpu.fleet.heartbeat import HeartbeatEmitter
    from apex_tpu.obs import spans as obs_spans
    from apex_tpu.obs.trace import get_ring, set_process_label

    from apex_tpu.tenancy import namespace as tenancy_ns

    # tenant-qualified identity (PR 13): the worker's beats must agree
    # with the role-level wire identity (park heartbeats, chunk-arrival
    # liveness) or a tenant's actor shows up TWICE in its registry;
    # the default tenant qualifies to the bare name
    identity = tenancy_ns.qualify(tenancy_ns.current_tenant(),
                                  f"actor-{actor_id}")
    set_process_label(identity)
    ring = get_ring()
    # attach the trace ring to the family's existing timers: every
    # policy-wait/env-step/drain phase and every dispatch gap becomes a
    # trace event on this role's track (bounded, host-only)
    family.phase.ring = ring
    family.phase.track = "actor-phases"
    family.gap.ring = ring
    family.gap.track = "actor-dispatch"

    key = jax.random.key(family.seeds[0])
    beat = HeartbeatEmitter(
        identity, role="actor",
        interval_s=cfg.comms.heartbeat_interval_s,
        counters_fn=getattr(chunk_queue, "wire_counters", None),
        park_fn=getattr(param_queue, "park_state", None),
        # remote-policy health (fallback count, round-trip percentiles)
        # rides the same beats the registry already consumes
        gauges_fn=(family.infer.gauges if family.infer is not None
                   else None))
    version, params = 0, None
    while True:                                  # block for first publish
        if stop_event.is_set():
            family.close()
            return
        hb = beat.maybe_beat(version)
        if hb is not None:
            try:
                stat_queue.put_nowait(hb)
            except queue_lib.Full:
                pass
        try:
            version, params = param_queue.get(timeout=0.5)
            break
        except queue_lib.Empty:
            continue

    # poll cadence in VECTOR steps so staleness in env frames matches the
    # scalar worker's update_interval
    poll_every = max(1, math.ceil(cfg.actor.update_interval / family.n_envs))
    timing_every = max(0, int(getattr(cfg.actor, "timing_interval", 0)))
    steps_since_poll = 0
    vec_steps = 0
    dropped = 0         # stats lost to a full queue, carried on the next
    #                     successful put (auditably lossy, not silently)
    family.reset_all()
    family.phase.window(reset=True)   # timing windows start at the loop,
    #                                   not at family construction

    while not stop_event.is_set():
        steps_since_poll += 1
        if steps_since_poll >= poll_every:
            steps_since_poll = 0
            try:
                while True:                      # keep only the newest
                    version, params = param_queue.get_nowait()
            except queue_lib.Empty:
                pass

        key, akey = jax.random.split(key)
        stats = list(family.step_all(params, akey))
        vec_steps += 1
        beat.tick(family.n_envs)
        hb = beat.maybe_beat(version)
        if hb is not None:
            stats.append(hb)      # rides the stat put loop like every stat
        if timing_every and vec_steps % timing_every == 0:
            stats.append(_timing_stat(actor_id, family, timing_every))
        for stat in stats:
            if hasattr(stat, "param_version"):
                stat.param_version = version
            stat.dropped_stats = dropped
            try:
                stat_queue.put_nowait(stat)
                dropped = 0
            except queue_lib.Full:
                dropped += 1

        with family.phase.phase("drain"):
            for msg in family.poll_msgs():
                beat.note_chunk()
                obs_spans.mark_send(msg, version)
                chunk_queue.put(("chunk", actor_id, msg))  # blocks when full

    family.close()


def worker_slots(cfg: ApexConfig, actor_id: int):
    """Pure slot derivation for one vector worker: ``(slot_ids, seeds,
    epsilons)``.  The ladder spans the WHOLE fleet
    (``n_actors * n_envs_per_actor`` slots) and worker ``i`` owns the
    contiguous band ``[i*B, (i+1)*B)`` — seeds match what a fleet of scalar
    workers with those global ids would use."""
    from apex_tpu.actors.pool import actor_epsilons

    b = cfg.actor.n_envs_per_actor
    total = cfg.actor.n_actors * b
    ladder = actor_epsilons(total, cfg.actor.eps_base, cfg.actor.eps_alpha)
    slot_ids = list(range(actor_id * b, (actor_id + 1) * b))
    seeds = [cfg.env.seed + 1000 * (s + 1) for s in slot_ids]
    return slot_ids, seeds, ladder[slot_ids]


def vector_worker_main(actor_id: int, cfg: ApexConfig, model_spec: dict,
                       chunk_queue, param_queue, stat_queue, stop_event,
                       epsilon: float, chunk_transitions: int) -> None:
    """Process body wired through :class:`~apex_tpu.actors.pool.ActorPool`'s
    scalar ``worker_fn`` signature: ``epsilon`` is ignored — the family
    re-derives its slots' epsilons from the GLOBAL ladder
    (:func:`worker_slots`) so the fleet's exploration spectrum is identical
    whether slots are processes or vector lanes."""
    slot_ids, seeds, epsilons = worker_slots(cfg, actor_id)
    family = VectorDQNWorkerFamily(
        cfg, model_spec, seeds=seeds, slot_ids=slot_ids, epsilons=epsilons,
        chunk_transitions=chunk_transitions)
    if getattr(cfg.actor, "remote_policy", False):
        # centralized inference: the half-group policy calls ship to this
        # worker's home infer shard (identity-hashed — serving/shard.py;
        # one shard IS the PR 9 single server); the family's local jit
        # stays as the fallback
        from apex_tpu.serving.shard import make_infer_client
        family.attach_infer(make_infer_client(cfg.comms,
                                              f"actor-{actor_id}"))
    vector_worker_loop(actor_id, cfg, family, chunk_queue, param_queue,
                       stat_queue, stop_event)


vector_worker_main.is_vector = True     # ActorPool guard marker
