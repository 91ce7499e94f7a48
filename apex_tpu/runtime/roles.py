"""Process roles for the multi-host topology (reference L6 role scripts).

The reference runs four role scripts — ``origin_repo/{learner,actor,replay,
eval}.py`` — wired by env vars (``actor.py:18-25``).  By default the replay
role is dissolved into the learner (HBM-resident replay, see
:mod:`apex_tpu.runtime.transport`); ``comms.replay_shards > 0`` restores it
as a sharded standalone plane (:mod:`apex_tpu.replay_service` — its role
entry point lives there as ``run_replay_shard``, dispatched by the CLI).
The three roles here:

* :func:`run_learner` — the standard :class:`ApexTrainer` driving a
  socket-backed :class:`RemotePool`: identical fused learner, chunks arrive
  over TCP instead of mp.Queue.
* :func:`run_actor` — the SAME exploration body as the in-host pool workers
  (``apex_tpu.actors.pool._worker_main``), with the mp queues swapped for
  socket adapters: SUB(CONFLATE) params, DEALER chunks with the credit
  window, stats piggybacked.  One body, two transports — the reference
  maintains two near-copies (``batchrecorder.py`` vs ``actor.py``).
* :func:`run_evaluator` — continuous greedy evaluation on the UNCLIPPED env,
  streaming params without ever pausing the learner
  (``origin_repo/eval.py:49-87``); scores are shipped to the learner's
  metric log as stats with negative ``actor_id``s.

Every role takes the shared :class:`~apex_tpu.config.ApexConfig` plus its
role identity — exactly the reference's single-argparse + env-var scheme
(``arguments.py:5-83``; :meth:`RoleIdentity.from_env`).
"""

from __future__ import annotations

import dataclasses
import queue as queue_lib
import threading

import numpy as np

from apex_tpu.config import ApexConfig, CommsConfig, RoleIdentity
from apex_tpu.runtime import codec as wire_codec
from apex_tpu.runtime import transport


# -- socket adapters with the mp.Queue interface ---------------------------

class _ParamQueueAdapter:
    """ParamSubscriber presented as the worker body's param queue.  The
    CONFLATE socket holds at most one (newest) message, so the body's
    drain-to-latest loop terminates after one hit.

    With a :class:`~apex_tpu.fleet.park.ParkController` attached, a stale
    param stream PARKS the worker right here — the loop is blocked inside
    its routine poll, env and chunk-builder state intact — until the
    rejoin race (barrier vs param stream) reattaches it."""

    def __init__(self, sub: transport.ParamSubscriber, park=None):
        self.sub = sub
        self.park = park

    def _got(self, got):
        if got is None:
            if self.park is not None and self.park.stale():
                got = self.park.park_and_rejoin(self.sub)
                if got is not None:
                    self.park.take_pending()    # consumed here, not twice
            if got is None:
                raise queue_lib.Empty
        elif self.park is not None:
            self.park.note_params()
        return got

    def get(self, timeout: float = 0.5):
        if self.park is not None:
            pending = self.park.take_pending()
            if pending is not None:
                return pending
        return self._got(self.sub.poll(int(timeout * 1000)))

    def get_nowait(self):
        if self.park is not None:
            pending = self.park.take_pending()
            if pending is not None:
                return pending
        return self._got(self.sub.poll(0))

    def park_state(self):
        """HeartbeatEmitter ``park_fn`` hook: (parked, rejoins)."""
        if self.park is None:
            return (False, 0)
        return self.park.park_state()


class _ChunkQueueAdapter:
    """ChunkSender presented as the worker body's chunk queue; ``put``
    blocks on the ack-credit window like a bounded mp.Queue blocks on
    depth.

    With a park controller attached, a WEDGED send (credit window
    exhausted with nothing draining) checks the param stream: a healthy
    backpressuring learner keeps publishing and the send just keeps
    waiting; a dead one parks the worker here, and the rejoin resets the
    credit window before this chunk re-sends."""

    def __init__(self, sender: transport.ChunkSender, stop_event,
                 park=None):
        self.sender = sender
        self.stop_event = stop_event
        self.park = park

    def put(self, item) -> None:
        _kind, _actor_id, msg = item
        if self.park is None:
            self.sender.send_chunk(msg, self.stop_event)
            return
        while not self.stop_event.is_set():
            if self.sender.send_chunk(msg, self.stop_event, max_wait_s=1.0):
                return
            # no credit for a full second: dead learner, withheld acks,
            # or just slow?  Count the retry (the chunk never hit the
            # wire — retrying is lossless), then park_and_rejoin probes
            # the param stream and only parks when it is stale too (the
            # rejoin stashes fresh params for the param adapter's next
            # poll and resets the credit window so this chunk can
            # re-send)
            note = getattr(self.sender, "note_resend", None)
            if note is not None:
                note()
            self.park.park_and_rejoin()

    def wire_counters(self) -> dict:
        """HeartbeatEmitter ``counters_fn`` hook."""
        return {"chunks_sent": self.sender.chunks_sent,
                "acks_received": self.sender.acks_received,
                "resends": getattr(self.sender, "resends", 0),
                "rerouted": getattr(self.sender, "rerouted", 0)}

    def wire_gauges(self) -> dict:
        """HeartbeatEmitter ``gauges_fn`` hook: the sender's codec byte
        counters + realized compression ratio (runtime/codec.py)."""
        fn = getattr(self.sender, "wire_gauges", None)
        return fn() if callable(fn) else {}


class _StatQueueAdapter:
    def __init__(self, sender: transport.ChunkSender):
        self.sender = sender

    def put_nowait(self, stat) -> None:
        self.sender.send_stat(stat)


# -- roles -----------------------------------------------------------------

def run_learner(cfg: ApexConfig, n_peers: int, total_steps: int,
                max_seconds: float = 3600.0, family: str = "dqn",
                logdir: str | None = None, verbose: bool = False,
                checkpoint_dir: str | None = None, train_ratio=None,
                min_train_ratio=None, queue_depth: int = 64,
                barrier_timeout_s: float = 120.0, restore: bool = False,
                rollout: str = "host", rollout_len: int | None = None,
                steps_per_dispatch: int = 4):
    """Learner role: barrier -> publish -> fused ingest+train loop.

    ``n_peers`` = actors + evaluators expected at the startup barrier
    (``learner.py:48-49``).  Returns the trainer (params, metrics history).

    ``rollout="ondevice"`` co-locates an Anakin rollout engine with the
    learner (:mod:`apex_tpu.training.anakin`): the socket pool keeps
    serving any host actors/evaluators while sealed chunks ALSO stream
    from the fused on-device scan — params hand to the engine as device
    arrays, never leaving the accelerator.

    ``rollout="fused"`` goes further (:mod:`apex_tpu.ondevice`): the
    whole rollout -> ingest -> sample -> train -> write-back cycle runs
    as ONE jitted program per dispatch; the socket pool keeps serving
    evaluators/status, host-actor chunks absorb between dispatches, and
    the host wakes once per ``steps_per_dispatch`` macro steps.
    """
    pool = transport.RemotePool(cfg.comms, n_peers, queue_depth=queue_depth,
                                barrier_timeout_s=barrier_timeout_s)
    if rollout == "fused":
        if family != "dqn":
            pool.cleanup()
            raise NotImplementedError(
                f"--rollout fused currently serves the dqn family only "
                f"(got {family!r}) — aql/r2d2 slot in behind the same "
                f"scan hooks (ROADMAP.md)")
        if cfg.comms.replay_shards > 0:
            pool.cleanup()
            raise ValueError(
                "--rollout fused owns replay on-device — run with "
                "--replay-shards 0 (APEX_REPLAY_SHARDS=0); the shard "
                "fleet serves the host topologies")
        from apex_tpu.ondevice.fused import FusedApexTrainer
        try:
            # make_jax_env's ValueError names non-jittable env ids and
            # the dp divisibility guards name --n-envs-per-actor /
            # --batch-size vs --mesh-dp, all before train()
            trainer = FusedApexTrainer(
                cfg, logdir=logdir, verbose=verbose,
                checkpoint_dir=checkpoint_dir, train_ratio=train_ratio,
                min_train_ratio=min_train_ratio, pool=pool,
                rollout_len=rollout_len,
                steps_per_dispatch=steps_per_dispatch)
            if restore:
                trainer.restore()
        except BaseException:
            pool.cleanup()
            raise
        return trainer.train(total_steps=total_steps,
                             max_seconds=max_seconds)
    if rollout == "ondevice":
        if family != "dqn":
            pool.cleanup()
            raise NotImplementedError(
                f"--rollout ondevice currently serves the dqn family "
                f"only (got {family!r}) — aql/r2d2 stay on the host "
                f"pipeline (ROADMAP.md)")
        from apex_tpu.training.anakin import AnakinPool, make_anakin_engine
        try:
            # make_jax_env raises a ValueError naming non-jittable env ids
            engine = make_anakin_engine(cfg, rollout_len=rollout_len)
        except BaseException:
            pool.cleanup()
            raise
        pool = AnakinPool(cfg, engine, inner=pool)
    client = None
    if cfg.comms.replay_shards > 0:
        # sharded replay service: sampling lives in the shard fleet; the
        # learner pulls pre-sampled batches and ships write-backs.  The
        # chunk ROUTER above stays bound — it still carries stats,
        # heartbeats, and the actors' direct-ingest fallback chunks.
        if family != "dqn":
            pool.cleanup()
            raise NotImplementedError(
                f"--replay-shards currently serves the dqn family only "
                f"(got {family!r}) — aql/r2d2 stay on in-learner replay")
        from apex_tpu.replay_service.client import ReplayServiceClient
        client = ReplayServiceClient(cfg.comms)
    try:
        if family == "dqn":
            from apex_tpu.training.apex import ApexTrainer
            trainer = ApexTrainer(cfg, logdir=logdir, verbose=verbose,
                                  checkpoint_dir=checkpoint_dir,
                                  train_ratio=train_ratio,
                                  min_train_ratio=min_train_ratio,
                                  pool=pool)
        elif family == "aql":
            from apex_tpu.training.aql import AQLApexTrainer
            trainer = AQLApexTrainer(cfg, logdir=logdir, verbose=verbose,
                                     checkpoint_dir=checkpoint_dir,
                                     train_ratio=train_ratio,
                                     min_train_ratio=min_train_ratio,
                                     pool=pool)
        elif family == "r2d2":
            from apex_tpu.training.r2d2 import R2D2ApexTrainer
            trainer = R2D2ApexTrainer(cfg, logdir=logdir, verbose=verbose,
                                      checkpoint_dir=checkpoint_dir,
                                      train_ratio=train_ratio,
                                      min_train_ratio=min_train_ratio,
                                      pool=pool)
        else:
            raise ValueError(f"unknown family {family!r}")
        if restore:
            trainer.restore()        # newest checkpoint in checkpoint_dir
        trainer.replay_client = client
    except BaseException:
        # the pool binds its ROUTER at construction — unwind it if the
        # trainer never gets far enough for train()'s finally to run
        pool.cleanup()
        if client is not None:
            client.close()
        raise
    try:
        return trainer.train(total_steps=total_steps,
                             max_seconds=max_seconds)
    finally:
        if client is not None:
            client.close()


def _join_fleet(comms, name: str, stop_event,
                timeout_s: float) -> "transport.ParamSubscriber":
    """Shared actor/evaluator fleet-join: connect the param SUB first, then
    race the one-shot startup barrier against the param stream
    (``transport.barrier_wait`` rejoin contract) — a fresh fleet releases
    via the barrier, a supervisor-respawned peer rejoins within seconds on
    the first republish, and the learner's ``silent_peers`` report clears
    on its first chunk.  Returns the connected subscriber; raises (and
    closes it) when neither signal arrives."""
    sub = transport.ParamSubscriber(comms)
    if not transport.barrier_wait(comms, name, stop_event=stop_event,
                                  timeout_s=timeout_s, rejoin_sub=sub):
        sub.close()
        raise TimeoutError(f"{name}: startup barrier timed out and no "
                           f"params flowing (learner not running)")
    return sub


def run_actor(cfg: ApexConfig, identity: RoleIdentity,
              family: str = "dqn", stop_event=None,
              barrier_timeout_s: float = 120.0) -> None:
    """Actor role: barrier -> SUB params -> explore -> DEALER chunks.

    Epsilon comes from the fleet-wide ladder position
    (``actor.py:69``): ``eps_base ** (1 + id/(N-1) * eps_alpha)``.
    """
    from apex_tpu.actors.pool import _worker_main, actor_epsilons

    from apex_tpu.fleet.chaos import maybe_wrap_sender
    from apex_tpu.fleet.park import ParkController

    if getattr(cfg.actor, "remote_policy", False) and family != "dqn":
        # guard BEFORE the fleet join: failing loud beats a fleet
        # silently acting on local policies while the operator believes
        # inference is centralized — and beats burning the barrier
        # timeout to say so
        raise NotImplementedError(
            f"--remote-policy currently serves the dqn family only "
            f"(got {family!r}) — aql/r2d2 actors stay on local "
            f"policies (ROADMAP.md)")
    stop_event = stop_event or threading.Event()
    # tenant-qualified wire identity (PR 13): two tenants' actor-0
    # processes sharing one replay/infer plane must never collide on a
    # ROUTER identity, and the tenant prefix is what partitions their
    # chunk ids; the default tenant qualifies to the bare name
    from apex_tpu.tenancy import namespace as tenancy_ns
    name = tenancy_ns.qualify(tenancy_ns.current_tenant(),
                              f"actor-{identity.actor_id}")
    comms = _with_ips(cfg.comms, identity)
    sub = _join_fleet(comms, name, stop_event, barrier_timeout_s)
    eps = actor_epsilons(identity.n_actors, cfg.actor.eps_base,
                         cfg.actor.eps_alpha)[identity.actor_id]

    sender = transport.ChunkSender(comms, name)
    if comms.replay_shards > 0:
        # sharded replay service: chunks hash to shard sockets; the
        # learner channel just built stays the stat/heartbeat pipe, the
        # park-liveness probe, and the direct-ingest fallback
        from apex_tpu.replay_service.sender import ShardedChunkSender
        sender = ShardedChunkSender(comms, name, direct=sender)
    sender = maybe_wrap_sender(sender, name)
    park = ParkController(comms, name, stop_event, sub=sub, sender=sender)
    # param-delta recovery: a delta this subscriber cannot apply (missed
    # keyframe under CONFLATE, checksum mismatch) asks the trainer for a
    # dense publish over the stat plane (best-effort, like any stat)
    sub.on_mismatch = lambda v: sender.send_stat(
        wire_codec.KeyframeRequest(name, int(v)))
    chunk_arg = cfg.actor.send_interval
    if family == "dqn":
        from apex_tpu.training.apex import dqn_model_spec
        worker_fn, model_spec = _worker_main, dqn_model_spec(cfg)
        if cfg.actor.n_envs_per_actor > 1 or cfg.actor.remote_policy:
            # remote policy lives on the vector family's half-group
            # hooks, so it forces the vector body even at B=1 (one
            # group, serial interleave — still one request per step)
            from apex_tpu.actors.vector import vector_worker_main
            worker_fn = vector_worker_main
            # the vector family re-derives its slots' epsilons from the
            # ladder over cfg.actor.n_actors * n_envs_per_actor — align the
            # config with the FLEET size the deploy scripts put in the
            # identity (actor.py:18-25)
            cfg = cfg.replace(actor=dataclasses.replace(
                cfg.actor, n_actors=identity.n_actors))
    elif family == "aql":
        from apex_tpu.actors.aql import aql_worker_main
        from apex_tpu.envs.registry import make_env
        from apex_tpu.training.aql import aql_model_spec
        probe = make_env(cfg.env.env_id, cfg.env, seed=0)
        worker_fn, model_spec = aql_worker_main, aql_model_spec(cfg, probe)
        probe.close()
        if cfg.actor.n_envs_per_actor > 1:
            from apex_tpu.actors.aql import vector_aql_worker_main
            worker_fn = vector_aql_worker_main
            cfg = cfg.replace(actor=dataclasses.replace(
                cfg.actor, n_actors=identity.n_actors))
    elif family == "r2d2":
        from apex_tpu.actors.r2d2 import r2d2_worker_main
        from apex_tpu.training.r2d2 import r2d2_model_spec
        model_spec = r2d2_model_spec(cfg)
        # single frames (the LSTM is the memory); the sequence group per
        # message is the one shared cfg.r2d2 constant, so actor messages
        # and the learner's expected shapes can't drift
        cfg = cfg.replace(env=dataclasses.replace(cfg.env, frame_stack=1))
        worker_fn, chunk_arg = r2d2_worker_main, cfg.r2d2.sequence_group
        if cfg.actor.n_envs_per_actor > 1:
            from apex_tpu.actors.r2d2 import vector_r2d2_worker_main
            worker_fn = vector_r2d2_worker_main
            cfg = cfg.replace(actor=dataclasses.replace(
                cfg.actor, n_actors=identity.n_actors))
    else:
        raise ValueError(f"unknown family {family!r}")
    try:
        worker_fn(identity.actor_id, cfg, model_spec,
                  _ChunkQueueAdapter(sender, stop_event, park=park),
                  _ParamQueueAdapter(sub, park=park),
                  _StatQueueAdapter(sender),
                  stop_event, float(eps), chunk_arg)
    finally:
        sender.close()
        sub.close()


def run_loadgen(cfg: ApexConfig, identity: RoleIdentity,
                family: str = "dqn", stop_event=None,
                max_seconds: float = 86400.0,
                rollout_len: int | None = None) -> dict:
    """Loadgen role: the on-device Anakin rollout engine as a standalone
    traffic source (:mod:`apex_tpu.training.anakin`).

    Subscribes the param stream like an actor, then ships device-rate
    sealed chunks down the normal chunk plane — hashed to the replay
    shards when ``comms.replay_shards > 0``, learner-direct otherwise —
    with heartbeats (role ``loadgen``) and episode stats riding the stat
    channel, so the registry/status/chaos planes cover it for free.  The
    credit window is the only throttle: this role exists to SATURATE the
    ingest path for honest load measurement, where the CI box's host
    actors top out two orders of magnitude lower.  Skips the startup
    barrier (useful from the first publish, launch order free).  Returns
    the counter dict for callers/tests."""
    import time as time_lib

    from apex_tpu.fleet.chaos import maybe_wrap_sender
    from apex_tpu.fleet.heartbeat import HeartbeatEmitter
    from apex_tpu.obs import spans as obs_spans
    from apex_tpu.obs.trace import set_process_label
    from apex_tpu.training.anakin import make_anakin_engine

    if family != "dqn":
        raise NotImplementedError(
            f"--role loadgen currently serves the dqn family only "
            f"(got {family!r}) — see ROADMAP.md")
    stop_event = stop_event or threading.Event()
    from apex_tpu.tenancy import namespace as tenancy_ns
    name = tenancy_ns.qualify(tenancy_ns.current_tenant(),
                              f"loadgen-{identity.actor_id}")
    set_process_label(name)
    comms = _with_ips(cfg.comms, identity)
    # engine first: make_jax_env's non-jittable ValueError must fire
    # before any socket waits
    engine = make_anakin_engine(
        cfg, rollout_len=rollout_len,
        n_envs=max(1, cfg.actor.n_envs_per_actor),
        slot_band=identity.actor_id,
        total_slots=max(identity.n_actors, 1)
        * max(1, cfg.actor.n_envs_per_actor))

    sub = transport.ParamSubscriber(comms)
    sender = transport.ChunkSender(comms, name)
    if comms.replay_shards > 0:
        from apex_tpu.replay_service.sender import ShardedChunkSender
        sender = ShardedChunkSender(comms, name, direct=sender)
    sender = maybe_wrap_sender(sender, name)
    sub.on_mismatch = lambda v: sender.send_stat(
        wire_codec.KeyframeRequest(name, int(v)))
    beat = HeartbeatEmitter(
        name, role="loadgen", interval_s=comms.heartbeat_interval_s,
        counters_fn=(lambda: {
            "chunks_sent": getattr(sender, "chunks_sent", 0),
            "acks_received": getattr(sender, "acks_received", 0),
            "resends": getattr(sender, "resends", 0),
            "rerouted": getattr(sender, "rerouted", 0)}),
        gauges_fn=(lambda: {
            "ondevice_chunks": engine.chunks,
            "ondevice_frames": engine.frames,
            "ondevice_dispatches": engine.dispatches,
            **(sender.wire_gauges()
               if hasattr(sender, "wire_gauges") else {})}))
    try:
        got = sub.wait_first(stop_event)
        if got is None:
            return {"chunks": 0, "frames": 0, "dispatches": 0}
        version, params = got
        t_end = time_lib.monotonic() + max_seconds
        while not stop_event.is_set() and time_lib.monotonic() < t_end:
            fresh = sub.poll(0)
            if fresh is not None:
                version, params = fresh
            msgs, stats = engine.rollout(params)
            beat.tick(engine.T * engine.B)
            for stat in stats:
                stat.param_version = version
                sender.send_stat(stat)
            hb = beat.maybe_beat(version)
            if hb is not None:
                sender.send_stat(hb)
            for msg in msgs:
                obs_spans.mark_send(msg, version)
                sender.send_chunk(msg, stop_event)   # credit backpressure
        return {"chunks": engine.chunks, "frames": engine.frames,
                "dispatches": engine.dispatches}
    finally:
        sender.close()
        sub.close()


def run_evaluator(cfg: ApexConfig, identity: RoleIdentity | None = None,
                  family: str = "dqn", stop_event=None, episodes: int = 0,
                  max_steps: int = 10_000, logdir: str | None = None,
                  verbose: bool = False,
                  barrier_timeout_s: float = 120.0) -> list[float]:
    """Evaluator role (``eval.py:49-87``): greedy episodes on the unclipped
    env, refreshing params per episode, forever (or ``episodes`` if > 0).
    Scores are logged locally AND shipped to the learner (actor_id = -(id+1))."""
    import uuid

    from apex_tpu.envs.registry import make_eval_env
    from apex_tpu.utils.metrics import MetricLogger

    stop_event = stop_event or threading.Event()
    identity = identity or RoleIdentity(role="evaluator")
    if family == "r2d2":        # single frames: the LSTM is the memory
        cfg = cfg.replace(env=dataclasses.replace(cfg.env, frame_stack=1))
    # unique per-evaluator socket/barrier identity: duplicate identities
    # dedup at the barrier (deadlock) and misroute on the ROUTER.  The
    # random suffix makes N default-launched evaluators safe — unlike
    # actors, evaluator ids carry no semantics (no epsilon ladder slot)
    from apex_tpu.fleet.chaos import maybe_wrap_sender
    from apex_tpu.fleet.park import ParkController

    from apex_tpu.tenancy import namespace as tenancy_ns
    name = tenancy_ns.qualify(
        tenancy_ns.current_tenant(),
        f"evaluator-{identity.actor_id}-{uuid.uuid4().hex[:6]}")
    comms = _with_ips(cfg.comms, identity)
    sub = _join_fleet(comms, name, stop_event, barrier_timeout_s)

    sender = maybe_wrap_sender(transport.ChunkSender(comms, name), name)
    park = ParkController(comms, name, stop_event, sub=sub, sender=sender,
                          role="evaluator")
    sub.on_mismatch = lambda v: sender.send_stat(
        wire_codec.KeyframeRequest(name, int(v)))
    log = MetricLogger("evaluator", logdir, verbose=verbose)
    env = make_eval_env(cfg.env.env_id, cfg.env, seed=cfg.env.seed + 7777)
    try:
        return _evaluator_body(cfg, identity, family, stop_event, episodes,
                               max_steps, sub, sender, log, env, park=park)
    finally:
        sender.close()
        sub.close()
        env.close()


def _evaluator_body(cfg, identity, family, stop_event, episodes, max_steps,
                    sub, sender, log, env, park=None) -> list[float]:
    import time

    import jax
    import jax.numpy as jnp

    from apex_tpu.actors.pool import EpisodeStat
    from apex_tpu.fleet.chaos import chaos_from_env
    from apex_tpu.fleet.heartbeat import HeartbeatEmitter
    from apex_tpu.obs.trace import get_ring, set_process_label

    # evaluators were the one role without a trace ring: label the
    # process by its fleet identity (obs.merge joins it against the
    # registry's clock offsets) and record episode/param-refresh events
    set_process_label(park.identity if park is not None
                      else f"evaluator-{identity.actor_id}")
    ring = get_ring()

    reset_act = None            # recurrent families override per episode
    if family == "dqn":
        from apex_tpu.models import make_q_network
        from apex_tpu.models.dueling import make_policy_fn
        from apex_tpu.training.apex import dqn_model_spec
        model = make_q_network(dqn_model_spec(cfg))
        policy = jax.jit(make_policy_fn(model))

        def act(params, obs, key):
            a, _ = policy(params, obs[None], jnp.float32(0.0), key)
            return int(a[0])
    elif family == "aql":
        from apex_tpu.envs.registry import make_env
        from apex_tpu.models.aql import AQLNetwork, make_aql_policy_fn
        from apex_tpu.training.aql import aql_model_spec
        probe = make_env(cfg.env.env_id, cfg.env, seed=0)
        model = AQLNetwork(**aql_model_spec(cfg, probe),
                           noisy_deterministic=True)
        probe.close()
        policy = jax.jit(make_aql_policy_fn(model))

        def act(params, obs, key):
            a, _, _, _ = policy(params, obs[None], jnp.float32(0.0), key)
            return np.asarray(a[0])
    elif family == "r2d2":
        from apex_tpu.models.recurrent import (RecurrentDuelingDQN,
                                               make_recurrent_policy_fn)
        from apex_tpu.training.r2d2 import r2d2_model_spec
        model = RecurrentDuelingDQN(**r2d2_model_spec(cfg))
        policy = jax.jit(make_recurrent_policy_fn(model))
        carry_box = [model.initial_state(1)]

        def act(params, obs, key):
            a, _, carry_box[0] = policy(params, obs[None], carry_box[0],
                                        jnp.float32(0.0), key)
            return int(a[0])

        def reset_act():
            carry_box[0] = model.initial_state(1)
    else:
        raise ValueError(f"unknown family {family!r}")

    got = sub.wait_first(stop_event)
    if got is None:
        return []
    version, params = got
    if park is not None:
        park.note_params()
    # eval-ladder scores ride the heartbeat gauges: each evaluator IS
    # one band (its actor_id slot — N evaluators span the eval ladder
    # the way actor ids span the epsilon ladder), and its recent-window
    # mean + episode count reach the registry/status/Prometheus surface
    # on the beats it already sends — so the SLO engine (and the future
    # canary/promotion gate) can objective on MODEL QUALITY
    # (obs/slo.py `eval_score`), not just plumbing.
    from collections import deque as _deque
    recent_scores: _deque = _deque(maxlen=16)
    scores: list[float] = []

    def _eval_gauges() -> dict:
        return {
            "eval_band": identity.actor_id,
            "eval_episodes": len(scores),
            "eval_score_last": (round(scores[-1], 3) if scores else 0.0),
            "eval_score_mean": (round(sum(recent_scores)
                                      / len(recent_scores), 3)
                                if recent_scores else 0.0)}

    emitter = HeartbeatEmitter(
        park.identity if park is not None
        else f"evaluator-{identity.actor_id}",
        role="evaluator", interval_s=cfg.comms.heartbeat_interval_s,
        counters_fn=(lambda: {
            "chunks_sent": getattr(sender, "chunks_sent", 0),
            "acks_received": getattr(sender, "acks_received", 0)}),
        park_fn=park.park_state if park is not None else None,
        gauges_fn=_eval_gauges)
    # chaos score_bias (serving-tier canary drills): a scheduled
    # model-quality regression — after after_s of this run, every
    # reported score shifts by delta, so the eval-ladder gauges and the
    # eval_score SLO see a degraded model on a deterministic schedule
    chaos = chaos_from_env()
    plan = (chaos.plan_for(emitter.identity) if chaos is not None
            else None)
    bias_t0 = time.monotonic()
    key = jax.random.key(cfg.env.seed + 31337)
    ep = 0
    while not stop_event.is_set() and (episodes <= 0 or ep < episodes):
        obs, _ = env.reset()
        if reset_act is not None:       # recurrent: fresh carry per episode
            reset_act()
        total, done, steps = 0.0, False, 0
        ep_t0 = time.perf_counter()
        while not done and steps < max_steps and not stop_event.is_set():
            key, k = jax.random.split(key)
            obs, r, term, trunc, _ = env.step(act(params, np.asarray(obs), k))
            total += float(r)
            done = term or trunc
            steps += 1
            emitter.tick()
            hb = emitter.maybe_beat(version)
            if hb is not None:
                sender.send_stat(hb)
        if (plan is not None and plan.score_bias_after_s is not None
                and time.monotonic() - bias_t0
                >= plan.score_bias_after_s):
            total += plan.score_bias_delta
        scores.append(total)
        recent_scores.append(total)
        ring.complete("episode", ep_t0, time.perf_counter() - ep_t0,
                      track="eval-episodes",
                      args={"reward": round(total, 3), "steps": steps,
                            "param_version": version})
        log.scalars({"episode_reward": total, "episode_length": steps,
                     "param_version": version}, ep)
        sender.send_stat(EpisodeStat(-(identity.actor_id + 1), total, steps,
                                     version))
        got = sub.poll(0)               # param refresh per episode
        if got is not None:
            version, params = got
            if park is not None:
                park.note_params()
            ring.instant("param_refresh", track="eval-episodes",
                         args={"version": version})
        elif park is not None and park.stale():
            # the stream died mid-run: park between episodes, resume on
            # the respawned learner's first publish
            got = park.park_and_rejoin()
            if got is not None:
                park.take_pending()
                version, params = got
        ep += 1
    return scores


def _with_ips(comms: CommsConfig, identity: RoleIdentity) -> CommsConfig:
    """An EXPLICIT learner/replay IP on the role identity wins over the
    config (``actor.py:18-25`` env-var pattern); a default-constructed
    identity must not stomp a configured ``comms.learner_ip`` (or
    ``replay_ip``) with localhost."""
    default = RoleIdentity()
    overrides = {}
    if identity.learner_ip != default.learner_ip:
        overrides["learner_ip"] = identity.learner_ip
    if identity.replay_ip != default.replay_ip:
        overrides["replay_ip"] = identity.replay_ip
    return dataclasses.replace(comms, **overrides) if overrides else comms
