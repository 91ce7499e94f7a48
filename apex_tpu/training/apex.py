"""Ape-X driver: actor pool + fused TPU learner, actually concurrent.

Capability parity with the reference ``ApeX.py`` (C11) and its
``origin_repo`` flagship topology, on one host:

* N worker processes explore continuously with the epsilon ladder and ship
  fixed-shape frame chunks with precomputed priorities
  (:mod:`apex_tpu.actors.pool`).
* The learner ingests chunks into the HBM frame-pool replay and runs the
  fused sample/loss/update/priority step — ingest+train fuse into one XLA
  program whenever a chunk is pending.
* Params publish version-stamped every ``publish_interval`` learner steps
  with a wall-clock floor (``publish_min_seconds``) — the reference's
  every-25-steps cadence (``learner.py:169-170``) assumed an 11-steps/s
  learner; at TPU step rates a pure step cadence would saturate the host
  queues.
* Warmup gate: no training until ``replay.warmup`` transitions are resident
  (``arguments.py:47-48``, ``replay.py:104-106``).

The reference's ``ApeX.py`` accidentally ran acting and learning
sequentially (``Process(target=test.sampling_data())`` calls the method
eagerly — ``ApeX.py:94-97``); here they genuinely overlap: workers are
independent processes, and the learner thread blocks only on device results.
"""

from __future__ import annotations

import collections
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.actors.pool import ActorPool, ActorTimingStat
from apex_tpu.config import ApexConfig
from apex_tpu.fleet.heartbeat import Heartbeat
from apex_tpu.fleet.registry import FleetRegistry
from apex_tpu.obs import spans as obs_spans
from apex_tpu.envs.registry import (make_env, make_eval_env, num_actions,
                                    unstacked_env_spec)
from apex_tpu.models import (learner_apply_fn, make_q_network, note_torso,
                             q_model_spec)
from apex_tpu.models.dueling import make_policy_fn
from apex_tpu.ops.losses import make_optimizer
from apex_tpu.replay.base import check_hbm_budget
from apex_tpu.replay.frame_pool import FramePoolReplay
from apex_tpu.population.controller import PopulationStat
from apex_tpu.runtime.codec import KeyframeRequest
from apex_tpu.serving.deploy import ServingStat
from apex_tpu.tenancy.scheduler import TenancyStat
from apex_tpu.training.checkpoint import (CheckpointableTrainer,
                                          Checkpointer)
from apex_tpu.training.ingest_pipeline import IngestPipeline, KeyBlocks
from apex_tpu.training.learner import LearnerCore
from apex_tpu.training.state import create_train_state
from apex_tpu.utils.metrics import MetricLogger, RateCounter
from apex_tpu.utils.seeding import set_global_seeds


def dqn_env_specs(cfg: ApexConfig):
    """(model_spec, frame_shape, frame_dtype, frame_stack) from a probe env
    — shared by the driver, the multi-host actor role, and the evaluator."""
    probe = make_env(cfg.env.env_id, cfg.env, seed=cfg.env.seed,
                     stack_frames=False)
    frame_shape, frame_dtype, frame_stack = unstacked_env_spec(probe, cfg.env)
    model_spec = q_model_spec(
        cfg.learner.torso,
        num_actions=num_actions(probe),
        obs_is_image=len(frame_shape) == 3,
        compute_dtype=jnp.dtype(cfg.learner.compute_dtype),
        scale_uint8=np.dtype(frame_dtype) == np.uint8)
    probe.close()
    return model_spec, frame_shape, frame_dtype, frame_stack


def dqn_model_spec(cfg: ApexConfig) -> dict:
    return dqn_env_specs(cfg)[0]


class ConcurrentTrainer(CheckpointableTrainer):
    """The concurrent learner loop shared by every distributed family
    (Ape-X DQN, Ape-X AQL): drain worker chunk messages, fuse ingest+train,
    enforce the replay-ratio band, publish versioned params, checkpoint.

    Chunk messages are family-agnostic dicts:
    ``{"payload": <ingest pytree>, "priorities": f32[K], "n_trans": int}`` —
    the payload goes straight into the family's fused step.

    Subclasses construct: ``cfg, key, pool, replay, replay_state,
    train_state, core, _fused, _train, _ingest, log, steps_rate,
    frames_rate, ingested, param_version, checkpointer`` and the replay-ratio
    knobs (see :class:`ApexTrainer` for the reference wiring).
    """

    # scan dispatch: ApexTrainer sets these when config.scan_steps > 1 on
    # a single-shard DQN learner; None = chunk-at-a-time everywhere else
    _multi = None
    scan_steps = 1
    scan_dispatches = 0      # K-step dispatches taken (observability)
    # async ingest pipeline (training/ingest_pipeline.py): live only
    # inside train() — single-shard (chunk-granular) and dp>1
    # (round-robin-group-granular, pre-placed per-chip keys) alike;
    # _ingest_multi is the scan-of-ingests dispatch for slots the
    # replay-ratio cap says to absorb without training
    _pipeline = None
    _pipeline_base = 0       # self.ingested when the pipeline started
    _ingest_multi = None
    _dispatch_gap = None
    _pipeline_last_stats = None
    # host spans of the hot loop (_span / _dispatch): the process's trace
    # ring, the track the loop's events go to, and the pass under way
    _ring = None
    _loop_track = "learner-hot-loop"
    # the newest update's metrics the loop has looked at (None before the
    # first), the losses of the updates still enqueued (_bound_in_flight)
    # and the model counters on their way to the ring (_note_model_stats:
    # (step, scalars) whose values the device has not produced yet)
    _stats_last = None
    _stats_pending = None
    _in_flight = None
    max_steps_in_flight = 2
    _pass = 0
    _pass_kind = "idle"
    # the per-pass operands no pass launches a program for: the key
    # chain's blocks (_dispatch_key; made on first use, kept across
    # train() calls) and the device scalar of the host float beta() last
    # returned (_dispatch)
    _key_blocks = None
    _beta_host = None
    _beta_dev = None
    beta_puts = 0            # dispatches that transferred a new float
    beta_reused = 0          # dispatches that took the scalar held
    # checkpoint/log bookkeeping persists ACROSS train() calls: a driver
    # interleaving short train() bursts with eval must still hit its
    # save/log cadence (per-call resets would silence both whenever
    # interval > steps-per-call)
    _last_save = 0
    _last_log = 0
    # actor-plane observability: latest ActorTimingStat per worker (the
    # vector workers' periodic policy-wait/env-step/drain splits) and the
    # cumulative count of stats workers dropped on a full stat queue
    actor_timing: dict | None = None
    stat_drops = 0
    # fleet control plane (apex_tpu/fleet): the membership registry fed by
    # Heartbeats off the stat drain (+ message-arrival liveness on socket
    # pools), its REP status server (socket pools only), and where the
    # periodic fleet_summary.json lands
    fleet: FleetRegistry | None = None
    _fleet_status = None
    # obs plane (apex_tpu/obs): the learner-side span join — publish-time
    # ledger + frame-age-at-train / param-propagation-lag histograms +
    # sampled chunk-lineage trace events (persists across train() calls
    # like the checkpoint marks)
    _obs = None
    # sharded replay service (apex_tpu/replay_service): when a
    # ReplayServiceClient is attached, sampling lives in the shard fleet —
    # the loop consumes pre-sampled batches (pipeline "batch" slots),
    # trains via core.update_from_batch, and routes priority write-backs
    # to the owning shard.  The chunk path stays live as the direct-ingest
    # fallback (actors reroute to the learner when their shard wedges).
    replay_client = None
    _train_batch = None
    service_steps = 0        # train steps taken on shard-served batches
    # learner-epoch fencing (PR 8): bumped once per learner LIFE (restore
    # reads the predecessor's epoch from checkpoint meta and adds one),
    # stamped onto every param publish and replay write-back so parked
    # actors can tell a restarted learner from a stalled one and shards
    # can reject a dead learner's ghost write-backs
    learner_epoch = 1
    # registry reactions (PR 8): when >= relax_floor_dead_frac of the
    # actor fleet is DEAD, the replay-ratio floor relaxes (the surviving
    # actors must not be starved by a throughput target sized for the
    # full fleet) and restores as peers rejoin
    _floor_relaxed = False
    floor_relaxes = 0        # times the floor reaction engaged
    # fleet SLO engine (apex_tpu/obs/slo): declarative objectives judged
    # by multi-window burn rates on every health tick; alert states land
    # in fleet_summary.json / the status table / apex_slo_* Prometheus
    # rows, and the scale supervisor's --scale-signal slo keys off the
    # snapshot's severity.  Lazily built on the first health tick so
    # knob env twins set by a drill are honored.
    _slo = None
    # serving tier (apex_tpu/serving): the deployment controller's
    # latest snapshot, shipped as a ServingStat on the stat channel —
    # folded into fleet_summary.json ("serving" section), the status
    # table, the SLO signal space, and the apex_serving_* rows, so the
    # canary timeline survives the controller the way the registry
    # survives an actor
    serving_state: dict | None = None
    # multi-tenant plane (apex_tpu/tenancy): the placement controller's
    # latest snapshot off the stat channel — folded into
    # fleet_summary.json ("tenancy" section), the status table's
    # tenancy lines, and the apex_tenancy_* Prometheus rows
    tenancy_state: dict | None = None
    # population plane (apex_tpu/population): the PBT controller's
    # latest snapshot off the stat channel ("population" section /
    # status lines / apex_population_* rows), plus the learner-side
    # half — a bounded ctl command queue the status-server thread
    # enqueues into and the trainer thread drains on its health tick
    # (exploit = donor-checkpoint weight copy + epoch bump, explore =
    # live hyperparameter application), with the applied-command
    # evidence surfaced as metrics["population_ctl"]
    population_state: dict | None = None
    _ctl_queue = None
    _population_ctl: dict | None = None
    hparams_live: dict | None = None
    # episode-scalar log index for the stats drain (reset per train()
    # call; an attribute so the fused on-device loop shares the drain)
    _episode_idx = 0
    # when the stats drain last ran (monotonic): a gap longer than the
    # beat interval means nobody was watching the fleet — the hot loop
    # sat in a blocking dispatch, or the driver was between train()
    # calls — and the registry forgives it that span
    # (FleetRegistry.forgive)
    _last_drain = None

    # -- param plane -------------------------------------------------------

    def _publish(self) -> None:
        self.param_version += 1
        with self._span("publish_handoff", version=self.param_version):
            self._publish_params()

    def _publish_params(self) -> None:
        if self._obs is not None:
            # the join key of both policy lags: when, and at which
            # learner step, THIS version left
            self._obs.note_publish(self.param_version,
                                   self.steps_rate.total)
        if getattr(self.pool, "accepts_device_params", False):
            # co-located on-device rollouts (training/anakin.py): the pool
            # takes its own snapshot of the live parameters, here on the
            # loop thread before the next fused step donates them, in the
            # compute dtype and into the buffers of the snapshot it
            # replaces — one snapshot lives, and params never leave the
            # device on this path
            self.pool.publish_params(self.param_version,
                                     self.train_state.params)
            return
        if self._pipeline is not None:
            # hand the staging thread an on-device COPY: the hot loop's
            # next fused step donates train_state, which would invalidate
            # the original buffers under the staging thread's device_get.
            # The copy dispatch is async — no hot-loop drain.
            params = jax.tree.map(jnp.copy, self.train_state.params)
            self._pipeline.publish(self.param_version, params)
            return
        # no pipeline: FusedApexTrainer.train (ondevice/fused.py) publishes
        # through here with no staging thread to take the device_get
        host_params = jax.device_get(self.train_state.params)
        self.pool.publish_params(self.param_version, host_params)

    # -- cooperative shutdown ---------------------------------------------

    _stop_requested = None      # lazily a threading.Event (request_stop)

    def request_stop(self) -> None:
        """Ask a running :meth:`train` (possibly in another thread) to
        return at its next loop iteration — graceful shutdown without
        waiting out ``max_seconds``."""
        import threading
        if self._stop_requested is None:
            self._stop_requested = threading.Event()
        self._stop_requested.set()

    # -- multi-chip plan (shared by both families) ------------------------

    def _init_sharded(self, example_item=None) -> None:
        """dp > 1: shard the replay per chip, pmean grads over ICI,
        round-robin whole chunks across shards (BASELINE.json north star:
        HBM replay + 8-chip learner).  Total replay capacity = per-chip
        capacity x dp.  Requires ``self.core``/``self.train_state``/
        ``self.pool`` already built single-shard; the replay state is
        built HERE, directly under the dp sharding
        (:meth:`ShardedLearner.init_replay` — ``example_item`` is what
        the family's ``replay.init`` takes).  AQL's NoisyNet update key is
        handled by ``ShardedLearner`` via ``core.update_needs_key``."""
        from apex_tpu.parallel.aggregate import ChunkAggregator
        from apex_tpu.parallel.learner import ShardedLearner
        from apex_tpu.parallel.mesh import make_mesh

        n = self.n_dp
        devices = jax.devices()
        if len(devices) < n:
            raise ValueError(
                f"mesh_shape={self.cfg.learner.mesh_shape} needs {n} "
                f"devices, have {len(devices)}")
        mesh = make_mesh(dp=n, devices=devices[:n])
        sl = ShardedLearner(self.core, mesh)
        self.replay_state = sl.init_replay(example_item)
        self.train_state = sl.replicate_train_state(self.train_state)
        self.pool = ChunkAggregator(self.pool, n)
        self._make_sharded_fns(mesh)

    def _make_sharded_fns(self, mesh=None) -> None:
        """(Re)build the sharded plan's jitted dispatches off the CURRENT
        core — construction calls this with the fresh mesh; a live lr
        application (``apply_hparams``) calls it bare to re-jit against
        the rebuilt optimizer on the mesh already in hand."""
        from apex_tpu.parallel.learner import ShardedLearner

        sl = self.sharded = ShardedLearner(
            self.core, mesh if mesh is not None else self.sharded.mesh)
        fused = sl.make_fused_step()
        train = sl.make_train_step()
        ingest = sl.make_ingest()

        def _keys(key):
            # pre-split + pre-placed per-chip keys (the pipeline's
            # KeyPrefetcher hands raw uint32 key data already sharded
            # over the mesh) pass straight through; a raw chain key (the
            # benchmark harness and FusedApexTrainer.train call these
            # programs with no pipeline live) pays the per-dispatch split
            # + sharded put
            if getattr(key, "dtype", None) == jnp.uint32:
                return key
            return sl.device_keys(key)

        def _fused(ts, rs, payload, prios, key, beta):
            return fused(ts, rs, payload, prios, _keys(key), beta)

        def _train(ts, rs, key, beta):
            return train(ts, rs, _keys(key), beta)

        # _dispatch names a program after its callable
        _fused.__name__, _train.__name__ = fused.__name__, train.__name__
        self._fused, self._train, self._ingest = _fused, _train, ingest

    # -- main loop ---------------------------------------------------------

    def train(self, total_steps: int, max_seconds: float = 3600.0,
              log_every: int = 200):
        """Run ``total_steps`` MORE learner updates (or until the wall
        clock).  On a restored trainer the step counter continues from the
        checkpoint — same resume contract as the single-process drivers."""
        cfg = self.cfg
        pool = self.pool
        target_steps = self.steps_rate.total + total_steps
        if self.actor_timing is None:
            self.actor_timing = {}
        from apex_tpu.obs.trace import get_ring, set_process_label
        from apex_tpu.utils.profiling import DispatchGapTimer
        set_process_label("learner")
        ring = self._ring = get_ring()
        if self._obs is None:
            self._obs = obs_spans.LearnerObs(ring=ring)
        gap = self._dispatch_gap = DispatchGapTimer(ring=ring,
                                                    track=self._loop_track)
        self._stats_pending = collections.deque(maxlen=64)
        self._in_flight = collections.deque()
        client = self.replay_client
        if client is not None and self._train_batch is None:
            # dp>1 included: _make_batch_train shards the service batch
            # over the mesh and pmeans the update (PR 17)
            self._train_batch = self._make_batch_train()
        sharded = getattr(self, "sharded", None)
        pipeline = self._pipeline = IngestPipeline(
            pool,
            scan_steps=self.scan_steps if self._multi is not None else 1,
            state_fn=self._pipeline_state,
            capacity=getattr(self.replay, "capacity", None),
            frame_capacity=getattr(self.replay, "f_capacity", None),
            # dp>1: group-granular staging + the key prefetcher takes
            # over the dispatch key chain (seeded with self.key;
            # _dispatch_key writes the advanced chain state back)
            sharded=sharded,
            key=self.key if sharded is not None else None,
            replay_client=client)
        self._pipeline_base = self.ingested
        if self.fleet is None:
            self.fleet = FleetRegistry(cfg.comms)
        try:
            pool.start()
        except BaseException:
            self._pipeline = None      # never started; don't route to it
            raise
        # learner-epoch fencing: stamp the param plane and the replay
        # write-back plane with this life's epoch (socket pools only —
        # in-host fleets die with the learner, nothing to fence)
        set_epoch = getattr(pool, "set_learner_epoch", None)
        if set_epoch is not None:
            set_epoch(self.learner_epoch)
        if client is not None:
            client.learner_epoch = self.learner_epoch
        self._start_status_server()
        # staging starts only once the pool is live: its thread owns
        # every poll_chunks/publish_params call from here to stop()
        # (see RemotePool's thread-affinity contract)
        pipeline.start()
        try:
            self._publish()
            last_publish = time.monotonic()
            t_end = last_publish + max_seconds
            self._episode_idx = 0
            # interval-since-last semantics (not ``% interval == 0``): a
            # scan dispatch ticks the step counter by K, which can jump
            # over any exact multiple.  Save/log marks live on self.
            last_pub_step = self.steps_rate.total
            last_health = last_publish
            metrics = None      # no update has run yet this call (a restored
                                # trainer can hit the log gate before one)

            while self.steps_rate.total < target_steps:
                now = time.monotonic()
                stop = self._stop_requested
                if now > t_end or (stop is not None and stop.is_set()):
                    break
                self._pass += 1
                self._pass_kind = "idle"
                with self._span("loop_iter") as this_pass:
                    # ``warm`` gates the LOCAL replay's train paths (train-only
                    # steps, fused chunk-train) — in service mode the local
                    # pool only fills through the fallback, so those paths
                    # stay cold until it genuinely warms.  The ratio budget
                    # and floor run on the EFFECTIVE ingest count (local +
                    # what the shard fleet reports), so service-mode training
                    # is budgeted against real fleet-wide ingest.
                    warm = self.ingested >= cfg.replay.warmup
                    ingested_eff = self.ingested + (
                        client.ingested_total() if client is not None else 0)
                    consumed = self.steps_rate.total * self.core.batch_size
                    budget = (float("inf") if self.train_ratio is None
                              else ingested_eff * self.train_ratio
                              / self.core.batch_size)
                    # Replay-ratio floor: learner behind -> pause draining so the
                    # bounded chunk queue backpressures the actor fleet.  The
                    # EFFECTIVE floor is None while the dead-fleet reaction
                    # has relaxed it (see _react_to_fleet).
                    floor = self._min_ratio_effective()
                    behind = (warm and floor is not None
                              and consumed < ingested_eff * floor)

                    # consume ready-on-device slots; the staging thread
                    # already polled/decoded/merged/staged while the
                    # previous dispatch ran.  Service mode consumes even
                    # when "behind" — behind means the learner owes MORE
                    # training, and batch slots are exactly that
                    got_data = False
                    slot = None
                    if not behind or client is not None:
                        with self._span("poll_slot"):
                            slot = pipeline.poll_slot(
                                timeout=0 if (warm or client is not None)
                                else 0.05)
                    if slot is not None:
                        got_data = True
                        m = self._consume_slot(slot, warm, budget,
                                               target_steps)
                        if m is not None:
                            metrics = m
                    if not got_data and warm \
                            and self.steps_rate.total < budget:
                        k = self._dispatch_key()
                        with self._dispatch("train", self._train,
                                            beta=self._beta) as call:
                            self.train_state, self.replay_state, metrics = \
                                call(self.train_state, self.replay_state, k)
                        self.steps_rate.tick()
                    elif not got_data and warm:
                        with self._span("ratio_sleep"):
                            time.sleep(0.002)   # replay-ratio cap reached

                    steps = self.steps_rate.total
                    if metrics is not None \
                            and metrics is not self._stats_last:
                        self._bound_in_flight(metrics)
                    if ring.enabled:
                        self._note_model_stats(metrics, steps)
                    self._stats_last = metrics
                    if (self.checkpointer is not None
                            and steps - self._last_save
                            >= cfg.learner.save_interval):
                        with self._span("checkpoint"):
                            self.save_checkpoint()
                        self._last_save = steps
                    # Pre-first-step republish (slow cadence) is needed only for
                    # socket pools: a TCP subscriber that joined after the
                    # initial publish would otherwise never receive params
                    # (PUB/SUB has no replay — the zmq slow-joiner race) and an
                    # actor fleet without params produces no chunks: deadlock.
                    # mp pools have pre-existing queues, so the initial publish
                    # cannot be lost and warmup republishes would only burn the
                    # ingest thread on param serialization.
                    if steps:
                        due = (now - last_publish >= self.publish_min_seconds
                               and (steps - last_pub_step
                                    >= cfg.learner.publish_interval
                                    or now - last_publish
                                    > 10 * self.publish_min_seconds))
                    else:
                        due = (getattr(pool, "needs_warmup_republish", False)
                               and now - last_publish
                               > 10 * self.publish_min_seconds)
                    if due:
                        self._publish()
                        last_publish = now
                        last_pub_step = steps

                    # Failure detection (beyond the reference, SURVEY.md §5.3:
                    # its fleets never notice actor death): crashed workers are
                    # logged and respawned on the same ladder slot; remote
                    # peers run the fleet registry's JOINING/ALIVE/SUSPECT/DEAD
                    # machine (config thresholds in CommsConfig — this
                    # replaced the old hardcoded silent_peers(60.0) report).
                    # drain BEFORE the health tick: after a dispatch that
                    # blocked the loop (a first compile is tens of seconds on
                    # the chip) the drain reads the queued heartbeats and
                    # forgives the span nobody was watching — judging silence
                    # first declares a healthy fleet DEAD
                    with self._span("drain_stats"):
                        self._drain_stats(steps)
                    if self.respawn_workers and now - last_health >= 5.0:
                        with self._span("health_tick"):
                            self._health_tick(steps)
                        last_health = now

                    # metrics is None until the first train dispatch, so the
                    # gate needs no warm check — and in service mode the
                    # LOCAL pool never warms while shard batches train fine
                    if metrics is not None \
                            and steps - self._last_log >= log_every:
                        with self._span("log_scalars"):
                            extra = gap.snapshot() | {
                                "loop_key_refills": self._blocks().refills,
                                "loop_keys_served": self._blocks().served,
                                "loop_beta_puts": self.beta_puts,
                                "loop_beta_reused": self.beta_reused}
                            extra |= {f"pipeline_{k}": v
                                      for k, v in pipeline.stats.items()}
                            if self._obs is not None:
                                extra |= self._obs.scalars()
                            if client is not None:
                                extra |= {
                                    "service_batches": client.batches,
                                    "service_steps": self.service_steps,
                                    "service_ingested":
                                        client.ingested_total()}
                            self.log.scalars(
                                {k: float(v) for k, v in metrics.items()}
                                | {"bps": self.steps_rate.rate,
                                   "fps": self.frames_rate.rate,
                                   "param_version": self.param_version,
                                   "ingested": ingested_eff} | extra, steps)
                        self._last_log = steps
                    this_pass.note(kind=self._pass_kind)
        finally:
            # stop staging BEFORE the pool teardown (the staging thread
            # is the pool's only chunk consumer while live)
            self._pipeline_last_stats = dict(pipeline.stats)
            pipeline.stop()
            self._pipeline = None
            if self._fleet_status is not None:
                self._fleet_status.stop()
                self._fleet_status = None
            self._dump_fleet_summary()     # final registry state on disk
            pool.cleanup()
            stop = self._stop_requested
            if stop is not None:
                # honored (or stale) requests clear at EXIT, never at
                # entry: a request racing train() startup must still stop
                # this run; the NEXT call then starts fresh
                stop.clear()
        return self

    def _start_status_server(self) -> None:
        """Socket learner: serve live registry snapshots for
        ``--role status`` (own REP socket + thread; a bind failure —
        e.g. two learners on one host — degrades to no status surface,
        never to a dead learner).  Shared by the chunk-driven loop and
        the fused on-device loop (:mod:`apex_tpu.ondevice.fused`)."""
        if not hasattr(self.pool, "peer_seen") \
                or self._fleet_status is not None:
            return
        try:
            from apex_tpu.fleet.registry import FleetStatusServer
            if self._ctl_queue is None:
                # built BEFORE the server thread starts (the enqueue
                # hook runs on that thread); bounded so a runaway
                # controller can only ever park 8 commands
                import queue as queue_lib
                self._ctl_queue = queue_lib.Queue(maxsize=8)
            self._fleet_status = FleetStatusServer(
                self.cfg.comms, self.fleet,
                metrics_fn=self._metrics_text,
                snapshot_fn=self.fleet_summary,
                ctl_fn=self._enqueue_ctl)
            self._fleet_status.start()
        except Exception:
            self._fleet_status = None

    def _health_tick(self, steps: int) -> None:
        """One health tick: respawns, registry machine, SLO judgment,
        fleet reactions, ctl drain, summary dump.  Shared by both hot
        loops — the caller owns the 5s cadence gate."""
        pool = self.pool
        if hasattr(pool, "dead_workers"):      # local fleets
            for dead in pool.dead_workers():
                self.log.scalars({"worker_respawn": dead}, steps)
                pool.respawn_worker(dead)
        if hasattr(pool, "peer_seen"):         # socket fleets:
            # chunk arrivals count as liveness even when a
            # backpressured actor's stat puts drop
            self.fleet.observe_seen(pool.peer_seen())
        for ident, old, new in self.fleet.tick():
            self.log.scalars(
                {f"fleet_{new.lower()}_transition": 1.0}, steps)
            if self.log.verbose or new in ("SUSPECT", "DEAD"):
                print(f"fleet: {ident} {old} -> {new}", flush=True)
        fm = self.fleet.metrics()
        if fm["peers"]:
            self.log.scalars(
                {"fleet_alive": fm["alive"],
                 "fleet_suspect": fm["suspect"],
                 "fleet_dead": fm["dead"],
                 "fleet_parked": fm["parked"],
                 "fleet_rejoins": fm["rejoins"]}, steps)
        # judge BEFORE reacting: the floor reaction consults
        # the actor-capacity alert the sample just advanced
        self._slo_tick(steps)
        self._react_to_fleet(steps)
        # PBT ctl commands drain HERE (trainer thread): the
        # status thread only ever enqueued them, so the
        # weight copy / optimizer rebuild touch learner
        # state from exactly one thread
        self._drain_ctl(steps)
        self._dump_fleet_summary()

    def _drain_stats(self, steps: int) -> None:
        """Drain the pool's stat stream: heartbeats into the registry,
        controller snapshots into their sections, timing/episode stats
        into the scalar log.  Shared by both hot loops."""
        now = time.monotonic()
        blind = 0.0 if self._last_drain is None else now - self._last_drain
        self._last_drain = now
        if blind > self.cfg.comms.heartbeat_interval_s:
            self.fleet.forgive(blind)
        for stat in self.pool.poll_stats():
            self.stat_drops += getattr(stat, "dropped_stats", 0)
            if isinstance(stat, Heartbeat):
                self.fleet.observe(stat)
                continue
            if isinstance(stat, ServingStat):
                self.serving_state = dict(stat.snapshot)
                continue
            if isinstance(stat, TenancyStat):
                self.tenancy_state = dict(stat.snapshot)
                continue
            if isinstance(stat, PopulationStat):
                self.population_state = dict(stat.snapshot)
                continue
            if isinstance(stat, KeyframeRequest):
                # a subscriber could not apply a param delta (missed
                # keyframe / checksum mismatch): force the next publish
                # dense.  No-op on dense-mode pools.
                fk = getattr(self.pool, "force_keyframe", None)
                if callable(fk):
                    fk()
                continue
            if isinstance(stat, ActorTimingStat):
                self.actor_timing[stat.actor_id] = stat
                self.log.scalars(
                    {"actor_fps": stat.frames_per_sec,
                     "actor_policy_wait_frac":
                         stat.policy_wait_frac,
                     "actor_env_step_frac": stat.env_step_frac,
                     "actor_drain_frac": stat.drain_frac,
                     "actor_dispatch_gap_ms_p50":
                         stat.dispatch_gap_ms_p50}, steps)
                continue
            self.log.scalars(
                {"episode_reward": stat.reward,
                 "episode_length": stat.length,
                 "actor_id": stat.actor_id}, self._episode_idx)
            self._episode_idx += 1

    def actor_plane(self) -> dict | None:
        """Aggregate actor-plane view from the latest per-worker
        :class:`~apex_tpu.actors.pool.ActorTimingStat`\\ s (the e2e bench
        surfaces this next to ``env_frames_per_sec``), or None when no
        worker has reported yet (scalar fleets / timing_interval=0)."""
        if not self.actor_timing:
            return None
        ts = list(self.actor_timing.values())

        def mean(vals):
            return round(float(np.mean(vals)), 4)

        return {
            "workers_reporting": len(ts),
            "double_buffer": all(t.double_buffer for t in ts),
            "frames_per_sec_sum":
                round(sum(t.frames_per_sec for t in ts), 1),
            "policy_wait_frac": mean([t.policy_wait_frac for t in ts]),
            "env_step_frac": mean([t.env_step_frac for t in ts]),
            "drain_frac": mean([t.drain_frac for t in ts]),
            "dispatch_gap_ms_p50":
                mean([t.dispatch_gap_ms_p50 for t in ts]),
            "stat_drops": self.stat_drops,
        }

    def latency_summary(self) -> dict | None:
        """The e2e bench ``latency`` section: the chunk-lineage
        histograms (frame-age-at-train, param-propagation-lag) plus the
        hot-loop dispatch-gap percentiles, or None before train()."""
        if self._obs is None:
            return None
        out = self._obs.summary()
        if self._dispatch_gap is not None:
            out["dispatch_gap_ms"] = self._dispatch_gap.snapshot()
        return out

    def _metrics_text(self) -> str:
        """Prometheus exposition for the status server's ``b"metrics"``
        request (runs on the server thread: every read here is either a
        locked snapshot or a GIL-atomic tail read)."""
        from apex_tpu.obs import metrics as obs_metrics

        gauges = dict(obs_metrics.scalar_tails(self.log.history))
        gauges["learner_steps_per_sec"] = self.steps_rate.rate
        gauges["learner_frames_per_sec"] = self.frames_rate.rate
        counters = {
            "learner_steps_total": self.steps_rate.total,
            "transitions_ingested_total": self.ingested,
            "param_version": self.param_version,
            "stat_drops_total": self.stat_drops,
        }
        wire_fn = getattr(self.pool, "wire_summary", None)
        if callable(wire_fn):
            # apex_wire_* rows (runtime/codec.py): decode counts + the
            # param-delta publisher's byte counters.  Registered
            # families in obs.metrics — J015 keeps this dict honest.
            w = wire_fn()
            counters.update({
                "wire_codec_chunks": w.get("codec_chunks"),
                "wire_codec_rejected": w.get("codec_rejected"),
                "wire_param_publishes": w.get("param_publishes"),
                "wire_param_keyframes": w.get("param_keyframes"),
                "wire_param_deltas": w.get("param_deltas"),
                "wire_param_delta_bytes": w.get("param_delta_bytes"),
                "wire_param_bytes_out": w.get("param_bytes_out"),
                "wire_param_bytes_raw": w.get("param_bytes_raw"),
                "wire_keyframes_forced": w.get("keyframes_forced"),
            })
        labeled: dict = {}
        if self.fleet is not None:
            fleet_gauges, labeled = obs_metrics.render_fleet(
                self.fleet.snapshot())
            gauges.update(fleet_gauges)
        histograms = {}
        if self._obs is not None:
            s = self._obs.summary()
            histograms = {
                "frame_age_at_train_seconds": s["frame_age_at_train_s"],
                "param_propagation_lag_seconds":
                    s["param_propagation_lag_s"],
            }
        if self._dispatch_gap is not None:
            snap = self._dispatch_gap.snapshot()
            gauges.update({f"learner_{k}": v for k, v in snap.items()})
        if self._slo is not None:
            # apex_slo_* rows: objective states/burns/compliance, so a
            # stock alertmanager can page off the same machine the
            # autoscaler scales from
            from apex_tpu.obs import slo as obs_slo
            slo_gauges, slo_labeled = obs_slo.prometheus_sections(
                self._slo.snapshot())
            gauges.update(slo_gauges)
            labeled.update(slo_labeled)
        if self.serving_state is not None:
            # apex_serving_* rows: the canary machine + per-shard pin
            # view, scraped from the same surface as the slo rows
            from apex_tpu.serving import deploy as serving_deploy
            srv_gauges, srv_labeled = serving_deploy.prometheus_sections(
                self.serving_state)
            gauges.update(srv_gauges)
            labeled.update(srv_labeled)
        if self.tenancy_state is not None:
            # apex_tenancy_* rows: the placement machine — per-tenant
            # state codes and band sizes next to the serving rows
            from apex_tpu.tenancy import scheduler as tenancy_sched
            tn_gauges, tn_labeled = tenancy_sched.prometheus_sections(
                self.tenancy_state)
            gauges.update(tn_gauges)
            labeled.update(tn_labeled)
        if self.population_state is not None:
            # apex_population_* rows: the PBT machine — per-lineage
            # liveness/generation/score next to the tenancy rows
            from apex_tpu.population import controller as population_ctl
            pp_gauges, pp_labeled = population_ctl.prometheus_sections(
                self.population_state)
            gauges.update(pp_gauges)
            labeled.update(pp_labeled)
        return obs_metrics.render(gauges=gauges, counters=counters,
                                  histograms=histograms, labeled=labeled)

    # -- fleet SLO engine (apex_tpu/obs/slo) -------------------------------

    def _slo_signals(self) -> dict:
        """The signal space one engine sample judges: registry peers +
        metrics, the obs-plane latency histograms, and the learner's
        rate counters — the same sections ``fleet_summary`` publishes,
        so an objective's signal path reads identically off the live
        engine and the persisted JSON."""
        snap = self.fleet.snapshot()
        m = snap["metrics"]
        m["dead_actor_frac"] = round(
            self.fleet.dead_fraction(roles=("actor",)), 4)
        return {
            "peers": snap["peers"], "metrics": m,
            "latency": (self._obs.summary()
                        if self._obs is not None else {}),
            "rates": {"steps_per_s": self.steps_rate.rate,
                      "frames_per_s": self.frames_rate.rate},
            # serving-tier counters ("serving.rollbacks" objective):
            # the dotted walk judges the controller's reported machine
            "serving": self.serving_state or {},
        }

    def _slo_tick(self, steps: int) -> None:
        """One engine sample per health tick (trainer thread ONLY — the
        status thread reads snapshots; sampling per scrape would make
        burn windows a function of scrape traffic).  Transitions print
        like fleet transitions do and land in the scalar log."""
        if self.fleet is None:
            return
        if self._slo is None:
            from apex_tpu.obs.slo import SloEngine, default_slos
            self._slo = SloEngine(default_slos(
                actor_dead_thresh=getattr(self.cfg.comms,
                                          "relax_floor_dead_frac", None)))
        for tr in self._slo.sample(self._slo_signals()):
            print(f"slo: {tr['objective']} {tr['from']} -> {tr['to']} "
                  f"(value={tr['value']})", flush=True)
            self.log.scalars(
                {f"slo_{tr['to'].lower()}_transition": 1.0}, steps)

    def fleet_summary(self) -> dict | None:
        """Registry snapshot + wire counters (the e2e bench ``fleet``
        section, ``--role status``'s JSON sibling), or None before the
        first train() call."""
        if self.fleet is None:
            return None
        snap = self.fleet.snapshot()
        rejected = getattr(self.pool, "wire_rejected", None)
        snap["metrics"]["wire_rejected"] = (rejected()
                                            if callable(rejected) else 0)
        m = snap["metrics"]
        # elastic-fleet surface (PR 8): epoch, reaction state, the
        # backpressure signal scale supervisors key off, re-admissions,
        # and the chaos receiver's withheld-ack count
        m["learner_epoch"] = self.learner_epoch
        # the published model fence (epoch-major, version-minor —
        # serving/fence.py): the serving tier's deployment controller
        # buckets deployable VERSIONS off exactly this pair, so the
        # status surface is the one place "what model is newest" lives
        m["param_version"] = self.param_version
        m["floor_relaxed"] = self._floor_relaxed
        m["floor_relaxes"] = self.floor_relaxes
        m["dead_actor_frac"] = round(
            self.fleet.dead_fraction(roles=("actor",)), 4)
        plane = self.actor_plane()
        m["actor_drain_frac"] = (plane["drain_frac"]
                                 if plane is not None else None)
        admitted = getattr(self.pool, "rejoin_admitted", None)
        m["barrier_admitted"] = (admitted() if callable(admitted) else 0)
        # population plane inputs/evidence (apex_tpu/population): the
        # newest donor-able checkpoint (the PBT controller reads it off
        # this surface to source exploit copies), the live-applied
        # hyperparameter vector, and the applied-ctl record the
        # pbt-smoke drill asserts (exploit count + post-copy epoch)
        m["checkpoint_latest"] = (self.checkpointer.latest_path()
                                  if self.checkpointer is not None
                                  else None)
        if self.hparams_live:
            m["hparams_live"] = dict(self.hparams_live)
        if self._population_ctl is not None:
            m["population_ctl"] = dict(self._population_ctl)
        withheld = getattr(self.pool, "acks_withheld", None)
        m["acks_withheld"] = (withheld() if callable(withheld) else 0)
        wire_fn = getattr(self.pool, "wire_summary", None)
        if callable(wire_fn):
            # wire-codec plane (runtime/codec.py): compressed-chunk
            # decode counts (codec_rejected must be 0 in a healthy
            # fleet — the codec-smoke CI drill asserts it) + the
            # param-delta publisher's byte counters
            m["wire"] = wire_fn()
        ondevice = getattr(self.pool, "ondevice_counters", None)
        if callable(ondevice):
            # on-device rollout plane (training/anakin.py): dispatch/
            # chunk/frame counters — the anakin-smoke CI drill asserts
            # these are nonzero from the persisted summary
            m["ondevice"] = ondevice()
        # SLO signal space + verdicts (apex_tpu/obs/slo): the sections
        # the engine judges ride the summary so an objective's signal
        # path resolves identically against the live engine, the status
        # snapshot, and the persisted JSON a soak/drill asserts on.
        # steps/ingested live HERE (not only in the disk dump) so the
        # soak's status-port samples can difference real progress.
        snap["steps"] = self.steps_rate.total
        snap["ingested"] = self.ingested
        snap["rates"] = {"steps_per_s": self.steps_rate.rate,
                         "frames_per_s": self.frames_rate.rate}
        lat = self.latency_summary()
        if lat is not None:
            snap["latency"] = lat
        if self._slo is not None:
            snap["slo"] = self._slo.snapshot()
        if self.serving_state is not None:
            # the serving tier's deployment machine (canary state,
            # per-shard pins, bounded timeline) — the serve-smoke drill
            # asserts its promotion/rollback edges from this persisted
            # section after the fleet is gone
            snap["serving"] = self.serving_state
        if self.tenancy_state is not None:
            # the tenancy placement machine (admissions, per-tenant
            # bands, eviction timeline) — the tenant-smoke drill asserts
            # both tenants' admissions from this persisted section
            snap["tenancy"] = self.tenancy_state
        if self.population_state is not None:
            # the PBT machine (task ladders, per-lineage score/
            # generation/survival, exploit/explore timeline) — the
            # pbt-smoke drill asserts its events from this persisted
            # section after the fleet is gone
            snap["population"] = self.population_state
        if self.replay_client is not None:
            c = self.replay_client
            snap["metrics"]["replay_service"] = {
                "shards": c.n_shards,
                "batches_pulled": c.batches,
                "service_steps": self.service_steps,
                "ingested_total": c.ingested_total(),
                "prio_sent": c.prio_sent,
                "prio_dropped": c.prio_dropped,
                "rejected": c.rejected,
                "shard_status": c.shard_status(),
            }
        return snap

    def _dump_fleet_summary(self) -> None:
        """Persist the registry view next to the logs.  The on-disk copy
        is the part of the control plane that SURVIVES the learner — the
        chaos rejoin test reads a SIGKILLed learner's last periodic dump
        to prove its registry saw the actor die and rejoin."""
        logdir = getattr(self.log, "logdir", None)
        if logdir is None or self.fleet is None:
            return
        import json
        import os
        summary = self.fleet_summary()
        path = os.path.join(logdir, "fleet_summary.json")
        try:
            os.makedirs(logdir, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(summary, fh, indent=2)
            os.replace(tmp, path)      # readers never see a torn write
        except OSError:
            pass                       # observability must not kill a run

    # -- registry reactions (PR 8) -----------------------------------------

    def _min_ratio_effective(self) -> float | None:
        """The replay-ratio floor the loop actually enforces: the
        configured ``min_train_ratio``, or None while the dead-fleet
        reaction has relaxed it."""
        return None if self._floor_relaxed else self.min_train_ratio

    def _react_to_fleet(self, steps: int) -> None:
        """Close the registry loop: when the DEAD fraction of the actor
        fleet reaches the config threshold, relax the replay-ratio floor
        (survivors must not be throttled against a throughput target the
        dead capacity was part of); restore it as peers rejoin.

        The reaction consults the SLO engine's actor-capacity alert
        (which judges the SAME threshold — default_slos wires
        relax_floor_dead_frac into the ``actor_dead_frac`` objective),
        so the two surfaces cannot disagree: while that alert is
        BREACHED the floor stays relaxed even if the instantaneous
        fraction dips under the bar mid-flap — the alert's own
        resolve damping is the hysteresis, the raw threshold keeps the
        reaction instant on a fresh mass death."""
        thresh = getattr(self.cfg.comms, "relax_floor_dead_frac", None)
        if (thresh is None or self.fleet is None
                or self.min_train_ratio is None):
            return
        frac = self.fleet.dead_fraction(roles=("actor",))
        slo_breached = (self._slo is not None
                        and self._slo.state_of("actor_dead_frac")
                        == "BREACHED")
        fire = frac >= thresh or slo_breached
        if not self._floor_relaxed and fire:
            self._floor_relaxed = True
            self.floor_relaxes += 1
            why = (f"{frac:.0%} of actor capacity DEAD" if frac >= thresh
                   else "actor-capacity SLO BREACHED")
            print(f"fleet reaction: {why} — relaxing the replay-ratio "
                  f"floor (min_train_ratio={self.min_train_ratio})",
                  flush=True)
        elif self._floor_relaxed and not fire:
            self._floor_relaxed = False
            print(f"fleet reaction: actor capacity back "
                  f"({frac:.0%} DEAD) — replay-ratio floor restored",
                  flush=True)
        self.log.scalars({"fleet_dead_actor_frac": frac,
                          "fleet_floor_relaxed":
                              float(self._floor_relaxed)}, steps)

    # -- population ctl (apex_tpu/population) ------------------------------

    def _enqueue_ctl(self, cmd: dict) -> dict:
        """Status-server-thread half of the ctl surface: enqueue ONLY
        (the trainer thread applies at its next health tick — learner
        state is single-threaded by contract)."""
        import queue as queue_lib
        q = self._ctl_queue
        if q is None:
            return {"accepted": False, "error": "no ctl queue"}
        try:
            q.put_nowait(dict(cmd))
        except queue_lib.Full:
            return {"accepted": False, "error": "ctl queue full"}
        return {"accepted": True, "pending": q.qsize()}

    def _drain_ctl(self, steps: int) -> None:
        """Trainer-thread half: apply every parked command."""
        import queue as queue_lib
        q = self._ctl_queue
        if q is None:
            return
        while True:
            try:
                cmd = q.get_nowait()
            except queue_lib.Empty:
                return
            self._apply_ctl(cmd, steps)

    def _apply_ctl(self, cmd: dict, steps: int) -> None:
        """One PBT command.  ``exploit`` = the donor-checkpoint weight
        copy (epoch bumped, fleet re-fenced, fresh publish) + the
        explore half's hyperparameter vector; ``hparams`` = the vector
        alone.  A failed copy is counted evidence, never a dead
        learner."""
        op = str(cmd.get("op") or "")
        rec = self._population_ctl or {"applied": 0, "exploits": 0,
                                       "explores": 0, "errors": 0}
        event: dict = {"op": op, "donor": cmd.get("donor"),
                       "step": steps}
        if op == "exploit":
            path = str(cmd.get("restore_from") or "")
            import os as os_lib
            if path and not os_lib.path.exists(path) \
                    and os_lib.path.isdir(os_lib.path.dirname(path)):
                # the donor's Checkpointer prunes to its newest few
                # files, and this command sat in the ctl queue up to
                # one health tick — a pruned path means a NEWER donor
                # checkpoint exists in the same directory; copy that
                # (strictly fresher weights, same lineage)
                from apex_tpu.training.checkpoint import Checkpointer
                newer = Checkpointer(
                    os_lib.path.dirname(path)).latest_path()
                if newer is not None:
                    path = newer
            try:
                self.restore_weights(path)
            except Exception as e:
                rec["errors"] += 1
                event["error"] = f"{type(e).__name__}: {e}"
                rec["last"] = event
                self._population_ctl = rec
                print(f"population: exploit failed ({event['error']})",
                      flush=True)
                return
            rec["exploits"] += 1
            event["restored_from"] = path
            event["learner_epoch"] = self.learner_epoch
            # re-fence the fleet on the new epoch, then publish the
            # copied weights promptly — actors/infer shards fence out
            # the pre-copy life's params, shards its write-backs
            set_epoch = getattr(self.pool, "set_learner_epoch", None)
            if set_epoch is not None:
                set_epoch(self.learner_epoch)
            if self.replay_client is not None:
                self.replay_client.learner_epoch = self.learner_epoch
            applied = self.apply_hparams(cmd.get("hparams") or {})
            if applied or cmd.get("hparams"):
                rec["explores"] += 1
                event["applied"] = applied
            self._publish()
        elif op == "hparams":
            applied = self.apply_hparams(cmd.get("hparams") or {})
            rec["explores"] += 1
            event["applied"] = applied
        else:
            rec["errors"] += 1
            event["error"] = f"unknown op {op!r}"
        rec["applied"] += 1
        rec["last"] = event
        self._population_ctl = rec
        print(f"population: applied {op} "
              f"(donor={cmd.get('donor') or '-'}, "
              f"epoch={self.learner_epoch})", flush=True)
        self.log.scalars({"population_ctl_applied": rec["applied"]},
                         steps)

    def restore_weights(self, path: str) -> dict:
        """The PBT exploit weight copy: impose the donor checkpoint's
        ``train_state`` — params, target, optimizer state — onto THIS
        live learner (PR 8 snapshot machinery,
        :func:`apex_tpu.training.checkpoint.load_raw`), leaving replay
        state, PRNG chain, and progress counters alone, and bump the
        learner epoch so the pre-copy life's params and write-backs are
        fenced out exactly as a restart's would be.  Returns the donor
        checkpoint's metadata."""
        from flax import serialization

        from apex_tpu.training.checkpoint import load_raw
        raw, meta = load_raw(path)
        self.train_state = serialization.from_state_dict(
            self.train_state, raw["train_state"])
        self.learner_epoch += 1
        return meta

    def apply_hparams(self, h: dict) -> dict:
        """Live half of the lineage hyperparameter vector
        (:data:`apex_tpu.population.lineage.LIVE_HPARAMS`): ``lr``
        rebuilds the optimizer chain (same structure, so the running
        ``opt_state`` carries over; one recompile per explore event),
        ``prio_beta`` re-points the IS-weight anneal the very next
        ``_beta()`` call reads.  The acting-side fields (n_steps /
        prio_alpha / eps_base shape chunk assembly, insert exponents,
        and the epsilon ladder) are recorded in ``hparams_live`` and
        apply to the lineage's next worker generation via
        ``population.lineage.apply_lineage``.  Returns the subset
        applied live."""
        import dataclasses as _dc
        applied: dict = {}
        lr = h.get("lr")
        if lr is not None and isinstance(self.core, LearnerCore):
            lc = self.cfg.learner
            optimizer = make_optimizer(
                lr=float(lr), decay=lc.rmsprop_decay, eps=lc.rmsprop_eps,
                centered=lc.rmsprop_centered,
                max_grad_norm=lc.max_grad_norm,
                lr_decay_steps=lc.lr_decay_steps,
                lr_decay_rate=lc.lr_decay_rate)
            self.core = _dc.replace(self.core, optimizer=optimizer)
            if getattr(self, "n_dp", 1) > 1:
                # the sharded plan closed over the old core — rebuild it
                # on the mesh already in hand (one recompile per explore,
                # same contract as the single-shard re-jits below)
                self._make_sharded_fns()
            else:
                self._fused = self.core.jit_fused_step()
                self._train = self.core.jit_train_step()
                self._ingest = self.core.jit_ingest()
                if self._multi is not None:
                    self._multi = self.core.jit_fused_multi_step()
            self._ingest_multi = None       # re-jit lazily off the new core
            if self._train_batch is not None:
                self._train_batch = self._make_batch_train()
            self.cfg = self.cfg.replace(
                learner=_dc.replace(lc, lr=float(lr)))
            applied["lr"] = float(lr)
        beta = h.get("prio_beta")
        if beta is not None:
            self.cfg = self.cfg.replace(
                replay=_dc.replace(self.cfg.replay, beta=float(beta)))
            applied["prio_beta"] = float(beta)
        recorded = {k: v for k, v in h.items() if v is not None}
        if recorded:
            self.hparams_live = {**(self.hparams_live or {}), **recorded}
        return applied

    def _beta(self, ingested: int | None = None) -> float:
        n = self.ingested if ingested is None else ingested
        frac = min(1.0, n / max(1, self.cfg.replay.beta_anneal))
        return self.cfg.replay.beta + (1.0 - self.cfg.replay.beta) * frac

    # -- async ingest pipeline (training/ingest_pipeline.py) ---------------

    def _dispatch_key(self):
        """One dispatch's PRNG key, advancing the key chain exactly as
        an eager ``self.key, k = split(self.key)`` would, without a
        program launched for it in this pass.  ``self.key`` is the
        chain; whoever assigns it (this method, construction, a checkpoint
        restore, ``evaluate()``) is followed from there.

        Single-shard plan: the trainer's :class:`KeyBlocks` hands out
        pairs ``(k_i, chain_{i+1})`` of a block one program made, for as
        long as ``self.key`` IS the chain object it handed out last; a
        ``self.key`` assigned from outside, or a block run dry, is
        answered with a block made from that very ``self.key`` here, on
        the loop thread (ring instant ``key_refill``: the one pass in
        ``KEY_BLOCK`` that launches), so keys and ``self.key`` are
        bit-identical to the eager chain at every dispatch count and a
        checkpoint taken mid-block saves the next key's parent.

        While a sharded pipelined run is live, the pipeline's
        KeyPrefetcher owns the chain instead (seeded with ``self.key``):
        it hands back keys already split per chip and placed over the
        mesh, plus the chain state the inline split would have left in
        ``self.key``."""
        with self._span("dispatch_key"):
            pipe = self._pipeline
            if pipe is not None and pipe.keys is not None:
                placed, self.key = pipe.keys.take()
                return placed
            blocks = self._blocks()
            taken = blocks.refills
            k, self.key = blocks.take(self.key)
            if blocks.refills != taken:
                self._ring.instant(
                    "key_refill", self._loop_track,
                    {"it": self._pass, "served": blocks.served - 1,
                     "beta_puts": self.beta_puts,
                     "beta_reused": self.beta_reused})
            return k

    def _blocks(self) -> KeyBlocks:
        if self._key_blocks is None:
            self._key_blocks = KeyBlocks()
        return self._key_blocks

    # -- host spans + the one dispatch site --------------------------------

    def _span(self, name: str, **args):
        """One named phase of the loop pass under way, on the hot loop's
        track and carrying the pass number ``it``: a ring event and a
        profiler annotation while tracing is live
        (:meth:`apex_tpu.obs.trace.TraceRing.span`), else the ring's
        shared no-op for one attribute check."""
        ring = self._ring
        if ring is None:
            from apex_tpu.obs.trace import get_ring
            ring = self._ring = get_ring()
        if not ring.live:
            return ring.span(name)
        return ring.span(name, self._loop_track, {"it": self._pass, **args})

    def _bound_in_flight(self, metrics) -> None:
        """Keep at most ``max_steps_in_flight`` updates enqueued on the
        device: wait for the oldest one's loss before going on.  A loop
        the host paces never waits here (the step it asks about finished
        passes ago).  A loop the DEVICE paces would otherwise enqueue
        every update the replay-ratio cap allows at once: its counters
        (and the ratio control that reads them) would run tens of seconds
        ahead of the work done, and a rollout dispatched by the staging
        thread would queue behind all of them."""
        self._in_flight.append(metrics["loss"])
        if len(self._in_flight) > self.max_steps_in_flight:
            oldest = self._in_flight.popleft()
            if not oldest.is_ready():
                # the loop waits for the device, not the device for the
                # loop: a span of its own, so the ring tells this wait
                # from host work inside ``host_gap``
                with self._span("in_flight_wait"):
                    oldest.block_until_ready()

    def _note_model_stats(self, metrics, steps: int) -> None:
        """What the model counted inside the step (the expert layers'
        ``moe_*`` scalars among the step's metrics) as one ring instant
        ``moe_stats`` an update, written once the device has the values:
        the loop never waits for them, and no program is launched.
        ``log_scalars`` carries the same numbers at its own cadence."""
        pending = self._stats_pending
        if metrics is not None and metrics is not self._stats_last:
            stats = {k: v for k, v in metrics.items()
                     if k.startswith("moe_") and getattr(v, "ndim", 1) == 0}
            if stats:
                pending.append((steps, stats))
        while pending and all(v.is_ready() for v in pending[0][1].values()):
            at, stats = pending.popleft()
            self._ring.instant("moe_stats", self._loop_track, {
                "step": at, **{k: float(v) for k, v in stats.items()}})

    def _pre_consume(self, spans) -> None:
        """Chunk-lineage join, first half (stamps ``consume``)."""
        if spans:
            with self._span("obs_join", n=len(spans)):
                self._obs.pre_consume(spans)

    def _post_consume(self, spans) -> None:
        """Second half: ``prio_wb``, the age and lag histograms, the
        lineage events."""
        if spans:
            with self._span("obs_join", n=len(spans)):
                self._obs.post_consume(spans, self.steps_rate.total)

    @contextlib.contextmanager
    def _dispatch(self, kind: str, fn, beta=None,
                  program: str | None = None):
        """One device dispatch, in the one place every dispatch of the
        loop goes through::

            with self._dispatch("fused", self._fused, self._beta) as call:
                self.train_state, self.replay_state, metrics = call(
                    self.train_state, self.replay_state, payload, prios, k)

        The block is the interval ``host_gap`` leaves out, as the
        hand-written sites had it: it opens with
        ``gap.about_to_dispatch()``, ``beta()`` (a host float) becomes
        the step's last operand inside it (timed apart as ``beta``): the
        device scalar held from the last dispatch while the float is the
        same, which it is between two chunks and for good once the anneal
        ends (counter ``beta_reused``); else a 4-byte transfer of
        ``np.float32(b)`` (``beta_puts``).  Never a program, and always
        the strong-typed uncommitted ``f32[]`` a ``jnp.float32`` gives, so
        the same compiled step.  ``call`` is ``fn`` under a ``dispatch``
        span, and the caller's assignment of the results (``adopt``, inside
        ``dispatch``: the donated state's Python objects die there) still
        lies before ``gap.dispatch_returned()``.  ``kind`` names the pass
        (``loop_iter``'s arg); ``program`` is the name XLA gives ``fn``,
        its module's name in a profiler trace, so a device program can be
        put beside the pass that issued it."""
        self._pass_kind = kind
        gap = self._dispatch_gap
        gap.about_to_dispatch()
        tail = ()
        if beta is not None:
            with self._span("beta"):
                b = beta()
                if b != self._beta_host:
                    self._beta_host = b
                    self._beta_dev = jax.device_put(np.float32(b))
                    self.beta_puts += 1
                else:
                    self.beta_reused += 1
                tail = (self._beta_dev,)
        with contextlib.ExitStack() as spans:
            def call(*args):
                spans.enter_context(self._span(
                    "dispatch", program=program or "jit_" + fn.__name__))
                out = fn(*args, *tail)
                spans.enter_context(self._span("adopt"))
                return out
            yield call
        gap.dispatch_returned()

    def _dispatch_fused(self, payload, prios):
        """Ingest one chunk and train one step (``_fused``); the step's
        metrics."""
        k = self._dispatch_key()
        with self._dispatch("fused", self._fused, beta=self._beta) as call:
            self.train_state, self.replay_state, metrics = call(
                self.train_state, self.replay_state, payload, prios, k)
        return metrics

    def _dispatch_scan(self, payload, prios, j: int, betas):
        """``j`` stacked chunks, ``j`` steps in one program (``_multi``),
        its per-step keys split off one chain key; the stacked metrics."""
        k = self._dispatch_key()
        with self._dispatch("scan", self._multi) as call:
            with self._span("dispatch_key"):
                keys = jax.random.split(k, j)
            self.train_state, self.replay_state, mm = call(
                self.train_state, self.replay_state, payload, prios, keys,
                betas)
        return mm

    def _dispatch_ingest(self, payload, prios) -> None:
        """Absorb one payload without training (``_ingest``)."""
        with self._dispatch("ingest", self._ingest) as call:
            self.replay_state = call(self.replay_state, payload, prios)

    def _pipeline_state(self):
        """Counter snapshot for the staging thread's grouping decisions.
        ``train_eligible`` is predicted with the pipeline's monotone
        polled-transition total (plus the ingested count the pipeline
        started from): when the chunk under consideration reaches the
        front of the (order-preserving) pipeline, the trainer's
        ``ingested`` will equal exactly that — so the prediction is the
        per-chunk warm/budget gate ``_consume_slot`` applies, and a merge
        group never straddles the warmup boundary."""
        from apex_tpu.training.ingest_pipeline import PipelineState
        cfg = self.cfg
        pipe = self._pipeline
        client = self.replay_client
        effective = self._pipeline_base + (0 if pipe is None
                                           else pipe.polled_total())
        # service mode: the shard fleet's reported ingest counts toward
        # the ratio budget (pulls ARE training), but NOT toward the
        # local-chunk warmup prediction — fallback chunks train against
        # the local pool, which only the local stream fills
        client_tot = client.ingested_total() if client is not None else 0
        consumed = self.steps_rate.total * self.core.batch_size
        floor = self._min_ratio_effective()
        behind = (self.ingested >= cfg.replay.warmup
                  and floor is not None
                  and consumed < (self.ingested + client_tot) * floor)
        # the step counter the chunk will MEET includes the train steps
        # already staged ahead of it — without them every chunk queued
        # behind one pending fused step looks budget-eligible and the
        # ingest-only stream degrades to unmerged singles
        steps_at_front = (self.steps_rate.total
                          + (0 if pipe is None
                             else pipe.staged_train_steps()))
        budget_ok = (self.train_ratio is None
                     or steps_at_front
                     < (effective + client_tot) * self.train_ratio
                     / self.core.batch_size)
        return PipelineState(
            behind=behind,
            train_eligible=effective >= cfg.replay.warmup and budget_ok,
            pull_eligible=budget_ok)

    # -- sharded replay service (apex_tpu/replay_service) ------------------

    def _make_batch_train(self):
        """The service-mode train dispatch: the family's shared update
        body over a shard-sampled batch (the sample half already ran on
        the shard).  Families whose update consumes a PRNG key (AQL
        NoisyNet) receive the shard-split update key with the batch, so
        the one chain never forks.

        dp>1 (PR 17): the service batch splits over the mesh as
        contiguous per-chip blocks, the update ``pmean``s over ``dp``,
        and the per-chip priorities reassemble ``[batch]`` in sample
        order — the shard write-back path is unchanged."""
        import jax as _jax
        core = self.core
        needs_key = getattr(core, "update_needs_key", False)
        sl = getattr(self, "sharded", None)
        if sl is None or getattr(self, "n_dp", 1) == 1:
            if needs_key:
                def train_on_batch(ts, batch, weights, key):
                    return core.update_from_batch(ts, batch, weights, key)
            else:
                def train_on_batch(ts, batch, weights):
                    return core.update_from_batch(ts, batch, weights)
            return _jax.jit(train_on_batch, donate_argnums=(0,))

        from jax.sharding import PartitionSpec as _P

        sl._per_chip_batch()    # loud divisibility check, names the knobs

        if needs_key:
            def per_chip(ts, batch, weights, kd):
                # one replicated update key, folded per chip so the
                # NoisyNet draws decorrelate (ShardedLearner semantics)
                key = _jax.random.fold_in(
                    _jax.random.wrap_key_data(kd),
                    _jax.lax.axis_index("dp"))
                return core.update_from_batch(ts, batch, weights, key,
                                              axis_name="dp")
            in_specs = (_P(), _P("dp"), _P("dp"), _P())
        else:
            def per_chip(ts, batch, weights):
                return core.update_from_batch(ts, batch, weights,
                                              axis_name="dp")
            in_specs = (_P(), _P("dp"), _P("dp"))
        mapped = _jax.shard_map(
            per_chip, mesh=sl.mesh, in_specs=in_specs,
            out_specs=(_P(), _P("dp"), _P()), check_vma=False)
        jitted = _jax.jit(mapped, donate_argnums=(0,))
        if needs_key:
            def train_on_batch(ts, batch, weights, key):
                return jitted(ts, batch, weights,
                              _jax.random.key_data(key))
            return train_on_batch
        return jitted

    def _consume_batch_slot(self, slot):
        """Train on one shard-sampled batch and route the priority
        write-back to its owning shard, via the staging thread (the
        device_get must not land on the hot loop)."""
        with self._dispatch("batch", self._train_batch) as call:
            key = ()
            if slot.update_key is not None:
                with self._span("dispatch_key"):
                    key = (jax.random.wrap_key_data(
                        jnp.asarray(slot.update_key)),)
            self.train_state, prios, metrics = call(
                self.train_state, slot.payload, slot.prios, *key)
        self.steps_rate.tick()
        self.service_steps += 1
        self._pipeline.write_back(slot.shard, slot.seq, slot.idx, prios)
        return metrics

    def _consume_slot(self, slot, warm: bool, budget: float,
                      target_steps: int):
        """Dispatch one staged slot; returns metrics or None.  Gated
        chunk for chunk: train-eligible singles run the fused step,
        eligible scan stacks run the K-step scan dispatch, everything else
        is absorbed ingest-only (the replay-ratio cap is re-checked at
        consume time, so a stale staging prediction can only under-train,
        never over-train)."""
        spans = slot.spans if self._obs is not None else ()
        self._pre_consume(spans)            # "consume": dispatch issued
        metrics = None
        if slot.kind == "batch":
            # shard-sampled: always trained (a staged batch skipped here
            # would leave its strict shard wedged on the write-back it
            # will never get; the budget re-check already gated the PULL,
            # so overshoot is bounded by the staged depth)
            metrics = self._consume_batch_slot(slot)
            self._post_consume(spans)
            return metrics
        if slot.kind == "scan":
            j = slot.chunks
            trainable = (warm and self._multi is not None
                         and self.steps_rate.total + j - 1 < budget
                         and target_steps - self.steps_rate.total >= j)
            if trainable:
                offsets = np.concatenate(
                    [[0], np.cumsum(slot.n_per)[:-1]])
                betas = np.asarray(
                    [self._beta(self.ingested + int(o)) for o in offsets],
                    np.float32)
                # scan slots exist only on the single-shard plan, so the
                # key is a raw chain key here — never prefetcher output
                mm = self._dispatch_scan(slot.payload, slot.prios, j, betas)
                metrics = jax.tree.map(lambda x: x.mean(0), mm)
                self.steps_rate.tick(j)
                self.scan_dispatches += 1
            else:
                if self._ingest_multi is None:
                    from apex_tpu.training.learner import make_multi_ingest
                    self._ingest_multi = make_multi_ingest(self.core)
                with self._dispatch("ingest", self._ingest_multi) as call:
                    self.replay_state = call(self.replay_state,
                                             slot.payload, slot.prios)
        elif slot.kind == "single" and warm \
                and self.steps_rate.total < budget:
            metrics = self._dispatch_fused(slot.payload, slot.prios)
            self.steps_rate.tick()
        else:
            # merged ingest payloads, and singles the cap says to absorb
            self._dispatch_ingest(slot.payload, slot.prios)
        self._post_consume(spans)           # "prio_wb" + the joins
        self.ingested += slot.n_trans
        self.frames_rate.tick(slot.n_trans)
        return metrics

    # -- checkpointing (A4): format/IO in CheckpointableTrainer ------------
    # (restore note: the actor fleet re-syncs from the first post-restore
    # publish — actors are stateless consumers)

    def _counters(self) -> dict:
        return dict(ingested=self.ingested, steps=self.steps_rate.total,
                    param_version=self.param_version,
                    learner_epoch=self.learner_epoch)

    def _apply_counters(self, meta: dict) -> None:
        self.ingested = meta["ingested"]
        self.steps_rate.total = meta["steps"]
        self.param_version = meta["param_version"]
        # epoch fencing: restoring from a checkpoint IS a new learner
        # life — bump past the saved epoch so parked actors and replay
        # shards see the restart (pre-fencing checkpoints restore as
        # epoch 2: their writer was life 1 by definition)
        self.learner_epoch = int(meta.get("learner_epoch", 1)) + 1
        # a restored trainer does not owe an immediate save/log: its marks
        # continue from the restored step count
        self._last_save = self._last_log = meta["steps"]


class ApexTrainer(ConcurrentTrainer):
    """train_DQN-equivalent driver (``ApeX.py:13-82``), frame-pool edition."""

    def __init__(self, config: ApexConfig | None = None,
                 logdir: str | None = None, verbose: bool = False,
                 publish_min_seconds: float = 0.2,
                 train_ratio: float | None = None,
                 min_train_ratio: float | None = None,
                 checkpoint_dir: str | None = None,
                 pool=None, respawn_workers: bool = True):
        """Replay-ratio control (samples consumed per transition ingested):

        ``train_ratio`` caps the ratio — the learner idles when it has
        consumed too much per ingested transition (prevents overfitting a
        slow actor fleet).  ``min_train_ratio`` FLOORS it — when the learner
        falls behind, chunk draining pauses so the bounded queue
        backpressures the actors (workers block on put), throttling
        collection to what the learner can digest.  Without the floor, a
        fast fleet can flood the buffer with data from a still-bad policy
        faster than the learner improves it — the failure mode does not
        exist in the reference only because its single-GPU learner was never
        outpaced this way.  ``None`` = fully decoupled (reference behavior).
        """
        self.cfg = cfg = config or ApexConfig()
        self.key = set_global_seeds(cfg.env.seed)
        self.publish_min_seconds = publish_min_seconds
        self.train_ratio = train_ratio
        self.min_train_ratio = min_train_ratio
        self.respawn_workers = respawn_workers
        if (train_ratio is not None and min_train_ratio is not None
                and min_train_ratio > train_ratio):
            raise ValueError("min_train_ratio must be <= train_ratio")

        self.model_spec, frame_shape, frame_dtype, frame_stack = \
            dqn_env_specs(cfg)

        self.model = make_q_network(self.model_spec)
        note_torso(self.model, "trainer")
        self.replay = FramePoolReplay(
            capacity=cfg.replay.capacity, frame_shape=frame_shape,
            frame_stack=frame_stack, frame_dtype=np.dtype(frame_dtype).name,
            alpha=cfg.replay.alpha, eps=cfg.replay.eps)
        check_hbm_budget(self.replay.hbm_bytes(), cfg.replay.hbm_budget_gb,
                         "frame-pool replay", cfg.replay.capacity)
        lc = cfg.learner
        optimizer = make_optimizer(
            lr=lc.lr, decay=lc.rmsprop_decay, eps=lc.rmsprop_eps,
            centered=lc.rmsprop_centered, max_grad_norm=lc.max_grad_norm,
            lr_decay_steps=lc.lr_decay_steps, lr_decay_rate=lc.lr_decay_rate)
        stacked = frame_shape[:-1] + (frame_stack * frame_shape[-1],)
        self.key, init_key = jax.random.split(self.key)
        self.train_state = create_train_state(
            self.model, optimizer, init_key,
            jnp.zeros((1,) + stacked, frame_dtype))
        self.core = LearnerCore(
            apply_fn=learner_apply_fn(self.model), replay=self.replay,
            optimizer=optimizer,
            batch_size=lc.batch_size,
            target_update_interval=lc.target_update_interval)
        self._policy = jax.jit(make_policy_fn(self.model))

        # pool injection: the multi-host learner passes a socket-backed
        # RemotePool; default is the in-host process pool
        if pool is not None:
            self.pool = pool
        else:
            from apex_tpu.native.ring import chunk_slot_bytes
            from apex_tpu.replay.frame_chunks import FRAME_MARGIN
            slot = chunk_slot_bytes(
                frame_dim=int(np.prod(frame_shape)),
                frame_dtype_size=np.dtype(frame_dtype).itemsize,
                kf=cfg.actor.send_interval + FRAME_MARGIN,
                k=cfg.actor.send_interval, stack=frame_stack)
            self.pool = ActorPool(cfg, self.model_spec,
                                  chunk_transitions=cfg.actor.send_interval,
                                  shm_slot_bytes=slot)

        self.n_dp = int(np.prod(lc.mesh_shape))
        if self.n_dp > 1:
            self._init_sharded()
        else:
            self.replay_state = self.replay.init()
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

    # _init_sharded: ConcurrentTrainer (shared with the AQL family)

    # -- evaluation --------------------------------------------------------

    def evaluate(self, episodes: int = 10, epsilon: float = 0.0,
                 max_steps: int = 10_000) -> float:
        """True-score eval on the unclipped, full-episode env
        (``eval.py:49-87``)."""
        if not hasattr(self, "_eval_env"):
            self._eval_env = make_eval_env(self.cfg.env.env_id, self.cfg.env,
                                           seed=self.cfg.env.seed + 999)
        rewards = []
        for ep in range(episodes):
            obs, _ = self._eval_env.reset(seed=self.cfg.env.seed + 1000 + ep)
            total, done, steps = 0.0, False, 0
            while not done and steps < max_steps:
                self.key, k = jax.random.split(self.key)
                a, _ = self._policy(self.train_state.params,
                                    np.asarray(obs)[None],
                                    jnp.float32(epsilon), k)
                obs, r, term, trunc, _ = self._eval_env.step(int(a[0]))
                total += float(r)
                done = term or trunc
                steps += 1
            rewards.append(total)
        return float(np.mean(rewards))
