"""In-host actor pool: worker processes feeding the learner's device replay.

Capability parity with the reference's ``BatchRecorder``/``Worker``
(``batchrecorder.py:79-152``) redesigned for the TPU topology:

* Each worker is an ``mp.Process`` with its own env, its own CPU-jitted
  policy, and a :class:`~apex_tpu.replay.frame_chunks.FrameChunkBuilder` —
  transitions ship as fixed-shape frame chunks ready for device ingest,
  priorities already computed from acting-time Q-values.
* Per-worker exploration ladder ``eps_base ** (1 + i/(N-1) * eps_alpha)``
  (``batchrecorder.py:121``, the Ape-X schedule).
* Unlike the reference's synchronous task rounds (``record_batch`` +
  ``queue.join`` — and the eager-call quirk at ``ApeX.py:94-97`` that made
  acting and learning fully sequential), workers run CONTINUOUSLY and the
  learner drains a bounded chunk queue — acting and the TPU step overlap.
* Param distribution is latest-wins, version-stamped: the learner puts
  ``(version, params)`` on per-worker depth-2 queues; workers drain and keep
  the newest (the reference's SUB+CONFLATE semantics, ``actor.py:40-49``),
  polling every ``update_interval`` env steps (``actor.py:97-103``).

Workers are forced onto the CPU JAX platform: a chip belongs to one
process at a time and the parent (the learner) owns it, so a child that
reached for the default platform would fail or hang.  The pool sets
``JAX_PLATFORMS=cpu`` in the parent's environment around ``Process.start``
so children inherit it before their interpreter boots.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import queue as queue_lib
import time
from dataclasses import dataclass
from typing import Any

import numpy as np

from apex_tpu.config import ApexConfig


def actor_epsilons(n: int, eps_base: float = 0.4,
                   eps_alpha: float = 7.0) -> np.ndarray:
    """The Ape-X per-actor exploration ladder (``batchrecorder.py:121``)."""
    if n == 1:
        return np.asarray([eps_base], np.float64)
    i = np.arange(n, dtype=np.float64)
    return eps_base ** (1.0 + i / (n - 1) * eps_alpha)


@dataclass
class EpisodeStat:
    actor_id: int
    reward: float
    length: int
    param_version: int = 0          # staleness observability
    # stats the worker dropped on a full stat_queue since its LAST
    # successful put (the drop itself stays lossy — bounded queue — but
    # the loss is now counted, so reward/staleness accounting is
    # auditably incomplete rather than silently incomplete)
    dropped_stats: int = 0


@dataclass
class ActorTimingStat:
    """Periodic actor-plane observability message (one per worker every
    ``ActorConfig.timing_interval`` vector steps): where the worker's wall
    time went — policy-wait vs env-step vs drain — plus its frames/s and
    the host gap between policy dispatches.  Ships on the same stat queue
    as :class:`EpisodeStat`; the learner's stats drain dispatches on type
    (``training/apex.py``) and the e2e bench aggregates these into its
    ``actor_plane`` section."""

    actor_id: int                   # worker index (process), not env slot
    frames_per_sec: float           # env frames/s over the window
    policy_wait_frac: float         # blocking materialization of outputs
    env_step_frac: float            # env.step + builder recording
    drain_frac: float               # chunk poll + queue put (backpressure)
    dispatch_gap_ms_p50: float      # host gap between policy dispatches
    vector_steps: int               # window length in vector steps
    double_buffer: bool             # mode the worker is running
    dropped_stats: int = 0          # same carry semantics as EpisodeStat


def drain_builder_chunks(builder) -> list[dict]:
    """FrameChunkBuilder chunks -> pool messages.  THE one place the chunk
    message shape is defined — every builder-based family (DQN scalar and
    vector, pixel AQL scalar and vector) drains through here.  Each
    message is born with its lineage span ("sealed" hop — obs plane,
    :mod:`apex_tpu.obs.spans`); the timestamps ride message METADATA
    beside the payload, never inside it."""
    from apex_tpu.obs import spans as obs_spans

    stamped = obs_spans.enabled()
    out = []
    for chunk in builder.poll():
        msg = {"payload": chunk,
               "priorities": chunk.pop("priorities"),
               "n_trans": int(chunk["n_trans"])}
        if stamped:
            msg[obs_spans.SPAN_KEY] = [obs_spans.new_span(hop="sealed")]
        out.append(msg)
    return out


class DQNWorkerFamily:
    """DQN acting/recording hooks for :func:`worker_loop` (reference
    ``Worker.run``, ``batchrecorder.py:79-98``): epsilon-greedy over the
    builder's acting stack, frame-chunk emission."""

    def __init__(self, cfg: ApexConfig, model_spec: dict, seed: int,
                 chunk_transitions: int):
        import jax

        from apex_tpu.envs.registry import make_env, unstacked_env_spec
        from apex_tpu.models import make_q_network
        from apex_tpu.models.dueling import make_policy_fn
        from apex_tpu.replay.frame_chunks import FrameChunkBuilder

        self.seed = seed
        self.env = make_env(cfg.env.env_id, cfg.env, seed=seed,
                            max_episode_steps=cfg.actor.max_episode_length,
                            stack_frames=False)
        frame_shape, frame_dtype, frame_stack = unstacked_env_spec(
            self.env, cfg.env)
        self.policy = jax.jit(make_policy_fn(make_q_network(model_spec)))
        self.builder = FrameChunkBuilder(
            cfg.learner.n_steps, cfg.learner.gamma, frame_stack, frame_shape,
            chunk_transitions=chunk_transitions, frame_dtype=frame_dtype)

    def begin_episode(self, obs) -> None:
        self.builder.begin_episode(obs)

    def step(self, params, obs, epsilon: float, key):
        import jax.numpy as jnp
        stack = self.builder.current_stack()
        actions, q = self.policy(params, stack[None], jnp.float32(epsilon),
                                 key)
        action = int(actions[0])
        next_obs, reward, term, trunc, _ = self.env.step(action)
        self.builder.add_step(action, float(reward), np.asarray(q[0]),
                              next_obs, bool(term), bool(trunc))
        return next_obs, float(reward), bool(term), bool(trunc)

    def poll_msgs(self) -> list[dict]:
        return drain_builder_chunks(self.builder)


def worker_loop(actor_id: int, cfg: ApexConfig, family, chunk_queue,
                param_queue, stat_queue, stop_event, epsilon: float) -> None:
    """The family-agnostic worker lifecycle: interruptible wait for the
    first publish, CONFLATE param polls every ``update_interval`` steps
    (``actor.py:97-103``), exploration-epsilon anneal, chunk shipping with
    backpressure, episode stats, clean shutdown.  The acting/recording
    specifics live in ``family`` (:class:`DQNWorkerFamily`,
    ``apex_tpu.actors.aql.AQLWorkerFamily``) — one lifecycle, N families,
    where the reference maintains near-copies (``batchrecorder.py`` vs
    ``batchrecoder_AQL.py``)."""
    import math

    import jax

    from apex_tpu.fleet.heartbeat import HeartbeatEmitter
    from apex_tpu.obs import spans as obs_spans
    from apex_tpu.obs.trace import get_ring, set_process_label

    from apex_tpu.tenancy import namespace as tenancy_ns

    key = jax.random.key(family.seed)
    env = family.env
    # tenant-qualified identity (PR 13): the worker's beats must agree
    # with the role-level wire identity (park heartbeats, chunk-arrival
    # liveness) or a tenant's actor shows up TWICE in its registry;
    # the default tenant qualifies to the bare name
    identity = tenancy_ns.qualify(tenancy_ns.current_tenant(),
                                  f"actor-{actor_id}")
    set_process_label(identity)
    ring = get_ring()
    # fleet liveness: periodic Heartbeats on the stat channel — the
    # in-host trainer and the socket learner's registry consume the same
    # message (the socket adapters expose wire counters / park state)
    beat = HeartbeatEmitter(
        identity, role="actor",
        interval_s=cfg.comms.heartbeat_interval_s,
        counters_fn=getattr(chunk_queue, "wire_counters", None),
        park_fn=getattr(param_queue, "park_state", None),
        gauges_fn=getattr(chunk_queue, "wire_gauges", None))

    def _maybe_beat(version: int) -> None:
        hb = beat.maybe_beat(version)
        if hb is not None:
            try:
                stat_queue.put_nowait(hb)
            except queue_lib.Full:
                pass                # droppable telemetry, like every stat

    version = 0
    while True:                                  # block for first publish,
        if stop_event.is_set():                  # but stay interruptible
            env.close()
            return
        _maybe_beat(version)
        try:
            version, params = param_queue.get(timeout=0.5)
            break
        except queue_lib.Empty:
            continue

    anneal = cfg.actor.eps_anneal_steps
    total_steps = 0

    def current_eps() -> float:
        if not anneal:
            return epsilon
        return epsilon + (1.0 - epsilon) * math.exp(-total_steps / anneal)

    steps_since_poll = 0
    obs, _ = env.reset(seed=family.seed)
    family.begin_episode(obs)
    ep_reward, ep_len = 0.0, 0
    dropped = 0                     # stats lost to a full queue, carried
    #                                 on the next successful put

    while not stop_event.is_set():
        steps_since_poll += 1
        if steps_since_poll >= cfg.actor.update_interval:
            steps_since_poll = 0
            try:
                while True:                      # keep only the newest
                    version, params = param_queue.get_nowait()
            except queue_lib.Empty:
                pass

        key, akey = jax.random.split(key)
        obs, reward, terminated, truncated = family.step(
            params, obs, current_eps(), akey)
        total_steps += 1
        ep_reward += reward
        ep_len += 1
        beat.tick()
        _maybe_beat(version)

        for msg in family.poll_msgs():
            beat.note_chunk()
            obs_spans.mark_send(msg, version)
            with ring.span("chunk_put", "chunk-drain"):
                chunk_queue.put(("chunk", actor_id, msg))  # blocks when full
        if terminated or truncated:
            try:
                stat_queue.put_nowait(
                    EpisodeStat(actor_id, ep_reward, ep_len, version,
                                dropped_stats=dropped))
                dropped = 0
            except queue_lib.Full:
                dropped += 1
            ep_reward, ep_len = 0.0, 0
            obs, _ = env.reset()
            family.begin_episode(obs)

    env.close()


def _worker_main(actor_id: int, cfg: ApexConfig, model_spec: dict,
                 chunk_queue: mp.Queue, param_queue: mp.Queue,
                 stat_queue: mp.Queue, stop_event, epsilon: float,
                 chunk_transitions: int) -> None:
    """DQN worker process body.  Imports (and therefore jax platform
    selection) happen in the child, under the CPU env set by the parent."""
    family = DQNWorkerFamily(cfg, model_spec,
                             seed=cfg.env.seed + 1000 * (actor_id + 1),
                             chunk_transitions=chunk_transitions)
    worker_loop(actor_id, cfg, family, chunk_queue, param_queue, stat_queue,
                stop_event, epsilon)


class ActorPool:
    """Fan-out/fan-in around N continuously-running actor workers
    (reference ``BatchRecorder``, ``batchrecorder.py:100-152``).

    ``worker_fn`` is the process body — the queue/lifecycle machinery is
    family-agnostic; the DQN body is the default and the AQL family plugs
    in its own (reference ``batchrecoder_AQL.py`` is a near-copy of
    ``batchrecorder.py`` for the same reason, solved here by injection).
    """

    def __init__(self, cfg: ApexConfig, model_spec: dict,
                 chunk_transitions: int, chunk_queue_depth: int = 64,
                 worker_fn=None, shm_slot_bytes: int | None = None):
        self.cfg = cfg
        n = cfg.actor.n_actors
        ctx = mp.get_context("spawn")
        self.chunk_queue = self._make_chunk_queue(
            cfg, chunk_queue_depth, shm_slot_bytes, ctx)
        self.stat_queue: mp.Queue = ctx.Queue(maxsize=1024)
        self.param_queues = [ctx.Queue(maxsize=2) for _ in range(n)]
        self.stop_event = ctx.Event()
        if cfg.actor.n_envs_per_actor > 1 or getattr(
                cfg.actor, "remote_policy", False):
            if worker_fn is not None and not getattr(worker_fn, "is_vector",
                                                     False):
                # silently falling back to one env/process would run a
                # 1/B-rate fleet with the wrong exploration spectrum
                raise ValueError(
                    "n_envs_per_actor > 1 requires a vectorized worker "
                    "body (vector_worker_main / vector_aql_worker_main); "
                    "this pool was built with "
                    f"{getattr(worker_fn, '__name__', worker_fn)}")
            if worker_fn is None:
                from apex_tpu.actors.vector import vector_worker_main
                worker_fn = vector_worker_main  # B envs, batched policy
        eps = actor_epsilons(n, cfg.actor.eps_base, cfg.actor.eps_alpha)
        self._ctx = ctx
        self._worker_fn = worker_fn or _worker_main
        self._worker_args = [
            (i, cfg, model_spec, self.chunk_queue, self.param_queues[i],
             self.stat_queue, self.stop_event, float(eps[i]),
             chunk_transitions)
            for i in range(n)
        ]
        self.procs = [ctx.Process(target=self._worker_fn, args=a,
                                  daemon=True) for a in self._worker_args]
        self._started = False
        self._last_params: tuple | None = None
        self.worker_deaths = 0          # cumulative respawn count
        # a worker that keeps dying is a systemic failure (bad env, import
        # error in the child), not flakiness: respawns are RATE-LIMITED to
        # this many per slot per window (anchored at the slot's last
        # respawn).  Sporadic crashes over a long run never retire a
        # healthy slot, and even a persistently-broken slot retries at a
        # bounded rate — so a cause fixed mid-run (path restored, OOM
        # relieved) recovers without intervention.
        self.max_respawns_per_slot = 5
        self.respawn_window_s = 600.0
        self._slot_respawns = [0] * n
        self._slot_last_respawn = [0.0] * n

    @staticmethod
    def _make_chunk_queue(cfg: ApexConfig, depth: int,
                          shm_slot_bytes: int | None, ctx):
        """The chunk plane: native shared-memory ring when available
        (:mod:`apex_tpu.native`), else mp.Queue.  Same bounded-queue
        backpressure either way; a degradation names its reason."""
        if cfg.actor.shm_data_plane:
            from apex_tpu.native import build_error, shm_available
            if shm_available():
                from apex_tpu.native.ring import ShmChunkQueue
                slot = (cfg.actor.shm_slot_bytes
                        or shm_slot_bytes or 4 * 1024 * 1024)
                name = f"apexshm-{os.getpid()}-{ShmChunkQueue.next_id()}"
                try:
                    return ShmChunkQueue(name, slot_bytes=slot, depth=depth)
                except Exception as e:  # tmpfs full / permissions
                    why = f"{type(e).__name__}: {e}"
            else:
                why = build_error() or "/dev/shm is not a directory"
            print(f"apex_tpu: shm chunk plane unavailable ({why}); "
                  f"degrading to mp.Queue", flush=True)
        return ctx.Queue(maxsize=depth)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        """Spawn workers with a CPU-pinned JAX environment (module docstring)."""
        self._spawn(self.procs)
        self._started = True

    def _spawn(self, procs) -> None:
        saved = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            for p in procs:
                p.start()
        finally:
            if saved is None:
                os.environ.pop("JAX_PLATFORMS", None)
            else:
                os.environ["JAX_PLATFORMS"] = saved

    # -- failure detection (beyond the reference: its fleets have no
    # death-handling at all — an actor crash silently shrinks the fleet
    # forever, SURVEY.md §5.3) ---------------------------------------------

    def _refresh_budget(self, i: int) -> None:
        """A full window elapsed since the slot's LAST respawn restores its
        budget (rate limit, not a lifetime cap — see __init__ comment)."""
        if (self._slot_respawns[i]
                and time.monotonic() - self._slot_last_respawn[i]
                > self.respawn_window_s):
            self._slot_respawns[i] = 0

    def dead_workers(self) -> list[int]:
        """Indices of workers that exited while the pool is live and are
        still eligible for respawn (RAPID crashers age out, see
        ``max_respawns_per_slot`` / ``respawn_window_s``)."""
        if not self._started or self.stop_event.is_set():
            return []
        out = []
        for i, p in enumerate(self.procs):
            if p.is_alive():
                continue
            self._refresh_budget(i)
            if self._slot_respawns[i] < self.max_respawns_per_slot:
                out.append(i)
        return out

    def respawn_worker(self, i: int) -> bool:
        """Replace a dead worker with a fresh process on the same slot
        (same global actor id, epsilon, seed — the fleet's exploration
        spectrum is restored, not shifted).  The newest published params
        are re-queued so the newcomer doesn't idle until the next publish.
        Returns False while the slot's rate budget is exhausted — the
        fleet runs reduced, loudly, until the window rolls over."""
        old = self.procs[i]
        if old.is_alive():
            return True
        self._refresh_budget(i)
        if self._slot_respawns[i] >= self.max_respawns_per_slot:
            return False
        old.join(timeout=0)            # reap the zombie
        self.procs[i] = self._ctx.Process(target=self._worker_fn,
                                          args=self._worker_args[i],
                                          daemon=True)
        self._spawn([self.procs[i]])
        self.worker_deaths += 1
        self._slot_respawns[i] += 1
        self._slot_last_respawn[i] = time.monotonic()
        if self._slot_respawns[i] >= self.max_respawns_per_slot:
            print(f"apex_tpu: actor slot {i} died "
                  f"{self._slot_respawns[i]}x within "
                  f"{self.respawn_window_s:.0f}s; pausing its respawns — "
                  f"running with a reduced fleet", flush=True)
        if self._last_params is not None:
            version, params = self._last_params
            self._put_latest(self.param_queues[i], version, params)
        return True

    def cleanup(self, grace_seconds: float = 10.0) -> None:
        """Stop workers (reference ``BatchRecorder.cleanup``,
        ``batchrecorder.py:148-152``).

        The chunk queue is drained CONTINUOUSLY while joining — a single
        pre-join drain would race with workers refilling it (a worker can be
        mid-``put`` or produce one more chunk before seeing the stop event)
        and the subsequent ``terminate()`` could kill a process inside
        ``Queue.put``, corrupting the queue's shared pipe."""
        self.stop_event.set()
        deadline = time.monotonic() + grace_seconds
        pending = list(self.procs)
        while pending and time.monotonic() < deadline:
            try:                       # keep unblocking producers mid-put
                while True:
                    self.chunk_queue.get_nowait()
            except queue_lib.Empty:
                pass
            pending = [p for p in pending if (p.join(timeout=0.1), p)[1]
                       .is_alive()]
        for p in pending:              # unresponsive after the grace window
            p.terminate()
            p.join(timeout=5)
        # Detach queue feeder threads: a dead child never drains its pipe, and
        # the default atexit join would hang the parent forever.
        for q in [self.chunk_queue, self.stat_queue, *self.param_queues]:
            q.cancel_join_thread()
            q.close()

    # -- data/param planes -------------------------------------------------

    def publish_params(self, version: int, params: Any) -> None:
        """Latest-wins broadcast (reference ``set_worker_weights``,
        ``batchrecorder.py:140-146``, + PUB/CONFLATE semantics)."""
        self._last_params = (version, params)
        for q in self.param_queues:
            self._put_latest(q, version, params)

    @staticmethod
    def _put_latest(q, version: int, params: Any) -> None:
        while True:      # drop the stalest entry if the depth-2 queue is full
            try:
                q.put_nowait((version, params))
                break
            except queue_lib.Full:
                try:
                    q.get_nowait()
                except queue_lib.Empty:
                    pass

    def poll_chunks(self, max_chunks: int, timeout: float = 0.0) -> list:
        """Drain up to ``max_chunks`` transition chunks."""
        out = []
        for _ in range(max_chunks):
            try:
                msg = self.chunk_queue.get(timeout=timeout) if timeout \
                    else self.chunk_queue.get_nowait()
            except queue_lib.Empty:
                break
            out.append(msg[2])
        return out

    def poll_stats(self) -> list[EpisodeStat]:
        out = []
        try:
            while True:
                out.append(self.stat_queue.get_nowait())
        except queue_lib.Empty:
            pass
        return out
