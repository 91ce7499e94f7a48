"""Configuration system.

Replaces the reference's single shared argparse (``origin_repo/arguments.py:5-83``)
plus env-var role identity (``origin_repo/actor.py:18-25``,
``origin_repo/learner.py:23-27``) with typed dataclasses.  Defaults reproduce the
reference's hyperparameters behind its published numbers
(``origin_repo/arguments.py:9-74``), with TPU-specific knobs added (mesh shape,
compute dtype, replay residency).
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field
from typing import Any, Sequence


@dataclass(frozen=True)
class ReplayConfig:
    """Prioritized replay hyperparameters (reference: arguments.py:41-50)."""

    # PER-CHIP transition capacity.  The reference's single buffer holds 2e6
    # transitions on a 128GB replay host (arguments.py:45-46); here replay is
    # HBM-resident and SHARDED over the dp mesh, so per-chip capacity stays
    # modest (2**19 ~ 524k transitions ~ 4.1 GiB of 84x84 frames) and an
    # 8-chip slice holds 2**22 ~ 4.2M transitions total — above reference
    # parity without overflowing any one chip's 16GB HBM.
    capacity: int = 2 ** 19
    alpha: float = 0.6               # priority exponent
    beta: float = 0.4                # IS-weight exponent (annealed toward 1 by drivers)
    # Transitions over which beta anneals linearly to 1.  A fixed horizon —
    # NOT derived from warmup, which CI configs shrink to nothing (full IS
    # correction against a tiny fresh buffer is high-variance and was
    # destabilizing the concurrent pipeline's learning).
    beta_anneal: int = 500_000
    warmup: int = 50_000             # learner gated until this many transitions (arguments.py:47-48)
    # Clamp floor for priorities entering the sum/min trees (pre-alpha).  The
    # reference's ADDITIVE 1e-6 on |td| (utils.py:77, memory.py:464) stays
    # hard-coded in the loss/actor priority calcs, exactly as it does there.
    eps: float = 1e-6
    # TPU knobs
    frame_pool: bool = False         # dedup frame-pool storage layout for stacked pixels
    # Drivers refuse to allocate a replay shard whose estimated footprint
    # exceeds this (leaving headroom for params/activations on a 16GB chip).
    hbm_budget_gb: float = 12.0

    def __post_init__(self) -> None:
        if self.capacity <= 0 or self.capacity & (self.capacity - 1):
            raise ValueError(f"capacity must be a power of 2, got {self.capacity}")


@dataclass(frozen=True)
class LearnerConfig:
    """Learner-loop hyperparameters (reference: arguments.py:49-66, ApeX.py:37)."""

    batch_size: int = 512
    lr: float = 6.25e-5
    # StepLR(step_size=1000, gamma=0.99) parity (DQN.py:39, ApeX.py:38);
    # 0 = constant lr (the reference's distributed learner,
    # origin_repo/learner.py:145)
    lr_decay_steps: int = 1000
    lr_decay_rate: float = 0.99
    rmsprop_decay: float = 0.95      # torch RMSprop alpha (ApeX.py:37)
    rmsprop_eps: float = 1.5e-7
    rmsprop_centered: bool = True
    gamma: float = 0.99
    n_steps: int = 3
    max_grad_norm: float = 40.0
    target_update_interval: int = 2500
    publish_interval: int = 25       # param publish period, learner steps
    save_interval: int = 5000
    # TPU knobs
    compute_dtype: str = "bfloat16"  # MXU-native matmul dtype; params stay f32
    # The Q-network's torso (--torso): "dueling" is the reference's
    # Nature-CNN / MLP dueling net; any other name is a preset of a
    # token-torso family under apex_tpu/models/ (make_q_network finds it)
    torso: str = "dueling"
    ingest_chunk: int = 512          # transitions folded into each fused step
    mesh_shape: tuple[int, ...] = (1,)
    # >1: when at least this many chunks are queued (i.e. the learner is
    # the bottleneck), drain and run them as ONE lax.scan dispatch of
    # scan_steps bit-identical fused steps — amortizes host dispatch
    # latency (training/learner.py:scan_fused_steps).  Both families (DQN
    # and AQL), single-shard only; on a dp>1 mesh it quietly stays at 1.
    scan_steps: int = 1


@dataclass(frozen=True)
class ActorConfig:
    """Actor-fleet hyperparameters (reference: arguments.py:9-40, batchrecorder.py:121)."""

    n_actors: int = 8
    # Env slots driven by EACH worker process through one batched policy
    # call per step (apex_tpu/actors/vector.py).  The exploration ladder
    # spans all n_actors * n_envs_per_actor slots, so 8 x 32 reproduces the
    # exploration spectrum of 256 scalar actor processes.  1 = the
    # reference's one-env-per-process topology (batchrecorder.py:79).
    n_envs_per_actor: int = 1
    send_interval: int = 50          # transitions per shipped batch
    update_interval: int = 400       # env steps between param refresh polls
    eps_base: float = 0.4            # per-actor ladder eps_base^(1 + i/(N-1)*eps_alpha)
    eps_alpha: float = 7.0
    # Anneal each worker's epsilon 1.0 -> its ladder value over this many of
    # its own env steps (exp decay).  0 = reference behavior (fixed ladder,
    # batchrecorder.py:121) — correct for large fleets where low-eps actors
    # can free-ride on the explorers' data; small fleets (CI, few actors)
    # need the anneal or greedy actors feed degenerate data from step 0.
    eps_anneal_steps: int = 0
    # None = the env's own limit; reference Atari deployments use 50_000
    # (wrapper.py:282-298 TimeLimit via arguments.py max_episode_length)
    max_episode_length: int | None = None
    # In-host chunk transport: the native shared-memory ring
    # (apex_tpu/native/) when it is buildable, else mp.Queue.  The reference
    # always pays mp.Queue's pickle->pipe->feeder-thread copies
    # (batchrecorder.py:111-112).
    shm_data_plane: bool = True
    # Ring slot size; 0 = drivers compute it from the frame spec (or a 4MiB
    # default when they can't).  A chunk message must fit one slot.
    shm_slot_bytes: int = 0
    # Alternating double-buffered sampling (actors/vector.py, the Stooke &
    # Abbeel alternating sampler): the B env slots split into two
    # half-groups whose jitted policy calls dispatch asynchronously, so one
    # group's env stepping overlaps the other group's inference.  Per-group
    # PRNG keys derive via fold_in(group) on the per-step key IN BOTH
    # MODES, so on/off trajectories are bit-identical per slot
    # (tests/test_vector.py pins it) — the knob is a pure scheduling A/B.
    # Families fall back to the serial interleave when B < 2 (one group:
    # nothing to overlap).  The win needs a spare host core or an off-host
    # policy device; a 1-core box shows parity, not regression.
    double_buffer: bool = True
    # Vector steps between periodic ActorTimingStat emissions (policy-wait
    # / env-step / drain fractions + frames/s, shipped on the stat queue
    # and surfaced in the learner logs and ``actor_plane()``).  0 = off.
    timing_interval: int = 256
    # Centralized batched inference (apex_tpu/infer_service): instead of
    # running the policy on the actor host's CPU, each half-group's
    # stacked observations ship to the `--role infer` server, which
    # batches requests ACROSS actor processes into one device dispatch
    # and returns (actions, q, param_version).  Rides the double-buffer
    # split: one group's round-trip overlaps the other group's env
    # stepping.  Remote-served results are BIT-IDENTICAL to the local
    # policy for the same params + key chain (tests/test_infer.py pins
    # it), and every actor keeps its local policy as the fallback — a
    # wedged/dead server costs comms.infer_wait_s once, then the actor
    # runs local until the re-probe finds the server again.  DQN vector
    # families only (the AQL/R2D2 remote families are ROADMAP items).
    remote_policy: bool = False


@dataclass(frozen=True)
class EnvConfig:
    env_id: str = "SeaquestNoFrameskip-v4"   # reference default (arguments.py:9-10)
    frame_stack: int = 4
    frame_skip: int = 4
    episodic_life: bool = True
    clip_rewards: bool = True
    seed: int = 1122                 # reference default seed (arguments.py:14)
    # ApexTokens-v0: ids a context holds and the vocabulary they are drawn
    # from (a frame is u8[2 * token_context]); the CLI sets both from the
    # --torso preset, whose model reads such frames
    token_context: int = 16
    token_vocab: int = 64


@dataclass(frozen=True)
class R2D2Config:
    """Recurrent-family (R2D2-style) hyperparameters.

    The reference lists recurrent DQN as an unimplemented TODO
    (``README.md:5``); these defaults follow the R2D2 recipe scaled to the
    reference's network widths.  Sequence length stored per replay item is
    ``burn_in + unroll + n_steps``.
    """

    burn_in: int = 8            # state-warmup prefix, no loss/gradient
    unroll: int = 16            # loss positions per sequence
    # sequence start spacing; None derives unroll // 2 (R2D2's 1/2
    # overlap) so raising unroll keeps the documented overlap invariant
    stride: int | None = None
    lstm_features: int = 128    # recurrent width (reference head scale;
                                # R2D2 itself uses 512 — raise for Atari)
    # sequences per ingest batch / pool message — ONE constant shared by
    # the single-process driver, the concurrent trainer, and the socket
    # actor role so every message has the same fixed shape (the scan
    # dispatch and shm slot sizing both assume it)
    sequence_group: int = 4


@dataclass(frozen=True)
class AQLConfig:
    """AQL proposal-action Q-learning knobs (reference: model.py:170, AQL.py:41-42)."""

    propose_sample: int = 100
    uniform_sample: int = 400
    action_var: float = 0.25
    proposal_lr: float = 1e-4
    q_lr: float = 1e-4
    entropy_coef: float = 0.01
    # CosineAnnealingLR(T_max=max_step, eta_min=lr/1000) horizon for the
    # single-process driver (AQL.py:18,48-49); the concurrent driver
    # ignores it (AQL_dis constructs no schedulers)
    cosine_lr_steps: int = 1_000_000


@dataclass(frozen=True)
class CommsConfig:
    """Multi-host plane (reference: replay.py:48-74, learner.py:57-68, actor.py:110-114)."""

    replay_ip: str = "127.0.0.1"
    learner_ip: str = "127.0.0.1"
    batch_port: int = 51001          # actor -> replay transition stream
    param_port: int = 52001          # learner PUB param broadcast
    barrier_port: int = 52002        # startup handshake ROUTER
    max_outstanding_sends: int = 3   # actor credit window (actor.py:110-112)
    param_hwm: int = 3               # PUB high-water mark (learner.py:60)
    status_port: int = 52003         # fleet-status REP (--role status)
    # Learner-side decoder threads unpickling chunk payloads off the
    # socket thread — the reference's N recv_batch pullers
    # (learner.py:71-114, count arguments.py:73-74)
    n_recv_batch_procs: int = 4
    # -- fleet control plane (apex_tpu/fleet) ------------------------------
    # Every role beats on the stat channel at this cadence; the learner's
    # FleetRegistry drives the JOINING -> ALIVE -> SUSPECT -> DEAD machine
    # from the thresholds below.  dead_after_s must comfortably exceed
    # suspect_after_s, and suspect_after_s the beat interval, or healthy
    # peers flap under ordinary queue backpressure.
    heartbeat_interval_s: float = 2.0
    suspect_after_s: float = 6.0
    dead_after_s: float = 15.0
    # Actor/evaluator park threshold: no param publish for this long means
    # the learner is gone (a live learner republishes at least every
    # ~10 * publish_min_seconds ~ 2s) — stop stepping, keep env + builder
    # state, and retry the barrier/param race with jittered backoff.
    park_after_s: float = 10.0
    rejoin_backoff_s: float = 1.0    # first retry delay (doubles per miss)
    rejoin_backoff_max_s: float = 8.0
    rejoin_attempt_s: float = 5.0    # per-attempt barrier/param race window
    # -- registry reactions (PR 8: the registry ACTS, not just observes) ---
    # When at least this fraction of actor-role peers is DEAD, the learner
    # RELAXES its replay-ratio floor (min_train_ratio) so the surviving
    # actors are not backpressured into starvation by a throughput target
    # sized for the full fleet; the floor restores as peers rejoin.
    # None = never relax.
    relax_floor_dead_frac: float | None = 0.5
    # A dead/respawned shard's traffic falls back to the learner; the
    # actor re-probes the shard (credit window reset + one real send)
    # every this many seconds so a RECOVERED shard gets its stream back
    # without an actor restart (the stale credit window used to wedge it
    # out forever).
    shard_reprobe_s: float = 10.0
    # -- sharded replay service (apex_tpu/replay_service) ------------------
    # 0 = in-learner replay (replay dissolved into the learner's HBM, the
    # default since PR 0).  N > 0 restores the reference's standalone
    # replay role (origin_repo/replay.py) as N shard processes: actors
    # hash sealed chunks to shards (stable chunk-id hash, per-shard
    # credit window), each shard owns one FramePoolReplay segment tree
    # and serves pre-sampled batches, and the learner pulls round-robin
    # + ships priority write-backs to the owning shard.
    replay_shards: int = 0
    # shard s binds ONE ROUTER at replay_port_base + s (chunk ingest from
    # actors AND pull/prio traffic from the learner multiplex on it)
    replay_port_base: int = 53001
    # strict: a shard samples batch j+1 only after batch j's priority
    # write-back lands (and defers the next ingest behind it), so the
    # shard replays the exact in-learner ingest->sample->write-back
    # interleave — N=1 is bit-identical to in-learner replay (pinned in
    # tests/test_replay_service.py).  False = the reference's loose
    # semantics: pre-sample ahead, apply write-backs whenever they land.
    replay_strict_order: bool = True
    # loose-mode pre-sample depth (batches staged ahead of the learner's
    # pulls); strict mode is structurally depth-1
    replay_presample: int = 2
    # Shard durability: a shard snapshots its whole replay state (segment
    # tree + frame pool + PRNG chain + counters) to the snapshot dir
    # (--replay-snapshot-dir) at most every this many seconds — atomic
    # tmp+rename, same discipline as fleet_summary.json — and a
    # supervised respawn restores it, rejoining WARM instead of refilling
    # from live streams.  0 = snapshots off (the pre-PR-8 behavior).
    replay_snapshot_s: float = 0.0
    # -- centralized inference plane (apex_tpu/infer_service) --------------
    # `--role infer` binds ONE ROUTER here; remote-policy actors connect
    # their per-worker DEALERs to it (ActorConfig.remote_policy).
    infer_port: int = 54001
    infer_ip: str = "127.0.0.1"      # host the infer server runs on
    # Adaptive request coalescing: the server collects policy requests
    # until infer_batch_max are queued OR infer_window_ms elapsed since
    # the first, then runs them as ONE scan-stacked device dispatch
    # (request count padded to pow2-quantized widths so compile count
    # stays bounded — the PR 2 scan-stack discipline).
    infer_batch_max: int = 16
    infer_window_ms: float = 2.0
    # Actor-side fallback: a request unanswered for this long falls back
    # to the LOCAL policy (bit-identical by the parity contract, so the
    # fallback changes scheduling, never trajectories) and marks the
    # server down — a dead/wedged infer server never stalls an actor
    # beyond one wait (the learner-direct fallback contract from the
    # replay service, applied to inference).
    infer_wait_s: float = 1.0
    # While the server is marked down the actor runs local-only and
    # re-probes with one real request every this many seconds, so a
    # supervised respawn gets its traffic back without an actor restart
    # (the PR 8 dead-shard re-probe discipline).
    infer_reprobe_s: float = 5.0
    # Keep the server's params device-placed (device_put on every
    # subscribed publish).  On a shared-device deployment this is the
    # device-to-device copy path; skipped automatically on the CPU
    # backend (same gate as the ingest pipeline's staging ring).
    infer_device_params: bool = False
    # -- sharded serving tier (apex_tpu/serving) ---------------------------
    # N infer servers, shard s binding infer_port + s (the replay
    # service's port-base discipline); remote-policy workers route to a
    # home shard by a stable identity hash (serving/shard.py), each
    # shard keeping the single-server down-marker/fallback/re-probe
    # semantics.  1 (default) IS the PR 9 topology — one server on
    # infer_port.  The whole fleet must agree, so it rides COMMON like
    # the ports.  The `--role serve-ctl` deployment controller canaries
    # new model versions onto a shard fraction via the servers'
    # epoch-fenced param gate (serving/deploy.py).
    infer_shards: int = 1
    # -- wire codec (apex_tpu/runtime/codec.py) ----------------------------
    # Chunk wire codec for every ChunkSender this process builds: "raw"
    # (legacy pickle, bit-identical wire), "delta" (XOR frame-delta +
    # RLE, the ~sparse Catch shape) or "dict" (per-chunk deflate
    # dictionary, the pixel-stack shape).  Empty = resolve from the
    # APEX_WIRE_CODEC env twin, default raw.  Receivers negotiate per
    # chunk off the wire tag, so senders never need fleet agreement.
    wire_codec: str = ""
    # Sparse param-delta publish: deltas carry only the leaves changed
    # since the last keyframe; first publish and every learner-epoch
    # bump stay dense, so fencing semantics are untouched.
    param_delta: bool = False
    # Dense keyframe at least every N publishes (bounds how long a
    # CONFLATE subscriber that missed a keyframe waits for recovery).
    param_keyframe_every: int = 16


@dataclass(frozen=True)
class ApexConfig:
    """Top-level bundle; one object configures every role."""

    env: EnvConfig = field(default_factory=EnvConfig)
    replay: ReplayConfig = field(default_factory=ReplayConfig)
    learner: LearnerConfig = field(default_factory=LearnerConfig)
    actor: ActorConfig = field(default_factory=ActorConfig)
    aql: AQLConfig = field(default_factory=AQLConfig)
    r2d2: R2D2Config = field(default_factory=R2D2Config)
    comms: CommsConfig = field(default_factory=CommsConfig)

    def replace(self, **sections: Any) -> "ApexConfig":
        return dataclasses.replace(self, **sections)


@dataclass(frozen=True)
class RoleIdentity:
    """Process role identity, injected via env vars by deploy scripts
    (reference: deploy/actor.sh:4-9; actor.py:18-25)."""

    role: str = "learner"            # learner | actor | replay | evaluator
    actor_id: int = 0
    n_actors: int = 1
    replay_ip: str = "127.0.0.1"
    learner_ip: str = "127.0.0.1"

    @classmethod
    def from_env(cls, environ: os._Environ | dict | None = None) -> "RoleIdentity":
        e = dict(environ if environ is not None else os.environ)
        return cls(
            role=e.get("APEX_ROLE", "learner"),
            actor_id=int(e.get("ACTOR_ID", 0)),
            n_actors=int(e.get("N_ACTORS", 1)),
            replay_ip=e.get("REPLAY_IP", "127.0.0.1"),
            learner_ip=e.get("LEARNER_IP", "127.0.0.1"),
        )


def small_test_config(
    capacity: int = 1024,
    batch_size: int = 32,
    n_actors: int = 2,
    env_id: str = "ApexCartPole-v0",
) -> ApexConfig:
    """A config sized for CI: tiny buffer, tiny batch, numpy-native env."""
    return ApexConfig(
        env=EnvConfig(env_id=env_id, frame_stack=1, clip_rewards=False,
                      episodic_life=False),
        replay=ReplayConfig(capacity=capacity, warmup=max(2 * batch_size, 64)),
        learner=LearnerConfig(batch_size=batch_size, ingest_chunk=batch_size,
                              target_update_interval=100, compute_dtype="float32"),
        actor=ActorConfig(n_actors=n_actors, send_interval=16),
    )


def flat_dict(cfg: ApexConfig) -> dict[str, Any]:
    """Pretty/loggable flattened view (reference: utils.print_args, utils.py:9-12)."""
    out: dict[str, Any] = {}
    for section in dataclasses.fields(cfg):
        sub = getattr(cfg, section.name)
        for f in dataclasses.fields(sub):
            out[f"{section.name}.{f.name}"] = getattr(sub, f.name)
    return out
