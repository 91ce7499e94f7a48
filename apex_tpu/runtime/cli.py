"""CLI entry points: `python -m apex_tpu.runtime --role ...`.

The reference is launched as four role scripts sharing one argparse
(``origin_repo/arguments.py:5-83``) with role identity injected through env
vars by the deploy scripts (``deploy/actor.sh:4-9``).  Same scheme here:
every flag has an env-var twin (flag wins), `--role` defaults to
``$APEX_ROLE``, and one binary serves every role — so the localhost
topology script and a cluster template launch identical commands.

Examples::

    # learner expecting 2 actors + 1 evaluator on this host
    python -m apex_tpu.runtime --role learner --n-actors 2 \
        --env-id ApexCartPole-v0 --total-steps 5000

    APEX_ROLE=actor ACTOR_ID=0 N_ACTORS=2 LEARNER_IP=10.0.0.2 \
        python -m apex_tpu.runtime --env-id ApexCartPole-v0

    python -m apex_tpu.runtime --role evaluator --learner-ip 10.0.0.2

    # single-process (no sockets) drivers
    python -m apex_tpu.runtime --role dqn --total-frames 20000
    python -m apex_tpu.runtime --role enjoy --checkpoint ckpt_5000.msgpack
"""

from __future__ import annotations

import argparse
import os

from apex_tpu.config import (ActorConfig, ApexConfig, AQLConfig, CommsConfig,
                             EnvConfig, LearnerConfig, ReplayConfig,
                             RoleIdentity)


def _env_bool(value: str) -> bool:
    """Env-var booleans: '0'/'false'/'no'/'' are off (bool(str) is not)."""
    return value.lower() not in ("", "0", "false", "no")


def build_parser() -> argparse.ArgumentParser:
    e = os.environ
    ident = RoleIdentity.from_env(e)
    p = argparse.ArgumentParser(
        prog="apex_tpu",
        description="TPU-native Ape-X/AQL roles (reference arguments.py)")
    p.add_argument("--role", default=ident.role,
                   choices=["learner", "actor", "evaluator", "replay",
                            "infer", "serve-ctl", "tenant-ctl", "pbt-ctl",
                            "status", "loadgen", "dqn", "aql", "r2d2",
                            "apex", "enjoy"],
                   help="socket roles: learner/actor/evaluator/replay "
                        "(one prioritized-replay shard — see "
                        "--replay-shards/--shard-id)/infer (one "
                        "batched-inference shard for --remote-policy "
                        "actors — see --infer-shards/--infer-shard-id)/"
                        "serve-ctl (the serving tier's canary "
                        "deployment controller, apex_tpu/serving)/"
                        "tenant-ctl (the multi-tenant placement "
                        "controller, apex_tpu/tenancy — admissions, "
                        "weighted shard bands, evictions)/"
                        "pbt-ctl (the population-based-training "
                        "controller, apex_tpu/population — task "
                        "ladders, exploit/explore over the "
                        "APEX_POPULATION lineage roster); "
                        "status: print the live fleet table from the "
                        "learner's registry; "
                        "loadgen: standalone on-device rollout fleet "
                        "saturating the chunk plane (training/anakin.py); "
                        "single-host drivers: dqn/aql/r2d2/apex; "
                        "enjoy: eval a checkpoint")
    p.add_argument("--family", default=e.get("APEX_FAMILY", "dqn"),
                   choices=["dqn", "aql", "r2d2"])
    p.add_argument("--torso", default="dueling",
                   help="dqn family: the Q-network's torso.  'dueling' "
                        "(default) is the reference's Nature-CNN / MLP "
                        "dueling net; 'glm47_flash_ep8' is GLM-4.7-Flash's "
                        "block (latent attention, a shared and routed "
                        "experts) at published widths, one of 8 chips' "
                        "share of each layer, over token contexts "
                        "(--env-id ApexTokens-v0, sized by the preset); "
                        "'glm47_flash_tiny' its toy "
                        "(apex_tpu/models/glm4_moe_lite.py); "
                        "'nemotron_twotower_ep16' is the causal tower of "
                        "Nemotron-Labs-TwoTower-30B-A3B (Mamba-2 mixers, "
                        "grouped-query attention, a shared and 6 of 128 "
                        "routed relu^2 experts, by its layer pattern) at "
                        "published widths, one of 16 chips' share of each "
                        "layer, 'nemotron_h_tiny' its toy "
                        "(apex_tpu/models/nemotron_h.py); "
                        "'qwen3_next_80b_ep16' is Qwen3-Next-80B-A3B's "
                        "stack (three Gated DeltaNet mixers to one gated "
                        "attention, each with a shared and 10 of 512 "
                        "softmax-routed experts) at published widths, one "
                        "of 16 chips' share of each layer, "
                        "'qwen3_next_tiny' its toy "
                        "(apex_tpu/models/qwen3_next.py); "
                        "'lfm2_8b_a1b_ep4' is LFM2-8B-A1B's stack "
                        "(double-gated short convolutions and QK-norm "
                        "grouped-query attention, a dense layer, then 4 "
                        "of 32 sigmoid-routed experts and no shared "
                        "expert; tied head) at published widths, one of 4 "
                        "chips' share of each layer, 'lfm2_tiny' its toy "
                        "(apex_tpu/models/lfm2_moe.py)")
    p.add_argument("--token-vocab", type=int, default=0,
                   help="ApexTokens-v0 under a token torso: the ids the "
                        "env draws from, which are the actions and the "
                        "vocabulary the torso holds (0 = the preset's)")
    p.add_argument("--rollout", default=e.get("APEX_ROLLOUT", "host"),
                   choices=["host", "ondevice", "fused"],
                   help="learner/apex roles: 'ondevice' co-locates an "
                        "Anakin rollout engine with the learner — env "
                        "step + epsilon-greedy policy + chunk assembly "
                        "fuse into one lax.scan on the training device, "
                        "params never leave it (jittable envs only: "
                        "ApexCatch*/ApexRally*; see envs/registry."
                        "make_jax_env).  'fused' goes further "
                        "(apex_tpu/ondevice): rollout + ingest + "
                        "prioritized sample + train + priority "
                        "write-back run as ONE jitted program per "
                        "dispatch — the host wakes once per "
                        "--steps-per-dispatch macro steps, sharded "
                        "over --mesh-dp chips (dqn family, in-learner "
                        "replay only).  'host' "
                        "(default) keeps the generic actor-process "
                        "pipeline")
    p.add_argument("--rollout-len", type=int,
                   default=int(e.get("APEX_ROLLOUT_LEN", 0)),
                   help="on-device scan length per dispatch (env steps "
                        "per slot); 0 derives the chunk size "
                        "(--send-interval twin) so each dispatch seals "
                        "about one chunk per env slot")
    p.add_argument("--steps-per-dispatch", type=int,
                   default=int(e.get("APEX_STEPS_PER_DISPATCH", 4)),
                   help="--rollout fused: macro steps (rollout segment "
                        "-> ingest -> train -> write-back) scanned into "
                        "one device dispatch (env twin "
                        "APEX_STEPS_PER_DISPATCH); the host wakes once "
                        "per dispatch for publish/checkpoint/stats")
    # multi-tenant namespace (apex_tpu/tenancy): a whole tenant's roles
    # opt in with one env export (or this flag twin); everything — wire
    # identities, chunk ids, param topics, infer requests — qualifies
    # off it.  Unset = the default tenant, byte-identical single-tenant
    # behavior.  APEX_TENANTS (JSON roster) configures the SHARED
    # planes (replay/infer shards, tenant-ctl) with every tenant's
    # spec; see tenancy/namespace.py.
    p.add_argument("--tenant", default=e.get("APEX_TENANT", ""),
                   help="this process's tenant name (env twin "
                        "APEX_TENANT; empty = the default tenant t0)")
    # env
    p.add_argument("--env-id", default=e.get("APEX_ENV_ID",
                                             "SeaquestNoFrameskip-v4"))
    p.add_argument("--seed", type=int, default=int(e.get("APEX_SEED", 1122)))
    p.add_argument("--frame-stack", type=int, default=4)
    p.add_argument("--no-clip-rewards", action="store_true")
    p.add_argument("--no-episodic-life", action="store_true")
    # identity (env-var twins are the reference's names, actor.py:18-25;
    # RoleIdentity.from_env above is the canonical reader, flags win)
    p.add_argument("--actor-id", type=int, default=ident.actor_id)
    p.add_argument("--n-actors", type=int, default=ident.n_actors)
    p.add_argument("--n-envs-per-actor", type=int,
                   default=int(e.get("N_ENVS_PER_ACTOR", 1)),
                   help="env slots per actor process, driven through one "
                        "batched policy call; the exploration ladder spans "
                        "n_actors * n_envs_per_actor slots (8 x 32 = the "
                        "256-actor spectrum in 8 processes)")
    p.add_argument("--n-evaluators", type=int,
                   default=int(e.get("N_EVALUATORS", 1)))
    p.add_argument("--send-interval", type=int,
                   default=50,
                   help="transitions per shipped chunk (the reference's "
                        "send interval); also the on-device rollout's "
                        "chunk size")
    p.add_argument("--learner-ip", default=ident.learner_ip)
    # comms ports (env twins let topology tests / multi-fleet hosts remap
    # the whole plane without code changes)
    c = CommsConfig()
    p.add_argument("--batch-port", type=int,
                   default=int(e.get("APEX_BATCH_PORT", c.batch_port)))
    p.add_argument("--param-port", type=int,
                   default=int(e.get("APEX_PARAM_PORT", c.param_port)))
    p.add_argument("--barrier-port", type=int,
                   default=int(e.get("APEX_BARRIER_PORT", c.barrier_port)))
    p.add_argument("--status-port", type=int,
                   default=int(e.get("APEX_STATUS_PORT", c.status_port)))
    # sharded replay service (apex_tpu/replay_service): the whole fleet
    # must agree on these, so they ride the shared COMMON flag set / env
    # twins like the ports above
    p.add_argument("--replay-shards", type=int,
                   default=int(e.get("APEX_REPLAY_SHARDS",
                                     c.replay_shards)),
                   help="N > 0: run prioritized replay as N standalone "
                        "shard processes (--role replay); actors hash "
                        "chunks to shards, the learner pulls pre-sampled "
                        "batches.  0 (default) = in-learner replay")
    p.add_argument("--replay-port-base", type=int,
                   default=int(e.get("APEX_REPLAY_PORT_BASE",
                                     c.replay_port_base)),
                   help="shard s binds replay_port_base + s")
    p.add_argument("--replay-ip", default=ident.replay_ip,
                   help="host the replay shards run on (env twin "
                        "REPLAY_IP); defaults to localhost")
    p.add_argument("--shard-id", type=int,
                   default=int(e.get("SHARD_ID", 0)),
                   help="replay role: this process's shard index in "
                        "[0, replay_shards)")
    p.add_argument("--replay-loose", action="store_true",
                   default=_env_bool(e.get("APEX_REPLAY_LOOSE", "")),
                   help="loose shard ordering (reference semantics: "
                        "pre-sample ahead, apply write-backs whenever "
                        "they land) instead of the default strict "
                        "lockstep that is bit-identical to in-learner "
                        "replay at N=1")
    p.add_argument("--replay-snapshot-dir",
                   default=e.get("APEX_REPLAY_SNAPSHOT_DIR"),
                   help="replay role: restore the newest shard snapshot "
                        "from here on startup (warm respawn) and keep "
                        "snapshotting at --replay-snapshot-every")
    p.add_argument("--replay-snapshot-every", type=float,
                   default=float(e.get("APEX_REPLAY_SNAPSHOT_S")
                                 or c.replay_snapshot_s),
                   help="seconds between shard snapshots (atomic "
                        "write, quiescent points only); 0 = off")
    # centralized inference plane (apex_tpu/infer_service): the whole
    # fleet must agree on the endpoint, so it rides COMMON like the
    # replay-service flags above
    p.add_argument("--remote-policy", action="store_true",
                   default=_env_bool(e.get("APEX_REMOTE_POLICY", "")),
                   help="actors ship half-group observations to the "
                        "--role infer server (one batched device "
                        "dispatch across actor hosts) instead of "
                        "running the policy locally; the local policy "
                        "stays as the bit-identical fallback after "
                        "--infer-wait")
    p.add_argument("--infer-port", type=int,
                   default=int(e.get("APEX_INFER_PORT", c.infer_port)))
    p.add_argument("--infer-ip", default=e.get("APEX_INFER_IP",
                                               c.infer_ip),
                   help="host the infer server runs on (env twin "
                        "APEX_INFER_IP); defaults to localhost")
    p.add_argument("--infer-batch-max", type=int,
                   default=int(e.get("APEX_INFER_BATCH_MAX",
                                     c.infer_batch_max)),
                   help="max requests coalesced into one scan-stacked "
                        "dispatch (also the pow2 padding cap)")
    p.add_argument("--infer-window-ms", type=float,
                   default=float(e.get("APEX_INFER_WINDOW_MS")
                                 or c.infer_window_ms),
                   help="coalesce window opened by the first queued "
                        "request")
    p.add_argument("--infer-wait", type=float,
                   default=float(e.get("APEX_INFER_WAIT")
                                 or c.infer_wait_s),
                   help="actor-side reply timeout before the local-"
                        "policy fallback (a dead server costs this "
                        "once, then re-probes every --infer-reprobe)")
    p.add_argument("--infer-reprobe", type=float,
                   default=float(e.get("APEX_INFER_REPROBE")
                                 or c.infer_reprobe_s))
    p.add_argument("--infer-device-params", action="store_true",
                   default=_env_bool(e.get("APEX_INFER_DEVICE_PARAMS",
                                           "")),
                   help="keep the infer server's params device-placed "
                        "(device_put per publish — the d2d path on a "
                        "shared-device deployment); skipped on the CPU "
                        "backend")
    # sharded serving tier (apex_tpu/serving): shard count rides COMMON
    # (clients hash to shards, so the whole fleet must agree); the
    # serve-ctl knobs are controller-local
    p.add_argument("--infer-shards", type=int,
                   default=int(e.get("APEX_INFER_SHARDS",
                                     c.infer_shards)),
                   help="N infer servers, shard s binding infer_port+s; "
                        "remote-policy workers route by a stable "
                        "identity hash (1 = the single PR 9 server)")
    p.add_argument("--infer-shard-id", type=int,
                   default=int(e.get("INFER_SHARD_ID", 0)),
                   help="infer role: this process's shard index in "
                        "[0, infer_shards)")
    # wire codec (apex_tpu/runtime/codec.py): the chunk plane's byte
    # format + the sparse param publish.  Both ride COMMON in the deploy
    # scripts for uniform fleets, but receivers negotiate per chunk off
    # the wire tag, so MIXED fleets (one actor still on raw) are fine.
    p.add_argument("--wire-codec", choices=["raw", "delta", "dict"],
                   default=(e.get("APEX_WIRE_CODEC") or "").strip()
                   or "raw",
                   help="chunk wire codec: raw = legacy pickle "
                        "(bit-identical wire, default), delta = XOR "
                        "frame-delta + RLE (~sparse Catch frames), "
                        "dict = per-chunk deflate dictionary (pixel "
                        "stacks); env twin APEX_WIRE_CODEC")
    p.add_argument("--param-delta", action="store_true",
                   default=_env_bool(e.get("APEX_PARAM_DELTA", "")),
                   help="publish sparse per-leaf param deltas with "
                        "periodic keyframes (first publish and every "
                        "learner-epoch bump stay dense); env twin "
                        "APEX_PARAM_DELTA")
    p.add_argument("--param-keyframe-every", type=int,
                   default=int(e.get("APEX_PARAM_KEYFRAME_EVERY")
                               or c.param_keyframe_every),
                   help="dense keyframe at least every N publishes in "
                        "--param-delta mode")
    p.add_argument("--serve-canary-frac", type=float,
                   default=float(e.get("APEX_SERVE_CANARY_FRAC") or 0.5),
                   help="serve-ctl: fraction of shards canarying a new "
                        "model version (lowest indices; the rest pin "
                        "the incumbent)")
    p.add_argument("--serve-soak", type=float,
                   default=float(e.get("APEX_SERVE_SOAK_S") or 60.0),
                   help="serve-ctl: seconds the canary's eval-score and "
                        "round-trip SLOs must hold before fleet-wide "
                        "promotion")
    p.add_argument("--serve-version-every", type=int,
                   default=int(e.get("APEX_SERVE_VERSION_EVERY") or 0),
                   help="serve-ctl: minimum param-version spacing "
                        "between deployments within one learner epoch "
                        "(0 = deploy on epoch changes only)")
    p.add_argument("--serve-interval", type=float,
                   default=float(e.get("APEX_SERVE_INTERVAL_S") or 5.0),
                   help="serve-ctl: seconds between control rounds "
                        "(learner probe + shard reconcile)")
    # population plane (apex_tpu/population): pbt-ctl decision knobs.
    # The lineage roster itself rides APEX_POPULATION (JSON list of
    # LineageSpec dicts) — env-only like APEX_TENANTS, so every
    # shared-plane process and the controller load the same one.
    p.add_argument("--pbt-decide", type=float,
                   default=float(e.get("APEX_PBT_DECIDE_S") or 30.0),
                   help="pbt-ctl: seconds between exploit/explore "
                        "decision rounds (probes keep the "
                        "--serve-interval cadence)")
    p.add_argument("--pbt-frac", type=float,
                   default=float(e.get("APEX_PBT_FRAC") or 0.25),
                   help="pbt-ctl: truncation fraction — the bottom "
                        "frac of each task ladder restores the top "
                        "frac's checkpoint (>= 1 lineage each)")
    p.add_argument("--pbt-resample", type=float,
                   default=float(e.get("APEX_PBT_RESAMPLE") or 0.25),
                   help="pbt-ctl: per-field probability explore "
                        "resamples from the hyperparameter band "
                        "instead of perturbing x0.8/x1.2")
    p.add_argument("--pbt-min-episodes", type=int,
                   default=int(e.get("APEX_PBT_MIN_EPISODES") or 4),
                   help="pbt-ctl: eval episodes a lineage needs behind "
                        "its score before selection judges it")
    # fleet control-plane thresholds (apex_tpu/fleet): heartbeat cadence
    # and the registry/park state-machine windows — env twins so a whole
    # topology (tests, chaos drills) retunes them without flag plumbing
    p.add_argument("--heartbeat-interval", type=float,
                   default=float(e.get("APEX_HEARTBEAT_INTERVAL",
                                       c.heartbeat_interval_s)))
    p.add_argument("--suspect-after", type=float,
                   default=float(e.get("APEX_SUSPECT_AFTER",
                                       c.suspect_after_s)))
    p.add_argument("--dead-after", type=float,
                   default=float(e.get("APEX_DEAD_AFTER", c.dead_after_s)))
    p.add_argument("--park-after", type=float,
                   default=float(e.get("APEX_PARK_AFTER", c.park_after_s)))
    # learner
    p.add_argument("--batch-size", type=int, default=512)
    p.add_argument("--lr", type=float, default=6.25e-5)
    p.add_argument("--lr-decay-steps", type=int,
                   default=int(e.get("APEX_LR_DECAY_STEPS", 1000)),
                   help="StepLR parity (DQN.py:39): lr *= rate every this "
                        "many learner steps; 0 = constant lr")
    p.add_argument("--lr-decay-rate", type=float,
                   default=float(e.get("APEX_LR_DECAY_RATE", 0.99)))
    p.add_argument("--gamma", type=float, default=0.99)
    p.add_argument("--n-steps", type=int, default=3)
    p.add_argument("--target-update-interval", type=int, default=2500)
    p.add_argument("--save-interval", type=int,
                   default=int(e.get("APEX_SAVE_INTERVAL", 5000)),
                   help="learner steps between checkpoints (env twin "
                        "APEX_SAVE_INTERVAL — PBT fleets compress it so "
                        "donor checkpoints exist early)")
    p.add_argument("--mesh-dp", type=int,
                   default=int(e.get("APEX_MESH_DP", 0)),
                   help="learner dp mesh degree: shard the replay across "
                        "this many chips with pmean gradient sync; 0 = all "
                        "local devices (learner/apex roles), 1 = single "
                        "chip")
    p.add_argument("--total-steps", type=int, default=1_000_000)
    p.add_argument("--total-frames", type=int, default=1_000_000)
    p.add_argument("--max-seconds", type=float, default=86400.0)
    p.add_argument("--train-ratio", type=float, default=None)
    p.add_argument("--min-train-ratio", type=float, default=None)
    # replay
    p.add_argument("--capacity", type=int, default=2 ** 19)
    p.add_argument("--warmup", type=int, default=50_000)
    p.add_argument("--alpha", type=float, default=0.6)
    p.add_argument("--beta", type=float, default=0.4)
    # observability (apex_tpu/obs)
    p.add_argument("--metrics", action="store_true",
                   help="status role: print the Prometheus text "
                        "exposition (scalars, rates, fleet, latency "
                        "histograms) instead of the fleet table — one "
                        "REQ round-trip to the learner's status server")
    p.add_argument("--http", type=int,
                   default=int(e.get("APEX_METRICS_HTTP", 0)),
                   help="status role with --metrics: serve the "
                        "exposition over plain HTTP on this port (GET "
                        "/metrics proxies one zmq round-trip per "
                        "scrape) so a stock Prometheus server can poll "
                        "directly; 0 = one-shot print")
    p.add_argument("--trace-dir", default=e.get("APEX_TRACE_DIR"),
                   help="enable the per-role trace ring and flush Chrome "
                        "trace-event JSON segments here (atexit/periodic/"
                        "SIGUSR2); merge a fleet's segments with "
                        "`python -m apex_tpu.obs.merge DIR`")
    # misc
    p.add_argument("--logdir", default=e.get("APEX_LOGDIR"))
    p.add_argument("--profile-dir", default=e.get("APEX_PROFILE_DIR"),
                   help="capture a jax.profiler (XProf) trace of the "
                        "learner run into this directory")
    p.add_argument("--checkpoint-dir", default=e.get("APEX_CKPT_DIR"))
    p.add_argument("--checkpoint", default=None,
                   help="checkpoint path (enjoy role)")
    p.add_argument("--restore", action=argparse.BooleanOptionalAction,
                   default=_env_bool(e.get("APEX_RESTORE", "")),
                   help="resume the learner from the newest checkpoint in "
                        "--checkpoint-dir before training (bit-exact "
                        "learner state; actors re-sync from the first "
                        "post-restore publish); --no-restore overrides the "
                        "APEX_RESTORE env var")
    p.add_argument("--episodes", type=int, default=0,
                   help="evaluator/enjoy episode budget (0 = forever)")
    p.add_argument("--render", choices=["ascii", "save"], default=None,
                   help="enjoy role: terminal ASCII rendering, or capture "
                        "observations to --render-dir as per-episode .npy "
                        "stacks (enjoy.py:29-48 on headless hosts)")
    p.add_argument("--render-dir", default=e.get("APEX_RENDER_DIR"))
    p.add_argument("--verbose", action="store_true")
    p.add_argument("--barrier-timeout", type=float, default=120.0)
    return p


def _mesh_shape(args: argparse.Namespace) -> tuple[int, ...]:
    """dp degree for the learner mesh; 0 = every local device (only the
    learner-side roles initialize jax to count them)."""
    dp = args.mesh_dp
    if dp == 0:
        if args.role in ("learner", "apex"):
            import jax
            dp = len(jax.devices())
        else:
            dp = 1
    return (dp,)


def config_from_args(args: argparse.Namespace) -> ApexConfig:
    from apex_tpu.models import DEFAULT_TORSO, token_preset, torso_names
    if args.torso not in torso_names():
        raise SystemExit(f"--torso {args.torso!r}: known are "
                         f"{torso_names()}")
    tokens = {}
    if args.torso != DEFAULT_TORSO:
        # the preset's model reads frames of `context` ids: ApexTokens-v0
        # is sized to it, and the model holds the ids the env draws from
        preset = token_preset(args.torso)
        tokens = dict(token_context=preset["context"],
                      token_vocab=args.token_vocab or preset["vocab_held"])
    return ApexConfig(
        env=EnvConfig(env_id=args.env_id, seed=args.seed,
                      frame_stack=args.frame_stack,
                      clip_rewards=not args.no_clip_rewards,
                      episodic_life=not args.no_episodic_life, **tokens),
        replay=ReplayConfig(capacity=args.capacity, warmup=args.warmup,
                            alpha=args.alpha, beta=args.beta),
        learner=LearnerConfig(batch_size=args.batch_size, lr=args.lr,
                              lr_decay_steps=args.lr_decay_steps,
                              lr_decay_rate=args.lr_decay_rate,
                              gamma=args.gamma, n_steps=args.n_steps,
                              target_update_interval=
                              args.target_update_interval,
                              save_interval=args.save_interval,
                              mesh_shape=_mesh_shape(args),
                              torso=args.torso),
        actor=ActorConfig(n_actors=args.n_actors,
                          n_envs_per_actor=args.n_envs_per_actor,
                          send_interval=args.send_interval,
                          remote_policy=args.remote_policy),
        aql=AQLConfig(),
        comms=CommsConfig(batch_port=args.batch_port,
                          param_port=args.param_port,
                          barrier_port=args.barrier_port,
                          status_port=args.status_port,
                          heartbeat_interval_s=args.heartbeat_interval,
                          suspect_after_s=args.suspect_after,
                          dead_after_s=args.dead_after,
                          park_after_s=args.park_after,
                          replay_shards=args.replay_shards,
                          replay_port_base=args.replay_port_base,
                          replay_ip=args.replay_ip,
                          replay_strict_order=not args.replay_loose,
                          replay_snapshot_s=args.replay_snapshot_every,
                          infer_port=args.infer_port,
                          infer_ip=args.infer_ip,
                          infer_batch_max=args.infer_batch_max,
                          infer_window_ms=args.infer_window_ms,
                          infer_wait_s=args.infer_wait,
                          infer_reprobe_s=args.infer_reprobe,
                          infer_device_params=args.infer_device_params,
                          infer_shards=args.infer_shards,
                          wire_codec=args.wire_codec,
                          param_delta=args.param_delta,
                          param_keyframe_every=args.param_keyframe_every),
    )


def identity_from_args(args: argparse.Namespace) -> RoleIdentity:
    return RoleIdentity(role=args.role, actor_id=args.actor_id,
                        n_actors=args.n_actors, learner_ip=args.learner_ip,
                        replay_ip=args.replay_ip)


def main(argv: list[str] | None = None) -> int:
    import contextlib

    args = build_parser().parse_args(argv)
    if args.restore and not args.checkpoint_dir:
        raise SystemExit("--restore requires --checkpoint-dir")
    if args.trace_dir:
        # the trace ring reads the env at creation; the flag is its twin
        # (exporting here also covers worker processes, which inherit it)
        os.environ["APEX_TRACE_DIR"] = args.trace_dir
    from apex_tpu.utils.compile_cache import ensure_compile_cache
    # before the first jit; workers inherit it
    ensure_compile_cache(traced=bool(args.profile_dir))
    if args.tenant:
        # the tenant namespace reads the env at each qualification site
        # (tenancy/namespace.current_tenant); exporting here covers the
        # worker processes too, exactly like the trace dir
        os.environ["APEX_TENANT"] = args.tenant
    cfg = config_from_args(args)
    # population lineage dispatch (apex_tpu/population): a tenant that
    # names an APEX_POPULATION roster lineage adopts ITS env id and
    # hyperparameter vector — make_env/make_jax_env (host and ondevice
    # rollout paths), the n-step chunk assembly, the priority
    # exponents, and the epsilon ladder all dispatch per lineage off
    # the one roster.  No roster entry (or a no-override one) leaves
    # the config untouched: population-of-1 is a plain run.
    from apex_tpu.population.lineage import load_population
    population = load_population()
    if population:
        from apex_tpu.population.lineage import apply_lineage
        from apex_tpu.tenancy import namespace as tenancy_ns
        lineage = population.get(tenancy_ns.current_tenant())
        if lineage is not None:
            cfg = apply_lineage(cfg, lineage)
    identity = identity_from_args(args)

    if args.profile_dir and args.role in ("learner", "apex", "dqn", "aql",
                                          "r2d2"):
        from apex_tpu.utils.profiling import trace
        profile_ctx = trace(args.profile_dir)
    else:
        profile_ctx = contextlib.nullcontext()

    with profile_ctx:
        return _dispatch(args, cfg, identity)


def build_trainer(args: argparse.Namespace, cfg: ApexConfig):
    """The single-host drivers' trainer (``--role dqn|aql|r2d2|apex``),
    constructed but not run: ``(trainer, train_kwargs)``.  ``_dispatch``
    trains it; ``chip_smoke.py`` builds it the same way so the trainer's
    counters can be read after ``train()`` returns."""
    if args.role == "dqn":
        from apex_tpu.training.dqn import DQNTrainer as trainer_cls
        extra, train_kw = {}, dict(total_frames=args.total_frames)
    elif args.role == "r2d2":
        from apex_tpu.training.r2d2 import R2D2Trainer as trainer_cls
        extra, train_kw = {}, dict(total_frames=args.total_frames)
    elif args.role == "aql":
        from apex_tpu.training.aql import AQLTrainer as trainer_cls
        extra, train_kw = {}, dict(total_frames=args.total_frames)
    else:
        if args.family == "aql":
            from apex_tpu.training.aql import AQLApexTrainer as trainer_cls
        elif args.family == "r2d2":
            from apex_tpu.training.r2d2 import R2D2ApexTrainer as trainer_cls
        else:
            from apex_tpu.training.apex import ApexTrainer as trainer_cls
        extra = dict(train_ratio=args.train_ratio,
                     min_train_ratio=args.min_train_ratio)
        if args.rollout == "fused":
            # the whole rollout -> ingest -> sample -> train ->
            # write-back cycle as one device program per dispatch
            # (apex_tpu/ondevice), sharded over the --mesh-dp axis;
            # make_jax_env's ValueError names non-jittable env ids,
            # the divisibility guards name --n-envs-per-actor /
            # --batch-size vs --mesh-dp, and the family gate fails
            # loud before construction
            if args.family != "dqn":
                raise NotImplementedError(
                    f"--rollout fused currently serves the dqn "
                    f"family only (got {args.family!r}) — aql/r2d2 "
                    f"slot in behind the same scan hooks "
                    f"(ROADMAP.md)")
            from apex_tpu.ondevice.fused import FusedApexTrainer
            trainer_cls = FusedApexTrainer
            extra["rollout_len"] = args.rollout_len or None
            extra["steps_per_dispatch"] = args.steps_per_dispatch
        elif args.rollout == "ondevice":
            # co-located Anakin rollouts replace the actor processes;
            # make_jax_env raises a ValueError naming non-jittable
            # env ids, and the family gate fails loud before any
            # trainer construction
            if args.family != "dqn":
                raise NotImplementedError(
                    f"--rollout ondevice currently serves the dqn "
                    f"family only (got {args.family!r}) — aql/r2d2 "
                    f"stay on the host pipeline (ROADMAP.md)")
            from apex_tpu.training.anakin import (AnakinPool,
                                                  make_anakin_engine)
            engine = make_anakin_engine(
                cfg, rollout_len=args.rollout_len or None)
            extra["pool"] = AnakinPool(cfg, engine)
        train_kw = dict(total_steps=args.total_steps,
                        max_seconds=args.max_seconds)
    return (trainer_cls(cfg, logdir=args.logdir, verbose=args.verbose,
                        checkpoint_dir=args.checkpoint_dir, **extra),
            train_kw)


def _dispatch(args: argparse.Namespace, cfg: ApexConfig,
              identity: RoleIdentity) -> int:
    if args.role == "learner":
        from apex_tpu.runtime.roles import run_learner
        run_learner(cfg, n_peers=args.n_actors + args.n_evaluators,
                    total_steps=args.total_steps,
                    max_seconds=args.max_seconds, family=args.family,
                    logdir=args.logdir, verbose=args.verbose,
                    checkpoint_dir=args.checkpoint_dir,
                    train_ratio=args.train_ratio,
                    min_train_ratio=args.min_train_ratio,
                    barrier_timeout_s=args.barrier_timeout,
                    restore=args.restore, rollout=args.rollout,
                    rollout_len=args.rollout_len or None,
                    steps_per_dispatch=args.steps_per_dispatch)
    elif args.role == "loadgen":
        # standalone on-device rollout fleet (training/anakin.py): ships
        # device-rate sealed chunks at the learner / replay shards — the
        # synthetic heavy traffic the scale planes are measured against.
        # Skips the startup barrier like replay/infer roles: it acts the
        # moment the first param publish lands.
        from apex_tpu.runtime.roles import run_loadgen
        run_loadgen(cfg, identity, family=args.family,
                    max_seconds=args.max_seconds,
                    rollout_len=args.rollout_len or None)
    elif args.role == "actor":
        from apex_tpu.runtime.roles import run_actor
        run_actor(cfg, identity, family=args.family,
                  barrier_timeout_s=args.barrier_timeout)
    elif args.role == "evaluator":
        from apex_tpu.runtime.roles import run_evaluator
        run_evaluator(cfg, identity, family=args.family,
                      episodes=args.episodes, logdir=args.logdir,
                      verbose=args.verbose,
                      barrier_timeout_s=args.barrier_timeout)
    elif args.role == "replay":
        # one prioritized-replay shard (apex_tpu/replay_service): binds
        # replay_port_base + shard_id, serves until killed/--max-seconds.
        # Shards skip the startup barrier — the learner counts only
        # actors/evaluators there, and a shard is useful the moment its
        # ROUTER binds.
        if not 0 <= args.shard_id < max(1, cfg.comms.replay_shards):
            raise SystemExit(
                f"--shard-id {args.shard_id} outside [0, "
                f"{cfg.comms.replay_shards}) — set --replay-shards/"
                f"APEX_REPLAY_SHARDS fleet-wide")
        from apex_tpu.replay_service.service import run_replay_shard
        from apex_tpu.runtime.roles import _with_ips
        cfg = cfg.replace(comms=_with_ips(cfg.comms, identity))
        run_replay_shard(cfg, args.shard_id, family=args.family,
                         max_seconds=args.max_seconds,
                         snapshot_dir=args.replay_snapshot_dir)
    elif args.role == "infer":
        # one batched-inference shard (apex_tpu/infer_service +
        # apex_tpu/serving): binds infer_port + shard id, subscribes the
        # learner's param channel, serves its hashed worker band until
        # killed / --max-seconds.  Skips the startup barrier like replay
        # shards — actors act locally until it answers, so launch order
        # is free.
        from apex_tpu.infer_service.service import run_infer_server
        from apex_tpu.runtime.roles import _with_ips
        cfg = cfg.replace(comms=_with_ips(cfg.comms, identity))
        run_infer_server(cfg, family=args.family,
                         server_id=args.infer_shard_id,
                         max_seconds=args.max_seconds)
    elif args.role == "serve-ctl":
        # the serving tier's deployment controller (apex_tpu/serving/
        # deploy): canaries new model versions onto a shard fraction,
        # promotes on healthy SLO soak, rolls back by epoch on breach.
        # Skips the barrier — it holds until the learner's status port
        # answers.
        from apex_tpu.runtime.roles import _with_ips
        from apex_tpu.serving.deploy import run_serve_ctl
        cfg = cfg.replace(comms=_with_ips(cfg.comms, identity))
        run_serve_ctl(cfg, identity,
                      canary_frac=args.serve_canary_frac,
                      soak_s=args.serve_soak,
                      version_every=args.serve_version_every,
                      interval_s=args.serve_interval,
                      max_seconds=args.max_seconds)
    elif args.role == "tenant-ctl":
        # the multi-tenant placement controller (apex_tpu/tenancy/
        # scheduler): admits the APEX_TENANTS roster, assigns weighted
        # replay/infer shard bands, probes each tenant's learner, and
        # evicts/rebalances on death.  Skips the barrier like the other
        # controllers.
        from apex_tpu.runtime.roles import _with_ips
        from apex_tpu.tenancy.scheduler import run_tenant_ctl
        cfg = cfg.replace(comms=_with_ips(cfg.comms, identity))
        run_tenant_ctl(cfg, interval_s=args.serve_interval,
                       max_seconds=args.max_seconds)
    elif args.role == "pbt-ctl":
        # the population-based-training controller (apex_tpu/population/
        # controller): probes each APEX_POPULATION lineage's learner,
        # runs truncation-selection exploit (donor checkpoint copy +
        # epoch bump via the learner ctl surface) and perturb/resample
        # explore per task ladder.  Skips the barrier like the other
        # controllers.
        from apex_tpu.population.controller import run_pbt_ctl
        from apex_tpu.runtime.roles import _with_ips
        cfg = cfg.replace(comms=_with_ips(cfg.comms, identity))
        run_pbt_ctl(cfg, interval_s=args.serve_interval,
                    decide_every_s=args.pbt_decide,
                    frac=args.pbt_frac,
                    resample_prob=args.pbt_resample,
                    min_episodes=args.pbt_min_episodes,
                    max_seconds=args.max_seconds)
    elif args.role == "status":
        # operator surface: one REQ round-trip to the learner's fleet
        # status server — the live membership table, or (--metrics) the
        # Prometheus text exposition for standard scrape tooling
        if args.metrics:
            if args.http:
                # plain-HTTP Prometheus sidecar: a stock Prometheus
                # server polls GET /metrics; each scrape proxies one zmq
                # REQ round-trip to the learner's status server
                from apex_tpu.obs.metrics import make_http_sidecar
                server = make_http_sidecar(cfg.comms, port=args.http,
                                           learner_ip=args.learner_ip)
                print(f"metrics sidecar: http://0.0.0.0:{args.http}"
                      f"/metrics -> zmq {args.learner_ip}:"
                      f"{cfg.comms.status_port}", flush=True)
                try:
                    server.serve_forever()
                except KeyboardInterrupt:
                    pass
                finally:
                    server.server_close()
                return 0
            from apex_tpu.obs.metrics import metrics_request
            text = metrics_request(cfg.comms, learner_ip=args.learner_ip)
            if text is None:
                print(f"no metrics from {args.learner_ip}:"
                      f"{cfg.comms.status_port} (learner not running, or "
                      f"an in-host trainer with no status server)")
                return 1
            print(text, end="")
            return 0
        from apex_tpu.fleet.registry import format_fleet_table, \
            status_request
        snap = status_request(cfg.comms, learner_ip=args.learner_ip)
        if snap is None:
            print(f"no fleet status from {args.learner_ip}:"
                  f"{cfg.comms.status_port} (learner not running, or "
                  f"an in-host trainer with no status server)")
            return 1
        print(format_fleet_table(snap))
    elif args.role in ("dqn", "aql", "r2d2", "apex"):
        # single-host drivers share one construct -> restore? -> train path
        t, train_kw = build_trainer(args, cfg)
        if args.restore:
            t.restore()
        t.train(**train_kw)
    elif args.role == "enjoy":
        from apex_tpu.training.checkpoint import evaluate_checkpoint
        if not args.checkpoint:
            raise SystemExit("--checkpoint required for enjoy")
        hook = None
        if args.render:
            if args.render == "save" and not args.render_dir:
                raise SystemExit("--render save requires --render-dir")
            from apex_tpu.utils.render import make_render_hook
            hook = make_render_hook(args.render, args.render_dir)
        score = evaluate_checkpoint(args.checkpoint,
                                    episodes=args.episodes or 10,
                                    render_hook=hook)
        print(f"enjoy: mean episode reward {score:.2f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
