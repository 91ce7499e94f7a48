#!/usr/bin/env bash
# Localhost all-roles topology (reference origin_repo/run.sh:1-5: tmux panes
# for replay/learner/actor/eval on 127.0.0.1).  By default replay is
# dissolved into the learner, so the topology is learner + N actors +
# evaluator.  Export APEX_REPLAY_SHARDS=N (N > 0) to restore the
# reference's standalone replay role as N shard processes
# (apex_tpu/replay_service): actors hash chunks to shards, the learner
# pulls pre-sampled batches round-robin and ships priority write-backs.
#
# Usage: scripts/run_local.sh [ENV_ID] [N_ACTORS] [TOTAL_STEPS] [ENVS_PER_ACTOR]
set -euo pipefail
cd "$(dirname "$0")/.."

ENV_ID="${1:-ApexCartPole-v0}"
N_ACTORS="${2:-2}"
TOTAL_STEPS="${3:-2000}"
ENVS_PER_ACTOR="${4:-1}"

# One process per chip: the learner keeps the platform the launching shell
# selected (JAX's default — the TPU when there is one — if JAX_PLATFORMS is
# unset); actors, evaluator, replay/infer shards, controllers and loadgen
# are pinned to CPU so none of them reaches for the learner's chip.
if [ -n "${JAX_PLATFORMS-}" ]; then
  LEARNER_ENV=(env "JAX_PLATFORMS=$JAX_PLATFORMS")
else
  LEARNER_ENV=(env -u JAX_PLATFORMS)
fi
export JAX_PLATFORMS=cpu

# Deterministic fault injection (apex_tpu/fleet/chaos.py): export
# CHAOS_SEED (+ optional CHAOS_SPEC JSON) before launching and every role
# inherits the same seeded fault schedule — kills at message N, chunk
# drops/delays, publish stalls — replayable run after run.  Example:
#   CHAOS_SEED=7 CHAOS_SPEC='{"kill":{"actor-0":200},"drop_frac":0.05}' \
#     scripts/run_local.sh
export CHAOS_SEED="${CHAOS_SEED:-}" CHAOS_SPEC="${CHAOS_SPEC:-}"

# Multi-tenancy (apex_tpu/tenancy): export APEX_TENANT=<name> and every
# role this script launches runs namespaced — qualified wire identities,
# tenant-prefixed chunk ids, topic-tagged param publishes — so N
# invocations of this script (one per tenant, distinct APEX_BATCH_PORT/
# APEX_PARAM_PORT/APEX_BARRIER_PORT/APEX_STATUS_PORT blocks) share ONE
# externally-launched replay/infer plane.  APEX_LAUNCH_SHARED=0 skips
# launching the shard/infer/controller processes here (the shared plane
# already runs elsewhere, carrying the APEX_TENANTS roster);
# APEX_TENANT_CTL=1 adds the tenancy placement controller
# (--role tenant-ctl) next to the shared planes.
export APEX_TENANT="${APEX_TENANT:-}" APEX_TENANTS="${APEX_TENANTS:-}"
LAUNCH_SHARED="${APEX_LAUNCH_SHARED:-1}"

# Population plane (apex_tpu/population): export APEX_POPULATION (JSON
# lineage roster — each lineage IS a tenant) and run one invocation of
# this script per lineage (APEX_TENANT=<lineage>, its own port block;
# the lineage's env id + hyperparameter vector apply from the roster).
# APEX_PBT_CTL=1 adds the PBT controller (--role pbt-ctl) next to the
# shared planes: it probes each lineage's status port, and bottom-of-
# ladder lineages restore the top's checkpoint with a mutated vector.
export APEX_POPULATION="${APEX_POPULATION:-}"

# Observability (apex_tpu/obs): every role flushes a per-process trace ring
# (chunk lineage spans, phase/gap events) into APEX_TRACE_DIR — on exit AND
# periodically, each flush a segment of what is new, so the actors killed
# by the EXIT trap still leave near-complete traces.  The learner's fleet_summary.json
# lands in the same dir, giving obs.merge the heartbeat-derived clock
# offsets for the single merged perfetto timeline.
TRACE_DIR="${APEX_TRACE_DIR:-/tmp/apex-obs-$$}"
export APEX_TRACE_DIR="$TRACE_DIR"
mkdir -p "$TRACE_DIR"

# Sharded replay service (apex_tpu/replay_service): the flag set below
# must agree fleet-wide, so it rides COMMON like the ports do.  0 =
# in-learner replay (the default topology).
REPLAY_SHARDS="${APEX_REPLAY_SHARDS:-0}"
export APEX_REPLAY_SHARDS="$REPLAY_SHARDS"

# On-device Anakin rollouts (apex_tpu/training/anakin): export
# APEX_ROLLOUT=ondevice and the learner co-locates a fused
# env+policy+chunk-assembly scan with the fused trainer — params never
# leave the device, sealed chunks enter the normal replay path, and the
# topology can run with ZERO host actors (N_ACTORS=0; the evaluator
# still rides the param stream).  APEX_ROLLOUT=fused goes all the way
# (apex_tpu/ondevice): rollout + ingest + prioritized sample + train +
# priority write-back run as ONE jitted program per dispatch, the host
# waking once per APEX_STEPS_PER_DISPATCH macro steps (requires
# APEX_REPLAY_SHARDS=0 — the fused loop owns replay on-device).
# Jittable envs only (ApexCatch*/ApexRally* — the CLI fails loud
# otherwise).
#
# Data-parallel mesh (PR 17): export APEX_MESH_DP=N (the --mesh-dp env
# twin — the CLI reads it, nothing to wire here) and the learner shards
# over N chips in EVERY rollout mode, fused included: env lanes split
# into per-chip blocks, each chip owns a replay pool partition, and
# gradients pmean across the mesh.  Divisibility is checked loud at
# startup (batch-size % N, ENVS_PER_ACTOR x actors % N).  On a CPU box,
# emulate the mesh with
#   XLA_FLAGS=--xla_force_host_platform_device_count=N
export APEX_ROLLOUT="${APEX_ROLLOUT:-host}"

# Centralized inference plane (apex_tpu/infer_service): export
# APEX_REMOTE_POLICY=1 to launch a `--role infer` policy server and make
# the actors ship half-group observations to it (one batched device
# dispatch across actor processes) instead of running the policy on
# their own CPU.  Every role reads the env twin, so the flag agrees
# fleet-wide for free; a killed server never stalls actors — they fall
# back to local policies within APEX_INFER_WAIT and re-probe.
REMOTE_POLICY="${APEX_REMOTE_POLICY:-0}"
export APEX_REMOTE_POLICY="$REMOTE_POLICY"

# Wire codec (apex_tpu/runtime/codec.py): APEX_WIRE_CODEC=raw|delta|dict
# picks the chunk wire codec for every role this script launches (raw =
# bit-identical legacy pickles; delta = frame XOR + RLE for ~sparse
# frames; dict = per-chunk byte dictionary for pixel stacks).
# Negotiation is per-chunk — mixed fleets interoperate, and
# APEX_WIRE_CODEC_MIXED=1 pins actor 0 to the raw codec to exercise
# exactly that (the CI codec-smoke lane's mixed-version rehearsal).
# APEX_PARAM_DELTA=1 turns on sparse param-delta publish (per-leaf diff
# vs the last keyframe + tree checksum; APEX_PARAM_KEYFRAME_EVERY sets
# the dense-keyframe cadence, default 16).
export APEX_WIRE_CODEC="${APEX_WIRE_CODEC:-}"
export APEX_PARAM_DELTA="${APEX_PARAM_DELTA:-}"
export APEX_PARAM_KEYFRAME_EVERY="${APEX_PARAM_KEYFRAME_EVERY:-}"
WIRE_CODEC_MIXED="${APEX_WIRE_CODEC_MIXED:-0}"

COMMON=(--env-id "$ENV_ID" --n-actors "$N_ACTORS"
        --n-envs-per-actor "$ENVS_PER_ACTOR"
        --batch-size 64 --capacity 8192 --warmup 500
        --barrier-timeout 600)

pids=()
cleanup() { kill "${pids[@]}" 2>/dev/null || true; }
trap cleanup EXIT

if [ "$REPLAY_SHARDS" -gt 0 ] && [ "$LAUNCH_SHARED" = "1" ]; then
  # shard s binds replay_port_base + s; shards skip the startup barrier
  # (useful the moment the ROUTER binds), so launch them first and the
  # actor fleet's first sealed chunks route straight to them.
  #
  # Durability (PR 8): APEX_REPLAY_SNAPSHOT_DIR (+ _S cadence) makes each
  # shard snapshot its whole replay state and restore it on respawn;
  # APEX_SUPERVISE_REPLAY=1 wraps each shard in the host supervisor so a
  # chaos-killed shard respawns automatically and rejoins WARM from its
  # snapshot (the chaos kill disarms on the supervised life).
  export APEX_REPLAY_SNAPSHOT_DIR="${APEX_REPLAY_SNAPSHOT_DIR:-}"
  export APEX_REPLAY_SNAPSHOT_S="${APEX_REPLAY_SNAPSHOT_S:-}"
  for s in $(seq 0 $((REPLAY_SHARDS - 1))); do
    if [ "${APEX_SUPERVISE_REPLAY:-0}" = "1" ]; then
      python -m apex_tpu.fleet.supervise --min-uptime 1 \
        --backoff 0.5 --backoff-max 2 -- \
        python -m apex_tpu.runtime --role replay --shard-id "$s" \
        "${COMMON[@]}" &
    else
      python -m apex_tpu.runtime --role replay --shard-id "$s" \
        "${COMMON[@]}" &
    fi
    pids+=($!)
  done
fi

if [ "$REMOTE_POLICY" = "1" ] && [ "$LAUNCH_SHARED" = "1" ]; then
  # Sharded serving tier (apex_tpu/serving): APEX_INFER_SHARDS=N runs N
  # infer servers, shard s binding infer_port + s; remote-policy workers
  # hash to a home shard by identity.  The servers skip the startup
  # barrier (useful the moment their ROUTERs bind); launch before the
  # actors so their first vector steps already batch centrally instead
  # of burning one fallback wait each.  APEX_SUPERVISE_INFER=1 wraps
  # each shard in the host supervisor so a chaos-killed server respawns
  # in seconds (the kill disarms on the supervised life) and the SLO
  # engine's round-trip alert can walk the full BREACHED -> RESOLVED
  # cycle — the slo-smoke drill's topology.
  INFER_SHARDS="${APEX_INFER_SHARDS:-1}"
  export APEX_INFER_SHARDS="$INFER_SHARDS"
  for s in $(seq 0 $((INFER_SHARDS - 1))); do
    if [ "${APEX_SUPERVISE_INFER:-0}" = "1" ]; then
      python -m apex_tpu.fleet.supervise --min-uptime 1 \
        --backoff 0.5 --backoff-max 2 -- \
        python -m apex_tpu.runtime --role infer --infer-shard-id "$s" \
        "${COMMON[@]}" &
    else
      python -m apex_tpu.runtime --role infer --infer-shard-id "$s" \
        "${COMMON[@]}" &
    fi
    pids+=($!)
  done
  # Canary deployment controller (apex_tpu/serving/deploy, --role
  # serve-ctl): APEX_SERVE_CTL=1 launches it against the shard tier —
  # new model versions canary onto APEX_SERVE_CANARY_FRAC of the
  # shards, promote after APEX_SERVE_SOAK_S of healthy SLO, roll back
  # by epoch on breach; the deployment timeline lands in the learner's
  # fleet_summary.json and apex_serving_* Prometheus rows.
  if [ "${APEX_SERVE_CTL:-0}" = "1" ]; then
    python -m apex_tpu.runtime --role serve-ctl "${COMMON[@]}" &
    pids+=($!)
  fi
fi

# Tenancy placement controller (apex_tpu/tenancy/scheduler, --role
# tenant-ctl): admits the APEX_TENANTS roster, assigns weighted replay/
# infer shard bands, probes each tenant's learner status port, evicts
# and rebalances on death; the admission timeline lands in the host
# learner's fleet_summary.json ("tenancy") and apex_tenancy_* rows.
if [ "${APEX_TENANT_CTL:-0}" = "1" ] && [ "$LAUNCH_SHARED" = "1" ]; then
  python -m apex_tpu.runtime --role tenant-ctl "${COMMON[@]}" &
  pids+=($!)
fi

# PBT controller (apex_tpu/population/controller, --role pbt-ctl):
# truncation-selection exploit (donor checkpoint copy + learner-epoch
# bump through the lineage learners' ctl surfaces) and perturb/resample
# explore over the APEX_POPULATION roster; the population timeline
# lands in the host learner's fleet_summary.json ("population") and
# apex_population_* rows.
if [ "${APEX_PBT_CTL:-0}" = "1" ] && [ "$LAUNCH_SHARED" = "1" ]; then
  python -m apex_tpu.runtime --role pbt-ctl "${COMMON[@]}" &
  pids+=($!)
fi

# SLO soak traffic (apex_tpu/obs/soak.py): APEX_LOADGEN=N spawns N
# standalone on-device loadgen roles (jittable envs only — the CLI fails
# loud otherwise) that saturate the chunk plane at device rate.  They
# skip the startup barrier like replay/infer roles, so they are NOT
# counted in --n-actors.
LOADGEN="${APEX_LOADGEN:-0}"
for g in $(seq 0 $((LOADGEN - 1))); do   # LOADGEN=0: no loadgen roles
  python -m apex_tpu.runtime --role loadgen --actor-id "$g" \
    "${COMMON[@]}" &
  pids+=($!)
done

for i in $(seq 0 $((N_ACTORS - 1))); do   # N_ACTORS=0: no host actors
  if [ "$WIRE_CODEC_MIXED" = "1" ] && [ "$i" = "0" ]; then
    # mixed-version fleet rehearsal: actor 0 stays on the legacy raw
    # codec while the rest follow APEX_WIRE_CODEC — per-chunk
    # negotiation means the learner ingests both streams untouched
    APEX_WIRE_CODEC=raw python -m apex_tpu.runtime --role actor \
      --actor-id "$i" "${COMMON[@]}" &
  else
    python -m apex_tpu.runtime --role actor --actor-id "$i" \
      "${COMMON[@]}" &
  fi
  pids+=($!)
done
python -m apex_tpu.runtime --role evaluator --episodes 0 --verbose \
  "${COMMON[@]}" &
pids+=($!)

# learner runs in the foreground; barrier holds until every peer dials in
"${LEARNER_ENV[@]}" python -m apex_tpu.runtime --role learner \
  --total-steps "$TOTAL_STEPS" --verbose --logdir "$TRACE_DIR" "${COMMON[@]}"

# one perfetto-loadable fleet timeline (clock-aligned via the heartbeat
# offsets in fleet_summary.json); load it at https://ui.perfetto.dev
sleep 1   # let the periodic flushers land their last dumps
python -m apex_tpu.obs.merge "$TRACE_DIR" \
  -o "$TRACE_DIR/merged_trace.json" || true
