#!/usr/bin/env python3
"""chip_smoke.py — the DQN Ape-X main path, once, on the chip, at full width.

The quickest proof that the system still starts on a TPU.  Every stage goes
through the entry points a user would call (``build_parser`` ->
``config_from_args`` -> ``build_trainer`` -> ``train``) at the CLI's default
widths — ``ApexCatch-v0`` (84x84x1 uint8 frames, stack 4), Nature-CNN
``DuelingDQN`` in bf16, batch 512, 2^19 transitions + 2^20 frames of replay
per chip.  Only run-length flags shrink; the weights are random, from the
CLI's default seed.

Stages (``STAGES``), in order:

* ``host_fed``      4 actor processes x 8 env slots over the shm ring
* ``fused``         ``--rollout fused``, 8 x 32 = 256 on-device lanes
* ``host_fed_dp4`` / ``fused_dp4``   the same two at ``--mesh-dp 4``, run
  whenever the first stage reports >= 4 devices
* ``gather_kernel`` the Pallas row gather, compiled, against ``jnp.take``

A chip belongs to one process at a time, so this parent NEVER imports JAX:
it runs each stage as a child (``--stage NAME``), one after the other, in
the child's own process group, and kills the whole group when the stage
ends or times out.  A child asserts on the trainer's counters — never on
``train()`` returning — and fails on any unmet check, on any exception, on
a JAX "donated buffers were not usable" warning, and on any platform but
``tpu``.  Nothing is caught and carried on.

Last line of stdout on success:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failure exits non-zero and prints no such line.

``--dry-run`` is the CPU rehearsal of the same script for tier-1 (toy
sizes, ``JAX_PLATFORMS=cpu``, kernel in interpret mode).  Every line it
prints says so, and its last line carries ``"dry_run": true`` — it can
never be read as a chip pass, and none of its timings is a device metric.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: the driver allows 1200 s, compilation included
WALL_BUDGET_S = 1100.0
STAGE_TIMEOUT_S = 420.0
GATHER_TIMEOUT_S = 180.0
RESULT_TAG = "STAGE_RESULT "
#: the replay's own estimate of one shard (``replay.hbm_bytes()``)
HBM_EST = "replay_hbm_bytes_est_per_chip"

_FULL = ["--role", "apex", "--family", "dqn", "--env-id", "ApexCatch-v0",
         "--warmup", "4096", "--max-seconds", "300"]
_DRY = ["--role", "apex", "--family", "dqn", "--env-id", "ApexCatchSmall-v0",
        "--frame-stack", "2", "--capacity", "4096", "--batch-size", "32",
        "--warmup", "256", "--max-seconds", "120"]
_HOST = {False: ["--n-actors", "4", "--n-envs-per-actor", "8",
                 "--total-steps", "64"],
         True: ["--n-actors", "2", "--n-envs-per-actor", "4",
                "--total-steps", "8"]}
_FUSED = {False: ["--rollout", "fused", "--n-actors", "8",
                  "--n-envs-per-actor", "32", "--steps-per-dispatch", "4",
                  "--total-steps", "32"],
          True: ["--rollout", "fused", "--n-actors", "2",
                 "--n-envs-per-actor", "8", "--steps-per-dispatch", "4",
                 "--total-steps", "8"]}


def stage_argv(name: str, dry: bool) -> list[str]:
    """The command line a user would type for this stage."""
    kind = _FUSED if name.startswith("fused") else _HOST
    dp = "4" if name.endswith("_dp4") else "1"
    return (_DRY if dry else _FULL) + kind[dry] + ["--mesh-dp", dp]


# -- child: one stage, one process, one claim on the chip --------------------

class CompileMeter:
    """Seconds spent in XLA backend compiles (a persistent-cache hit costs
    its retrieval time), plus the cache's hit/miss counts."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.seconds, self.hits, self.misses = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def memory(devices) -> list[dict | None]:
    """``memory_stats()`` per device (None where the backend has none)."""
    out = []
    for d in devices:
        m = d.memory_stats()
        out.append(None if m is None else {
            k: int(m[k]) for k in ("bytes_in_use", "peak_bytes_in_use",
                                   "bytes_limit") if k in m})
    return out


def q_parity(trainer) -> float:
    """Largest |Q_chip - Q_reference| on 8 random observations, relative
    to the reference's scale: the trained params through the trainer's own
    model on the default device (bf16 compute) against the same module in
    float32 on the host CPU backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models.dueling import DuelingDQN

    shape, stack = trainer.replay.frame_shape, trainer.replay.frame_stack
    obs = np.random.default_rng(0).integers(
        0, 255, (8,) + shape[:-1] + (stack * shape[-1],), dtype=np.uint8)
    params = trainer.train_state.params
    q = np.asarray(jax.jit(trainer.model.apply)(params, obs))
    reference = DuelingDQN(**{**trainer.model_spec,
                              "compute_dtype": jnp.float32})
    with jax.default_device(jax.devices("cpu")[0]):
        q_ref = np.asarray(jax.jit(reference.apply)(
            jax.device_get(params), obs))
    if q.shape != q_ref.shape or not np.isfinite(q).all():
        return float("inf")
    return float(np.abs(q - q_ref).max() / max(1.0, np.abs(q_ref).max()))


def trainer_stage(name: str, dry: bool, devices, meter) -> tuple[dict, dict]:
    import math

    import jax
    import numpy as np

    from apex_tpu.runtime.cli import (build_parser, build_trainer,
                                      config_from_args)

    args = build_parser().parse_args(stage_argv(name, dry))
    cfg = config_from_args(args)
    n_dp = args.mesh_dp
    used = devices[:n_dp]
    platform = devices[0].platform

    t0 = time.monotonic()
    trainer, train_kw = build_trainer(args, cfg)
    jax.block_until_ready(trainer.replay_state)
    mem_built = memory(used)
    build_s = time.monotonic() - t0

    fused = name.startswith("fused")
    alive_at_cleanup: list[bool] = []
    if not fused:
        # ChunkAggregator (dp>1) wraps the ActorPool; unwrap to the real one
        pool = getattr(trainer.pool, "pool", trainer.pool)
        cleanup = pool.cleanup

        def watched_cleanup(*a, **kw):
            alive_at_cleanup.extend(p.is_alive() for p in pool.procs)
            return cleanup(*a, **kw)

        pool.cleanup = watched_cleanup

    compile_before = meter.seconds
    t1 = time.monotonic()
    trainer.train(**train_kw, log_every=1)
    jax.block_until_ready((trainer.train_state, trainer.replay_state))
    train_s = time.monotonic() - t1
    train_compile_s = meter.seconds - compile_before
    mem_trained = memory(used)

    rs = trainer.replay_state
    loss_log = trainer.log.history.get("learner/loss")
    loss = loss_log[-1][1] if loss_log else float("nan")
    hbm_est = trainer.replay.hbm_bytes()
    facts = {
        "argv": " ".join(stage_argv(name, dry)),
        "steps": trainer.steps_rate.total,
        "ingested": trainer.ingested,
        "param_version": trainer.param_version,
        "last_loss": loss,
        "q_parity_rel": q_parity(trainer),
        HBM_EST: hbm_est,
        "ring_shape": list(trainer.replay.ring_shape),
        "memory_after_build": mem_built,
        "memory_after_train": mem_trained,
        "build_s": round(build_s, 2),
        "train_s": round(train_s, 2),
        "train_compile_s": round(train_compile_s, 2),
        "train_run_s": round(train_s - train_compile_s, 2),
    }
    checks = {
        "steps>=target": trainer.steps_rate.total >= args.total_steps,
        "last_loss_finite": math.isfinite(loss),
        "ingested>=warmup": trainer.ingested >= args.warmup,
        "param_publish_after_first_step": trainer.param_version >= 2,
        "q_parity_rel<=0.05": facts["q_parity_rel"] <= 0.05,
        f"frames_on_{platform}": all(
            d.platform == platform for d in rs.frames.devices()),
    }
    if mem_trained[0] is not None:
        # donation held: a second copy of the ring would be >= 2x
        checks["peak<2x_replay"] = all(
            m["peak_bytes_in_use"] < 2 * hbm_est for m in mem_trained)

    if fused:
        counters = trainer.fused.counters()
        facts["fused_counters"] = counters
        checks["fused_dispatches"] = counters["dispatches"] > 0
        checks["fused_transitions"] = counters["transitions"] > 0
        checks["fused_prio_writeback"] = counters["prio_writebacks"] >= 1
    else:
        from apex_tpu.native.ring import ShmChunkQueue
        actors = [p for p in trainer.fleet.snapshot()["peers"]
                  if p["role"] == "actor"]
        stats = trainer._pipeline_last_stats or {}
        facts["pipeline"] = stats
        facts["worker_deaths"] = pool.worker_deaths
        facts["fleet"] = trainer.fleet.metrics()
        facts["actor_param_versions"] = [p["param_version"] for p in actors]
        checks["shm_chunk_plane"] = isinstance(pool.chunk_queue,
                                               ShmChunkQueue)
        checks["no_worker_deaths"] = pool.worker_deaths == 0
        # nor may a compile stall make the registry declare a live one dead
        checks["fleet_registry_saw_no_death"] = (
            facts["fleet"]["deaths"] == 0 and facts["fleet"]["dead"] == 0)
        checks["workers_alive_until_cleanup"] = (
            len(alive_at_cleanup) == args.n_actors and all(alive_at_cleanup))
        checks["actor_stat_param_version>=1"] = any(
            p["param_version"] >= 1 for p in actors)
        checks["pipeline_staged_slots"] = stats.get("slots", 0) > 0

    if n_dp > 1:
        shards = rs.frames.addressable_shards
        sizes = np.asarray(jax.device_get(rs.size)).reshape(-1).tolist()
        facts["shard_devices"] = [str(s.device) for s in shards]
        facts["shard_sizes"] = sizes
        checks["frames_on_distinct_devices"] = (
            len(shards) == n_dp and len({s.device for s in shards}) == n_dp)
        checks["every_shard_filled"] = (len(sizes) == n_dp
                                        and all(s > 0 for s in sizes))
        checks["params_identical_across_replicas"] = all(
            len(leaf.addressable_shards) == n_dp and all(
                np.array_equal(np.asarray(s.data),
                               np.asarray(leaf.addressable_shards[0].data))
                for s in leaf.addressable_shards[1:])
            for leaf in jax.tree.leaves(trainer.train_state.params))
        if mem_trained[0] is not None:
            in_use = [m["bytes_in_use"] for m in mem_trained]
            facts["bytes_in_use_spread"] = round(max(in_use) / min(in_use), 3)
            checks["memory_balanced_10pct"] = max(in_use) <= 1.10 * min(in_use)
    return facts, checks


def gather_stage(dry: bool, devices, meter) -> tuple[dict, dict]:
    """``_pallas_gather`` on the ring layout the replay stores
    (``[F, 8, 896]`` uint8), one learner step's worth of row ids
    (2 x 512 x 4), bit-for-bit against ``jnp.take``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops.gather import _pallas_gather
    from apex_tpu.replay.frame_pool import FramePoolReplay

    replay = FramePoolReplay(capacity=256 if dry else 2 ** 19,
                             frame_shape=(84, 84, 1), frame_stack=4)
    f, rows, cols = replay.ring_shape
    n_ids = 64 if dry else 2 * 512 * 4

    @jax.jit
    def pattern():
        # every (row, sublane, lane) distinct mod 251: a wrong row shows
        r = jax.lax.broadcasted_iota(jnp.int32, (f, rows, cols), 0)
        k = jax.lax.broadcasted_iota(jnp.int32, (f, rows, cols), 1)
        c = jax.lax.broadcasted_iota(jnp.int32, (f, rows, cols), 2)
        return ((r * 131 + k * 17 + c) % 251).astype(jnp.uint8)

    frames = jax.block_until_ready(pattern())
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, f, n_ids).astype(np.int32))
    t0 = time.monotonic()
    out = jax.block_until_ready(_pallas_gather(frames, ids, interpret=dry))
    first_call_s = time.monotonic() - t0
    ref = jnp.take(frames, ids, axis=0).reshape(n_ids, rows * cols)
    facts = {"ring_shape": [f, rows, cols], "n_ids": n_ids,
             HBM_EST: replay.hbm_bytes(),
             "mode": "interpret" if dry else "compiled",
             "first_call_s": round(first_call_s, 2),
             "memory": memory(devices[:1])}
    checks = {"shape": out.shape == ref.shape,
              "dtype": out.dtype == ref.dtype,
              "bit_identical_to_jnp_take": bool(jnp.array_equal(out, ref)),
              "not_all_equal": bool(out.min() != out.max())}
    return facts, checks


def run_stage(name: str, dry: bool) -> int:
    import warnings

    # a refused donation means a second copy of a 7.5 GB ring: an error
    warnings.filterwarnings(
        "error", message=".*donated buffers were not usable.*")
    from apex_tpu.utils.compile_cache import ensure_compile_cache
    cache_dir = ensure_compile_cache()
    import jax

    meter = CompileMeter()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    want = "cpu" if dry else "tpu"
    if device["platform"] != want:
        print(f"chip_smoke[{name}]: needs platform {want!r}, JAX found "
              f"{device}", file=sys.stderr, flush=True)
        return 1
    tag = f"chip_smoke[{name}]" + (" [DRY RUN on cpu, not a chip pass]"
                                   if dry else "")
    print(f"{tag}: platform={device['platform']} "
          f"device_kind={device['kind']!r} device_count={device['count']} "
          f"compile_cache={cache_dir}", flush=True)

    t0 = time.monotonic()
    if name == "gather_kernel":
        facts, checks = gather_stage(dry, devices, meter)
    else:
        facts, checks = trainer_stage(name, dry, devices, meter)
    wall_s = time.monotonic() - t0
    ok = all(checks.values())
    result = {"stage": name, "ok": ok, "dry_run": dry, "device": device,
              "wall_s": round(wall_s, 2),
              "compile_s": round(meter.seconds, 2),
              "run_s": round(wall_s - meter.seconds, 2),
              "cache_hits": meter.hits, "cache_misses": meter.misses,
              "checks": checks, **facts}
    print(f"{tag}: compile {result['compile_s']}s vs run "
          f"{result['run_s']}s (cache hits {meter.hits}, misses "
          f"{meter.misses})", flush=True)
    for key in ("memory_after_build", "memory_after_train", "memory"):
        if key in facts:
            print(f"{tag}: {key}={facts[key]} next to replay.hbm_bytes()="
                  f"{facts[HBM_EST]}", flush=True)
    failed = [k for k, v in checks.items() if not v]
    print(f"{tag}: " + ("all checks passed" if ok else f"FAILED {failed}"),
          flush=True)
    print(RESULT_TAG + json.dumps(result), flush=True)
    return 0 if ok else 1


# -- parent: sequential children, no JAX -------------------------------------

STAGES = ("host_fed", "fused", "host_fed_dp4", "fused_dp4", "gather_kernel")


def run_child(name: str, dry: bool, env: dict, timeout: float) -> dict:
    """One stage in its own process group; the group is killed when the
    stage ends, however it ends.  Exits the smoke (``SystemExit``, code 1)
    on timeout, on a non-zero exit and on a child that printed no result."""
    cmd = [sys.executable, "-u", os.path.abspath(__file__), "--stage", name]
    if dry:
        cmd.append("--dry-run")
    proc = subprocess.Popen(cmd, cwd=HERE, env=env, stdout=subprocess.PIPE,
                            text=True, start_new_session=True)
    results: list[dict] = []

    def pump() -> None:             # pass the stage's output through
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                results.append(json.loads(line[len(RESULT_TAG):]))
            print(line, end="", flush=True)

    reader = threading.Thread(target=pump, daemon=True)
    reader.start()
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        raise SystemExit(f"chip_smoke: stage {name} FAILED "
                         f"(exceeded {timeout:.0f}s)") from None
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)     # workers included
        except ProcessLookupError:
            pass
        proc.wait()
        reader.join(timeout=10)
    result = results[-1] if results else None
    if proc.returncode != 0 or result is None or not result["ok"]:
        raise SystemExit(f"chip_smoke: stage {name} FAILED "
                         f"(rc={proc.returncode}, "
                         f"result={'printed' if result else 'none'})")
    return result


def run_parent(dry: bool, only: list[str]) -> int:
    env = dict(os.environ)
    if dry:
        env["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in env.get(
                "XLA_FLAGS", ""):
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_"
                                "host_platform_device_count=4").strip()
    deadline = time.monotonic() + WALL_BUDGET_S
    results: list[dict] = []
    for name in STAGES:
        if only and name not in only:
            continue
        if (name.endswith("_dp4") and not only and results
                and results[0]["device"]["count"] < 4):
            print(f"chip_smoke: skipping {name} "
                  f"({results[0]['device']['count']} device(s) < 4)",
                  flush=True)
            continue
        budget = GATHER_TIMEOUT_S if name == "gather_kernel" \
            else STAGE_TIMEOUT_S
        results.append(run_child(
            name, dry, env, min(budget, deadline - time.monotonic())))
    device = results[0]["device"]
    summary = {
        "stages": [r["stage"] for r in results], "dry_run": dry,
        "device": device,
        "compile_s": {r["stage"]: r["compile_s"] for r in results},
        "run_s": {r["stage"]: r["run_s"] for r in results},
        "cache_hits": {r["stage"]: r["cache_hits"] for r in results},
        "claim": None}
    print("chip_smoke summary " + json.dumps(summary), flush=True)
    final: dict = {"ok": True}
    if dry:
        final["dry_run"] = True
    if only:
        final["partial"] = [r["stage"] for r in results]
    final["device"] = device
    print(json.dumps(final), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU rehearsal at toy sizes (tier-1); never a "
                         "chip pass")
    ap.add_argument("--stages", default="",
                    help="comma-separated subset of " + ",".join(STAGES))
    ap.add_argument("--stage", choices=STAGES,
                    help="(internal) run ONE stage in this process")
    a = ap.parse_args()
    if a.stage:
        return run_stage(a.stage, a.dry_run)
    only = [s for s in a.stages.split(",") if s]
    unknown = set(only) - set(STAGES)
    if unknown:
        ap.error(f"unknown stage(s) {sorted(unknown)}")
    return run_parent(a.dry_run, only)


if __name__ == "__main__":
    sys.exit(main())
