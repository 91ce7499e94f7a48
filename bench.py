"""Headline benchmark: learner throughput on one TPU chip + end-to-end rates.

Reference baseline: 10-12 batches/s at batch 512 on a V100 learner fed by a
separate replay server (``origin_repo/README.md:42``; BASELINE.md).  Part 1
measures the SAME unit of work, harder: each learner step here also ingests
512 fresh transitions and performs the PER priority write-back on-device —
work the reference offloads to its replay server — fused into one XLA
program on the Atari-shape DuelingDQN (84x84x4 uint8 stacks, batch 512),
repeated ``REPS`` times for a spread.

Part 2 runs the REAL concurrent pipeline (ApexTrainer + vectorized actor
processes over the shm data plane) on the PIXEL env ``ApexCatch-v0``
(84x84x4 uint8, the flagship geometry — the numpy renderer stands in for
ALE, absent in this image) to measure env-frames/sec ingested and
learner-steps/sec sustained end to end, queue/staging/publish overhead
included.

Replay is the frame-pool layout: 2^19 transitions + 2^20 single frames
resident in HBM (~7.5GB/chip); an 8-chip slice with per-chip shards doubles
the reference's 2e6 total capacity.  Stacks are gathered on device at
sample time.

Part 1 measures two dispatch shapes: one fused step per host round-trip
("single") and a ``lax.scan`` of BENCH_SCAN=8 bit-identical steps per
round-trip ("scanK" — amortizing host dispatch latency); the headline
takes the faster, with both recorded.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
"spread" (min/max over reps), "mfu", "gather" (the row-gather path actually
used), "dispatch" ("single" | "scanK"), "platform"/"device_kind"/
"device_count" as JAX reports them, and "e2e" (the ApexTrainer rates).
vs_baseline = value / 11.0 (midpoint of the reference's 10-12 range).

No fallback hides the device: the backend initialises in-process, and any
platform but ``tpu`` is an error unless the operator pinned
``JAX_PLATFORMS=cpu`` themselves (the CI toy lane — its numbers are
diagnostics of the host code, never device metrics).  A watchdog thread
arms a deadline per stage; a missed deadline or a stage that raises prints
the accumulated partial result as the final JSON line and exits NON-ZERO.
Parts 1 and 2 run on the XLA gather first; the pallas kernel is attempted
last, in-process (one process per chip), so a kernel that misbehaves
cannot cost the numbers already recorded.  ``BENCH_SKIP_PALLAS=1`` skips
the attempt.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import threading
import time

BASELINE_BPS = 11.0
BATCH = int(os.environ.get("BENCH_BATCH", 512))
FRAME_SHAPE = (84, 84, 1)
FRAME_STACK = 4
CAPACITY = int(os.environ.get("BENCH_CAPACITY", 2 ** 19))
FRAME_CAPACITY = 2 * CAPACITY
CHUNK = BATCH          # transitions ingested per fused step
CHUNK_FRAMES = CHUNK + 16
WARMUP_STEPS = 3
# env overrides let CI smoke-test the bench on CPU at toy scale; the
# driver's real-chip run uses the defaults
MEASURE_STEPS = int(os.environ.get("BENCH_STEPS", 50))
REPS = int(os.environ.get("BENCH_REPS", 3))
# first TPU compile of the concurrent pipeline eats ~20-40s of this wall
# budget and the 2048-transition warmup a further slice; the steady-state
# window after both is what the sliding rate counters report.  On TPU the
# e2e stage is a SOAK: >=300s wall so that >=180s of post-compile steady
# state is measured (round numbers must not be a 37-step sliver); the CPU
# diagnostic lane keeps the short default.
def _e2e_seconds(platform: str) -> float:
    if "BENCH_E2E_SECONDS" in os.environ:
        return float(os.environ["BENCH_E2E_SECONDS"])
    return 300.0 if platform == "tpu" else 120.0


# stage deadlines (watchdog): generous but finite — the whole bench must
# land inside the driver's outer timeout with the JSON line printed
INIT_TIMEOUT = float(os.environ.get("BENCH_INIT_TIMEOUT", 240.0))
PART1_TIMEOUT = float(os.environ.get("BENCH_PART1_TIMEOUT", 360.0))
PART2_MARGIN = float(os.environ.get("BENCH_PART2_MARGIN", 240.0))
PIPELINE_TIMEOUT = float(os.environ.get("BENCH_PIPELINE_TIMEOUT", 300.0))
# wall seconds granted to train() ON TOP of the soak target so the first
# compile of the concurrent pipeline (~20-40s on TPU) cannot eat the
# steady-state window (the soak used to run INSIDE its own budget,
# leaving no compile margin)
E2E_COMPILE_MARGIN = float(os.environ.get("BENCH_E2E_COMPILE_MARGIN", 90.0))


def e2e_budgets(platform: str) -> tuple[float, float, float]:
    """(soak, train_seconds, stage_seconds) for the e2e stage.

    The soak (:func:`_e2e_seconds`) is the STEADY-STATE wall target; the
    ``train()`` call gets ``soak + E2E_COMPILE_MARGIN`` so compile time
    comes out of the margin, not the soak; and the watchdog stage budget
    adds ``PART2_MARGIN`` on top for trainer construction, actor spawn,
    and teardown.  Unit-tested in tests/test_bench.py — the invariant is
    strict containment: soak < train < stage."""
    soak = _e2e_seconds(platform)
    train_seconds = soak + E2E_COMPILE_MARGIN
    return soak, train_seconds, train_seconds + PART2_MARGIN


# -- watchdog ---------------------------------------------------------------

RESULT: dict = {
    "metric": f"learner_batches_per_sec_batch{BATCH}_framepool_per_ingest",
    "value": None, "unit": "batches/s", "vs_baseline": None,
}
_stage = {"name": "start", "deadline": None}
_done = threading.Event()
_print_lock = threading.Lock()


def _emit_and_exit(code: int) -> None:
    # _print_lock also guards RESULT mutations (main thread), so the dump
    # cannot race a concurrent insert; the dict(...) copy is belt-and-braces
    with _print_lock:
        print(json.dumps(dict(RESULT) | {"claim": None}), flush=True)
    os._exit(code)       # watchdog path: threads/children may be wedged


def _arm(name: str, seconds: float) -> None:
    _stage["name"] = name
    _stage["deadline"] = time.monotonic() + seconds
    print(f"[bench] stage {name} (budget {seconds:.0f}s)",
          file=sys.stderr, flush=True)


def _watchdog() -> None:
    while not _done.wait(2.0):
        dl = _stage["deadline"]
        if dl is not None and time.monotonic() > dl:
            RESULT["error"] = (f"watchdog: stage {_stage['name']!r} "
                               f"exceeded its budget")
            _emit_and_exit(1)


# -- stage 0: backend ------------------------------------------------------

def init_backend() -> dict:
    """Initialise the backend in THIS process and say what it is.  The
    bench measures the chip: any platform but ``tpu`` raises unless the
    operator pinned ``JAX_PLATFORMS=cpu`` themselves (the CI toy lane)."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}
    if (info["platform"] != "tpu"
            and os.environ.get("JAX_PLATFORMS") != "cpu"):
        raise RuntimeError(
            f"bench needs a TPU, JAX found {info} — set JAX_PLATFORMS=cpu "
            f"explicitly for the CPU toy lane (never a device metric)")
    return info


# -- final stage: pallas kernel probe ---------------------------------------

PALLAS_PROBE_TIMEOUT = float(os.environ.get("BENCH_PALLAS_TIMEOUT", 150.0))


def probe_pallas() -> None:
    """Compile + run the standalone gather kernel on the real chip and
    compare it with the XLA gather; raises on any mismatch or failure.

    Runs IN-PROCESS (a chip belongs to one process, and the bench holds
    it) and LAST: by this point every other number is already in RESULT,
    so a hang here is caught by the watchdog, which emits the accumulated
    JSON and exits non-zero."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.gather import ROW_UNIT, _pallas_gather

    f = 64
    f3 = (jnp.arange(f * ROW_UNIT, dtype=jnp.int32) % 251
          ).astype(jnp.uint8).reshape(f, 8, ROW_UNIT // 8)
    ids = jnp.array([3, 1, 63, 0, 17, 3, 62, 9], jnp.int32)
    out = jax.block_until_ready(_pallas_gather(f3, ids))
    ref = jnp.take(f3.reshape(f, -1), ids, axis=0)
    if not bool(jnp.array_equal(out, ref)):
        raise RuntimeError("on-chip pallas gather != XLA gather")


# -- part 1: fused learner step --------------------------------------------

def _synthetic_chunk(rng):
    """A representative actor chunk: CHUNK transitions over CHUNK_FRAMES
    contiguous frames, stacks referencing chunk-relative windows."""
    import numpy as np
    d = int(np.prod(FRAME_SHAPE))
    base = np.minimum(np.arange(CHUNK), CHUNK_FRAMES - 1 - 3)
    offs = np.arange(-(FRAME_STACK - 1), 1)
    obs_ref = np.maximum(base[:, None] + offs[None, :], 0).astype(np.int32)
    next_ref = np.minimum(obs_ref + 3, CHUNK_FRAMES - 1).astype(np.int32)
    chunk = dict(
        frames=rng.integers(0, 255, (CHUNK_FRAMES, d)).astype(np.uint8),
        n_frames=np.int32(CHUNK_FRAMES),
        n_trans=np.int32(CHUNK),
        action=rng.integers(0, 6, CHUNK).astype(np.int32),
        reward=rng.normal(size=CHUNK).astype(np.float32),
        discount=np.full(CHUNK, 0.99 ** 3, np.float32),
        obs_ref=obs_ref,
        next_ref=next_ref,
    )
    prios = np.abs(rng.normal(size=CHUNK)).astype(np.float32) + 1e-3
    return chunk, prios


def bench_fused_step() -> dict:
    """The fused ingest+sample+update+write-back step, pre-staged device
    inputs, REPS timed repetitions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models.dueling import DuelingDQN
    from apex_tpu.ops.gather import resolved_mode
    from apex_tpu.ops.losses import make_optimizer
    from apex_tpu.replay.frame_pool import FramePoolReplay
    from apex_tpu.training.learner import LearnerCore
    from apex_tpu.training.state import create_train_state

    model = DuelingDQN(num_actions=6)
    pool = FramePoolReplay(capacity=CAPACITY, frame_shape=FRAME_SHAPE,
                           frame_stack=FRAME_STACK,
                           frame_capacity=FRAME_CAPACITY)
    optimizer = make_optimizer()
    ts = create_train_state(
        model, optimizer, jax.random.key(0),
        jnp.zeros((1, 84, 84, FRAME_STACK), jnp.uint8))
    core = LearnerCore(apply_fn=model.apply, replay=pool,
                       optimizer=optimizer, batch_size=BATCH,
                       target_update_interval=2500)
    rs = pool.init()
    gather = resolved_mode(rs.frames, pool.gather_mode)

    rng = np.random.default_rng(0)
    chunk, prios = _synthetic_chunk(rng)
    chunk = jax.device_put(chunk)
    prios = jax.device_put(jnp.asarray(prios))

    fused = core.jit_fused_step()
    for i in range(WARMUP_STEPS):
        ts, rs, metrics = fused(ts, rs, chunk, prios, jax.random.key(i),
                                jnp.float32(0.4))
    jax.block_until_ready(metrics["loss"])

    rates = []
    for rep in range(REPS):
        t0 = time.perf_counter()
        for i in range(MEASURE_STEPS):
            ts, rs, metrics = fused(ts, rs, chunk, prios,
                                    jax.random.key(1000 * rep + i),
                                    jnp.float32(0.4))
        jax.block_until_ready(metrics["loss"])
        rates.append(MEASURE_STEPS / (time.perf_counter() - t0))

    from apex_tpu.utils.profiling import (device_peak_flops, flops_per_call,
                                          mfu)
    flops = flops_per_call(fused, ts, rs, chunk, prios, jax.random.key(0),
                           jnp.float32(0.4))
    # utilization is a device metric: the CPU toy lane reports none
    peak = (device_peak_flops() if RESULT.get("platform") == "tpu"
            else 0.0)
    util = mfu(flops, float(np.median(rates)), peak)
    out = {"median": float(np.median(rates)),
           "min": round(min(rates), 2), "max": round(max(rates), 2),
           "reps": REPS, "gather": gather,
           "mfu": None if util is None else round(util, 4)}

    # scan-of-K dispatch: same per-step program (tests pin bit-parity),
    # K fewer host round-trips (host dispatch latency).  Reported
    # per-STEP so the unit stays comparable.  The CPU toy lane sets
    # BENCH_SCAN=0 itself: XLA:CPU lowers the conv backward far slower
    # inside while-loops, so a CPU scan number is a backend artifact.
    k = int(os.environ.get("BENCH_SCAN", 8))
    if k > 1:
        multi = core.jit_fused_multi_step()
        stacked = jax.device_put(jax.tree.map(
            lambda x: jnp.stack([jnp.asarray(x)] * k), chunk))
        sprios = jax.device_put(jnp.stack([jnp.asarray(prios)] * k))
        n_dispatch = max(1, MEASURE_STEPS // k)
        keys = jax.random.split(jax.random.key(7), k)
        ts, rs, m = multi(ts, rs, stacked, sprios, keys, jnp.float32(0.4))
        jax.block_until_ready(m["loss"])              # compile + warm
        scan_rates = []
        for rep in range(REPS):
            t0 = time.perf_counter()
            for i in range(n_dispatch):
                keys = jax.random.split(
                    jax.random.key(5000 + 1000 * rep + i), k)
                ts, rs, m = multi(ts, rs, stacked, sprios, keys,
                                  jnp.float32(0.4))
            jax.block_until_ready(m["loss"])
            scan_rates.append(n_dispatch * k
                              / (time.perf_counter() - t0))
        # apexlint: disable=J004 -- flops probe re-invokes with measurement-only keys
        sflops = flops_per_call(multi, ts, rs, stacked, sprios, keys,
                                jnp.float32(0.4))
        sutil = mfu(None if sflops is None else sflops / k,
                    float(np.median(scan_rates)), peak)
        out["scan"] = {"k": k, "median": float(np.median(scan_rates)),
                       "min": round(min(scan_rates), 2),
                       "max": round(max(scan_rates), 2),
                       "mfu": None if sutil is None else round(sutil, 4)}
    return out


# -- part 1b: async ingest pipeline on vs off -------------------------------

def bench_ingest_pipeline(n_dp: int = 1) -> dict:
    """The per-ingest framepool hot loop through the REAL concurrent
    trainer, pipeline ON vs OFF, same pre-recorded chunk stream.

    ``n_dp > 1`` runs the SAME A/B over the sharded (shard_map) plan:
    chunks round-robin onto ``n_dp`` replay shards through the
    ChunkAggregator, the pipelined lane stages whole groups (per-shard
    merged when ingest-only) plus pre-split per-chip keys, and the
    serial lane pays the per-dispatch split_ingest/device_keys cost
    inline — the exact contrast the dp staging follow-up exists to
    measure.  Runs in the dp child process (``--dp-pipe-child``) on the
    host-platform-device-count emulated mesh.

    The stream arrives PICKLED (the decode cost every real data plane
    pays — mp.Queue pickle or socket recv) through an in-process pool, in
    the ingest-dominant regime a production Ape-X learner actually runs
    (train_ratio caps steps well below chunk supply, so most chunks are
    absorbed ingest-only).  Serial pays decode + H2D + one dispatch per
    chunk inline on the hot loop; the pipeline moves decode/staging onto
    the background thread and coalesces ingest-only chunks into merged
    payloads (training/ingest_pipeline.py).  Both lanes run the same
    step/transition quantum, so the transitions-per-second ratio is the
    pipeline's honest speedup on this machine — recorded either way,
    with the dispatch-gap stats that locate where the host time went.

    Small MLP geometry on purpose: the stage measures the INGEST path
    (dispatch count, decode, staging), not MXU throughput — part 1 and
    the e2e stage own those.
    """
    import pickle

    import numpy as np

    from apex_tpu.config import (ActorConfig, ApexConfig, EnvConfig,
                                 LearnerConfig, ReplayConfig)
    from apex_tpu.replay.frame_chunks import FrameChunkBuilder
    from apex_tpu.runtime import codec as wire_codec
    from apex_tpu.training.apex import ApexTrainer

    # the chunk stream honors APEX_WIRE_CODEC (default raw): under
    # delta/dict the A/B re-runs with every poll paying the codec's
    # decode instead of a plain unpickle — the ingest-envelope check the
    # part-1g acceptance bar asks of the compressed lanes
    bench_codec = wire_codec.resolve_codec(None)
    chunk_k = int(os.environ.get("BENCH_PIPE_CHUNK", 128))
    batch = int(os.environ.get("BENCH_PIPE_BATCH", 128))
    ratio = float(os.environ.get("BENCH_PIPE_RATIO", 0.015625))
    steps = int(os.environ.get("BENCH_PIPE_STEPS", 24))
    reps = int(os.environ.get("BENCH_PIPE_REPS", 2))
    warm_steps = 4
    # chunk supply sized so neither lane ever runs dry: warmup fill plus
    # steps/ratio budget over all reps, with 2x headroom.  A small set of
    # UNIQUE chunks is recycled to keep stream generation off the stage
    # budget — every poll still pays the full decode (fresh pickle.loads
    # per message), which is what the lanes measure.
    n_chunks = int(2 * (1024 + (warm_steps + reps * steps) * batch / ratio)
                   / chunk_k) + 8
    n_unique = min(n_chunks, 96)

    rng = np.random.default_rng(0)
    builder = FrameChunkBuilder(3, 0.99, 1, (4,), chunk_transitions=chunk_k,
                                frame_dtype=np.float32)
    unique: list[bytes] = []
    while len(unique) < n_unique:
        builder.begin_episode(rng.normal(size=4).astype(np.float32))
        ep_len = int(rng.integers(20, 200))
        for t in range(ep_len):
            builder.add_step(int(rng.integers(0, 2)), float(rng.normal()),
                             rng.normal(size=2).astype(np.float32),
                             rng.normal(size=4).astype(np.float32),
                             terminated=t == ep_len - 1, truncated=False)
        for chunk in builder.poll():
            prios = chunk.pop("priorities")
            unique.append(wire_codec.encode_chunk(
                {"payload": chunk, "priorities": prios,
                 "n_trans": int(chunk["n_trans"])}, bench_codec)[0])
    unique = unique[:n_unique]
    blobs = [unique[i % n_unique] for i in range(n_chunks)]

    def _load(b: bytes) -> dict:
        # in-process replay of a stream this bench encoded itself; no
        # trust boundary
        # apexlint: disable=C005 -- same-process bench stream
        kind, body = pickle.loads(b)
        return (wire_codec.decode_chunk(body) if kind == "chunkc"
                else body)

    class _PickledStreamPool:
        """In-process stand-in for the worker data plane: chunks decode
        (unpickle) at poll time — on the hot loop serially, on the
        staging thread pipelined; params pay the publish serialization
        either way."""

        def __init__(self, stream):
            self._stream = list(stream)
            self.procs = []

        def start(self):
            pass

        def cleanup(self):
            pass

        def publish_params(self, version, params):
            pickle.dumps(params, protocol=pickle.HIGHEST_PROTOCOL)

        def poll_stats(self):
            return []

        def poll_chunks(self, max_chunks, timeout=0.0):
            out = []
            while self._stream and len(out) < max_chunks:
                out.append(_load(self._stream.pop(0)))
            return out

    def warm_shapes(trainer, pipeline_on: bool) -> None:
        """Compile every dispatch shape the lane will use OUTSIDE the
        timed window, on throwaway copies of the donated states (the
        compile cost is a once-per-process constant, not the per-step
        throughput this stage measures)."""
        import jax
        import jax.numpy as jnp

        from apex_tpu.training.ingest_pipeline import (merge_chunk_messages,
                                                       merge_group_messages)

        def cp(tree):
            return jax.tree.map(jnp.copy, tree)

        key_f, key_t = jax.random.split(jax.random.key(999))
        beta = jnp.float32(0.4)
        merge_max = trainer.cfg.learner.pipeline_merge
        msgs = [_load(b) for b in blobs[:merge_max * max(1, n_dp)]]
        if n_dp > 1:
            # the dp lanes dispatch GROUP-granular payloads (aggregator
            # stacking); merged widths per-shard-merge whole groups
            from apex_tpu.parallel.aggregate import stack_chunk_messages
            groups = []
            for i in range(0, len(msgs) - n_dp + 1, n_dp):
                payload, gprios, n_tr = stack_chunk_messages(
                    msgs[i:i + n_dp])
                groups.append({"payload": payload, "priorities": gprios,
                               "n_trans": n_tr})
            msgs = groups
            merge = lambda mm: merge_group_messages(mm, n_dp)  # noqa: E731
        else:
            merge = merge_chunk_messages

        def forms(msg):
            payload = msg["payload"]
            prios = np.asarray(msg["priorities"], np.float32)
            if pipeline_on and n_dp == 1:  # staged slots: device arrays
                return jax.device_put(payload), jax.device_put(prios)
            return payload, jnp.asarray(prios)

        pay, pr = forms(msgs[0])
        jax.block_until_ready(
            trainer._ingest(cp(trainer.replay_state), pay, pr))
        out = trainer._fused(cp(trainer.train_state),
                             cp(trainer.replay_state), pay, pr, key_f, beta)
        jax.block_until_ready(out[2]["loss"])
        out = trainer._train(cp(trainer.train_state),
                             cp(trainer.replay_state), key_t, beta)
        jax.block_until_ready(out[2]["loss"])
        if pipeline_on:
            w, outs = 2, []
            while w <= merge_max and w <= len(msgs):
                mpay, mpr = forms(merge(msgs[:w]))
                outs.append(trainer._ingest(cp(trainer.replay_state),
                                            mpay, mpr))
                w *= 2
            jax.block_until_ready(outs)

    def lane(pipeline_on: bool) -> dict:
        cfg = ApexConfig(
            env=EnvConfig(env_id="ApexCartPole-v0", frame_stack=1,
                          clip_rewards=False, episodic_life=False),
            replay=ReplayConfig(capacity=2 ** 13, warmup=1024),
            learner=LearnerConfig(batch_size=batch, ingest_chunk=chunk_k,
                                  compute_dtype="float32",
                                  target_update_interval=500,
                                  ingest_pipeline=pipeline_on,
                                  pipeline_merge=32,
                                  mesh_shape=(n_dp,)),
            actor=ActorConfig(n_actors=1, send_interval=chunk_k),
        )
        trainer = ApexTrainer(cfg, pool=_PickledStreamPool(blobs),
                              publish_min_seconds=1.0, train_ratio=ratio,
                              respawn_workers=False)
        warm_shapes(trainer, pipeline_on)
        # warm call: the loop's own paths (publish copies, rate counters)
        trainer.train(total_steps=warm_steps, max_seconds=120,
                      log_every=10 ** 9)
        runs = []
        for _ in range(reps):        # best-of-reps damps 1-core scheduler
            ingested0 = trainer.ingested         # noise in short windows
            steps0 = trainer.steps_rate.total
            t0 = time.perf_counter()
            trainer.train(total_steps=steps, max_seconds=120,
                          log_every=10 ** 9)
            dt = time.perf_counter() - t0
            runs.append({
                "trans_per_sec":
                    round((trainer.ingested - ingested0) / dt, 1),
                "steps_per_sec":
                    round((trainer.steps_rate.total - steps0) / dt, 2),
                "seconds": round(dt, 2),
                "transitions": trainer.ingested - ingested0,
                "dispatch_gap": trainer._dispatch_gap.snapshot(),
            })
        out = max(runs, key=lambda r: r["trans_per_sec"])
        out["reps"] = [r["trans_per_sec"] for r in runs]
        if pipeline_on:
            out["pipeline"] = trainer._pipeline_last_stats
        return out

    serial = lane(False)
    pipelined = lane(True)
    speedup = (pipelined["trans_per_sec"] / serial["trans_per_sec"]
               if serial["trans_per_sec"] else None)
    return {"geometry": f"cartpole-mlp_b{batch}_k{chunk_k}"
                        + (f"_dp{n_dp}" if n_dp > 1 else ""),
            "n_dp": n_dp, "wire_codec": bench_codec,
            "train_ratio": ratio, "steps": steps,
            "serial": serial, "pipelined": pipelined,
            "speedup": None if speedup is None else round(speedup, 3)}


# -- part 1c: the dp>1 lane in a device-count-emulated child ----------------

DP_PIPE_DEVICES = int(os.environ.get("BENCH_DP_PIPE_DEVICES", 4))
DP_PIPE_TIMEOUT = float(os.environ.get("BENCH_DP_PIPE_TIMEOUT", 420.0))


def _dp_pipe_child() -> None:
    """Child entry (``bench.py --dp-pipe-child``): run the part-1b A/B
    over the sharded plan and print ONE JSON line.  The parent launched
    us with JAX_PLATFORMS=cpu and
    ``--xla_force_host_platform_device_count=DP_PIPE_DEVICES`` — device
    count is a process-startup flag, so the dp mesh can only exist in a
    fresh interpreter (the parent's backend is already initialized).

    Default chunk size is SMALLER than the single-shard lane's: a
    round-robin group is ``n_dp`` chunks, so equal-size chunks would
    start the serial dp lane with its dispatch overhead already
    amortized n_dp-fold and the A/B would measure mostly the merge copy
    cost.  chunk 32 x dp 4 keeps the per-dispatch transition quantum
    (128) equal to the single-shard lane's — the same
    dispatch-overhead-dominant regime, now over the shard_map plan."""
    os.environ.setdefault("BENCH_PIPE_CHUNK",
                          os.environ.get("BENCH_DP_PIPE_CHUNK", "32"))
    print(json.dumps(bench_ingest_pipeline(n_dp=DP_PIPE_DEVICES)),
          flush=True)


def bench_ingest_pipeline_dp() -> dict:
    """Spawn the dp>1 pipeline A/B on a CPU mesh emulated via
    ``--xla_force_host_platform_device_count`` in a subprocess, and
    return its JSON (with per-lane DispatchGapTimer stats, so the
    multichip artifacts pick up the sharded loop's gap trend).  A child
    that times out or prints no JSON raises."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count"
            f"={DP_PIPE_DEVICES}").strip()
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--dp-pipe-child"],
        capture_output=True, text=True, timeout=DP_PIPE_TIMEOUT, env=env)
    return _child_json(p, "dp child")


def _child_json(p, what: str) -> dict:
    """The last JSON line a child lane printed; raises with the tail of
    its output when there is none."""
    for line in reversed(p.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(f"{what} (rc={p.returncode}) printed no JSON: "
                       + (p.stderr or p.stdout or "no output")[-400:])


# -- part 1d: actor-plane double-buffer A/B ---------------------------------

ACTOR_AB_TIMEOUT = float(os.environ.get("BENCH_ACTOR_AB_TIMEOUT", 300.0))


def _burn_cpu(n: int = 4_000_000) -> int:
    """Fixed CPU burn for the effective-core probe (module-level: spawn
    contexts pickle the target by reference)."""
    x = 0
    for i in range(n):
        x += i * i
    return x


def _burn_child(n: int, barrier, out_q) -> None:
    """Probe child: sync on the barrier (so both children burn
    CONCURRENTLY and spawn startup stays out of the measurement), then
    time its own burn."""
    barrier.wait()
    t0 = time.perf_counter()
    _burn_cpu(n)
    out_q.put(time.perf_counter() - t0)


def _effective_cores(samples: int = 2) -> float:
    """Measured parallel CPU capacity (2-process scaling of a fixed burn,
    barrier-synced, per-child timed).  The double-buffer A/B is a PURE
    SCHEDULING experiment (both modes run bit-identical work — the parity
    pin demands it), so its ceiling is exactly this number: a 1-core
    cgroup shows ~1.0x by physics, a 2-core actor host can show the real
    overlap win.  Recorded so the artifact is interpretable across
    boxes."""
    import multiprocessing as mp
    import queue as queue_lib

    n = 4_000_000
    ctx = mp.get_context("spawn")
    ones = []
    for _ in range(samples):
        t0 = time.perf_counter()
        _burn_cpu(n)
        ones.append(time.perf_counter() - t0)
    ratios = []
    for _ in range(samples):
        barrier = ctx.Barrier(3)
        out_q = ctx.Queue()
        ps = [ctx.Process(target=_burn_child, args=(n, barrier, out_q),
                          daemon=True) for _ in range(2)]
        try:
            for p in ps:
                p.start()
            # a child that dies before the barrier (spawn pickling only
            # resolves _burn_child when this module is importable under
            # its real name) must never hang the probe: bounded waits,
            # 0.0 = probe unavailable
            barrier.wait(timeout=30)
            times = [out_q.get(timeout=60) for _ in range(2)]
        except (threading.BrokenBarrierError, queue_lib.Empty):
            return 0.0
        finally:
            for p in ps:
                if p.is_alive():
                    p.terminate()
                p.join(timeout=10)
        ratios.append(2 * min(ones) / max(max(times), 1e-9))
    return round(max(ratios), 2)


def bench_actor_plane() -> dict:
    """Part 1d: the vector-actor hot loop, double-buffer on vs off, same
    fixed-seed env batch and key chain (the modes are bit-identical per
    slot — tests/test_vector.py pins it — so frames/s is the ONLY thing
    the knob changes).  Two geometries: the toy CartPole MLP (dispatch-
    overhead regime) and the 84x84x4 pixel conv (inference-bound regime,
    the flagship shape).  Reports per-mode frames/s and the PhaseTimer
    overlap split (policy-wait / env-step fractions), plus the box's
    measured effective cores — the scheduling win's hard ceiling."""
    import jax
    import numpy as np

    from apex_tpu.actors.pool import actor_epsilons
    from apex_tpu.actors.vector import VectorDQNWorkerFamily
    from apex_tpu.config import ApexConfig, ActorConfig, EnvConfig
    from apex_tpu.models.dueling import DuelingDQN
    from apex_tpu.ops.losses import make_optimizer
    from apex_tpu.training.apex import dqn_env_specs
    from apex_tpu.training.state import create_train_state

    steps = int(os.environ.get("BENCH_ACTOR_STEPS", 60))
    reps = int(os.environ.get("BENCH_ACTOR_REPS", 3))
    warm = 6

    def make_family(env_cfg: EnvConfig, n_envs: int, double_buffer: bool):
        cfg = ApexConfig(env=env_cfg,
                         actor=ActorConfig(n_actors=1,
                                           n_envs_per_actor=n_envs,
                                           double_buffer=double_buffer))
        model_spec, frame_shape, frame_dtype, frame_stack = \
            dqn_env_specs(cfg)
        fam = VectorDQNWorkerFamily(
            cfg, model_spec,
            seeds=[cfg.env.seed + 1000 * (s + 1) for s in range(n_envs)],
            slot_ids=list(range(n_envs)),
            epsilons=actor_epsilons(n_envs), chunk_transitions=64)
        model = DuelingDQN(**model_spec)
        stacked = frame_shape[:-1] + (frame_stack * frame_shape[-1],)
        ts = create_train_state(model, make_optimizer(), jax.random.key(0),
                                np.zeros((1,) + stacked, frame_dtype))
        fam.reset_all()
        return fam, ts.params

    def timed_window(fam, params, key, n_steps: int):
        fam.phase.window(reset=True)
        t0 = time.perf_counter()
        for _ in range(n_steps):
            key, k = jax.random.split(key)
            fam.step_all(params, k)
            fam.poll_msgs()
        dt = time.perf_counter() - t0
        w = fam.phase.window(reset=False)
        return key, {
            "frames_per_sec": round(n_steps * fam.n_envs / dt, 1),
            "policy_wait_frac":
                round(w["fracs"].get("policy_wait", 0.0), 3),
            "env_step_frac": round(w["fracs"].get("env_step", 0.0), 3),
            "dispatch_gap_ms_p50":
                round(fam.gap.snapshot()["dispatch_gap_ms_p50"], 3),
            "seconds": round(dt, 2)}

    def ab(env_cfg: EnvConfig, n_envs: int, n_steps: int) -> dict:
        fams = {mode: make_family(env_cfg, n_envs, mode)
                for mode in (False, True)}
        keys = {mode: jax.random.key(7) for mode in fams}
        for mode, (fam, params) in fams.items():     # compile + warm
            for _ in range(warm):
                keys[mode], k = jax.random.split(keys[mode])
                fam.step_all(params, k)
                fam.poll_msgs()
        runs: dict[bool, list] = {False: [], True: []}
        for _ in range(reps):         # alternate modes so scheduler drift
            for mode in (False, True):     # hits both; best-of-reps damps
                fam, params = fams[mode]   # 1-core noise (cf. part 1b)
                keys[mode], r = timed_window(fam, params, keys[mode],
                                             n_steps)
                runs[mode].append(r)
        best = {mode: max(rs, key=lambda r: r["frames_per_sec"])
                for mode, rs in runs.items()}
        for mode, rs in runs.items():
            best[mode]["reps"] = [r["frames_per_sec"] for r in rs]
        for fam, _ in fams.values():
            fam.close()
        return {
            "n_envs": n_envs, "vector_steps": n_steps,
            "off": best[False], "on": best[True],
            "speedup": (round(best[True]["frames_per_sec"]
                              / best[False]["frames_per_sec"], 3)
                        if best[False]["frames_per_sec"] else None)}

    toy = EnvConfig(env_id="ApexCartPole-v0", frame_stack=1,
                    clip_rewards=False, episodic_life=False)
    pixel = EnvConfig(env_id="ApexCatch-v0", frame_stack=FRAME_STACK,
                      clip_rewards=False, episodic_life=False)
    return {"effective_cores": _effective_cores(),
            "toy": ab(toy, 32, steps * 4),
            "pixel": ab(pixel, 16, steps)}


# -- part 1e: inference-plane remote/local A/B ------------------------------

INFER_AB_TIMEOUT = float(os.environ.get("BENCH_INFER_AB_TIMEOUT", 300.0))


def bench_infer_plane() -> dict:
    """Part 1e: the vector-actor hot loop with the policy served by the
    centralized inference plane vs computed locally, same fixed-seed env
    batch and key chain (remote and local are BIT-IDENTICAL per slot —
    tests/test_infer.py pins it — so frames/s, round-trip, and coalesce
    latency are the ONLY things the knob changes).  The server runs
    in-process on a second thread, which on this 1-core driver box makes
    remote a pure-plumbing-cost measurement; ``effective_cores`` is
    recorded like part 1d so a multi-core/TPU run's real batching win
    stays legible against it."""
    import socket as socket_lib
    import threading as threading_lib

    import jax
    import numpy as np

    from apex_tpu.actors.pool import actor_epsilons
    from apex_tpu.actors.vector import VectorDQNWorkerFamily
    from apex_tpu.config import (ActorConfig, ApexConfig, CommsConfig,
                                 EnvConfig)
    from apex_tpu.infer_service.client import InferClient
    from apex_tpu.infer_service.service import InferServer
    from apex_tpu.models.dueling import DuelingDQN, make_policy_fn
    from apex_tpu.ops.losses import make_optimizer
    from apex_tpu.training.apex import dqn_env_specs
    from apex_tpu.training.state import create_train_state

    steps = int(os.environ.get("BENCH_INFER_STEPS", 120))
    warm = 6

    def free_port() -> int:
        s = socket_lib.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def ab(env_cfg: EnvConfig, n_envs: int, n_steps: int) -> dict:
        comms = CommsConfig(infer_port=free_port())
        cfg = ApexConfig(env=env_cfg, comms=comms,
                         actor=ActorConfig(n_actors=1,
                                           n_envs_per_actor=n_envs))
        model_spec, frame_shape, frame_dtype, frame_stack = \
            dqn_env_specs(cfg)
        model = DuelingDQN(**model_spec)
        stacked = frame_shape[:-1] + (frame_stack * frame_shape[-1],)
        ts = create_train_state(model, make_optimizer(), jax.random.key(0),
                                np.zeros((1,) + stacked, frame_dtype))
        server = InferServer(comms, make_policy_fn(model), heartbeat=False)
        server.set_params(1, ts.params)
        stop = threading_lib.Event()
        thread = threading_lib.Thread(target=server.run,
                                      kwargs={"stop_event": stop},
                                      daemon=True)
        thread.start()

        out: dict = {"n_envs": n_envs, "vector_steps": n_steps}
        try:
            for mode in ("local", "remote"):
                fam = VectorDQNWorkerFamily(
                    cfg, model_spec,
                    seeds=[cfg.env.seed + 1000 * (s + 1)
                           for s in range(n_envs)],
                    slot_ids=list(range(n_envs)),
                    epsilons=actor_epsilons(n_envs), chunk_transitions=64)
                if mode == "remote":
                    fam.attach_infer(InferClient(comms, "bench-actor",
                                                 wait_s=10.0))
                fam.reset_all()
                key = jax.random.key(7)
                for _ in range(warm):
                    key, k = jax.random.split(key)
                    fam.step_all(ts.params, k)
                    fam.poll_msgs()
                t0 = time.perf_counter()
                for _ in range(n_steps):
                    key, k = jax.random.split(key)
                    fam.step_all(ts.params, k)
                    fam.poll_msgs()
                dt = time.perf_counter() - t0
                out[mode] = {
                    "frames_per_sec": round(n_steps * n_envs / dt, 1),
                    "seconds": round(dt, 2)}
                if mode == "remote":
                    client = fam.infer
                    rt = client.round_trip.snapshot()
                    out[mode] |= {
                        "remote_steps": client.remote_steps,
                        "fallbacks": client.fallbacks,
                        "round_trip_ms": {
                            "p50": round(rt["p50_s"] * 1000, 3),
                            "p90": round(rt["p90_s"] * 1000, 3),
                            "p99": round(rt["p99_s"] * 1000, 3)}}
                fam.close()
            b = server.batch_hist.snapshot()
            c = server.coalesce_hist.snapshot()
            out["server"] = {
                "dispatches": server.dispatches,
                "mean_batch": round(b["mean_s"], 2),
                "batch_p90": b["p90_s"],
                "coalesce_ms_p50": round(c["p50_s"] * 1000, 3),
                "coalesce_ms_p90": round(c["p90_s"] * 1000, 3)}
            out["speedup"] = (round(out["remote"]["frames_per_sec"]
                                    / out["local"]["frames_per_sec"], 3)
                              if out["local"]["frames_per_sec"] else None)
        finally:
            stop.set()
            thread.join(timeout=10)
            server.close()
        return out

    toy = EnvConfig(env_id="ApexCartPole-v0", frame_stack=1,
                    clip_rewards=False, episodic_life=False)
    pixel = EnvConfig(env_id="ApexCatch-v0", frame_stack=FRAME_STACK,
                      clip_rewards=False, episodic_life=False)
    return {"effective_cores": _effective_cores(),
            "toy": ab(toy, 32, steps),
            "pixel": ab(pixel, 16, max(10, steps // 4))}


# -- part 1f: on-device Anakin rollout vs host vector-actor ------------------

ONDEVICE_AB_TIMEOUT = float(os.environ.get("BENCH_ONDEVICE_TIMEOUT", 420.0))


def bench_ondevice_rollout() -> dict:
    """Part 1f: the fused on-device rollout engine (training/anakin.py —
    env step + epsilon-greedy policy + chunk assembly in ONE lax.scan) vs
    the host vector-actor loop on the same env/model/ladder.

    The host lane is measured at TWO widths: ``host_default`` is the
    shipping default topology (``n_envs_per_actor=1`` — the reference's
    one-env-per-process shape), whose per-step dispatch + python overhead
    is exactly what the fused scan retires (the 5x-class win on this
    1-core box); ``host_wide`` is width-matched to the engine's B, where
    both lanes are policy-conv-bound on one core and the multiplier
    collapses toward parity — the honest ceiling ``effective_cores``
    contextualizes, and the lane a TPU run blows open (the conv is ~free
    on the MXU while the host lane stays CPU-bound).  ``chunks_per_sec``/
    ``transitions_per_sec`` are the sealed-chunk rate into the replay
    path — the loadgen saturation figure.

    The third lane ``ondevice_fused`` (apex_tpu/ondevice/fused.py) runs
    the WHOLE training cycle — rollout + ingest + prioritized sample +
    train + priority write-back — as one device program per dispatch and
    reports acting throughput (``frames_per_sec``, apples-to-apples with
    the other lanes, which do no training) PLUS ``train_steps_per_sec``,
    the number the host loops pay dispatch round-trips for.  Leaf names
    end in ``per_sec`` so the ``obs.slo --check`` differ classifies the
    lane higher-better automatically."""
    import jax
    import numpy as np

    from apex_tpu.actors.pool import actor_epsilons
    from apex_tpu.actors.vector import VectorDQNWorkerFamily
    from apex_tpu.config import ActorConfig, ApexConfig, EnvConfig
    from apex_tpu.models.dueling import DuelingDQN
    from apex_tpu.ops.losses import make_optimizer
    from apex_tpu.training.anakin import make_anakin_engine
    from apex_tpu.training.apex import dqn_env_specs
    from apex_tpu.training.state import create_train_state

    dispatches = int(os.environ.get("BENCH_ONDEVICE_STEPS", 12))
    rollout_len = int(os.environ.get("BENCH_ONDEVICE_T", 64))

    def ab(env_cfg: EnvConfig, n_envs: int) -> dict:
        cfg = ApexConfig(env=env_cfg,
                         actor=ActorConfig(n_actors=1,
                                           n_envs_per_actor=n_envs,
                                           send_interval=64))
        model_spec, frame_shape, frame_dtype, frame_stack = \
            dqn_env_specs(cfg)
        model = DuelingDQN(**model_spec)
        stacked = frame_shape[:-1] + (frame_stack * frame_shape[-1],)
        ts = create_train_state(model, make_optimizer(),
                                jax.random.key(0),
                                np.zeros((1,) + stacked, frame_dtype))
        params = jax.device_get(ts.params)

        engine = make_anakin_engine(cfg, rollout_len=rollout_len)
        engine.rollout(params)                       # compile + warm
        t0 = time.perf_counter()
        for _ in range(dispatches):
            engine.rollout(params)
        dt = time.perf_counter() - t0
        out = {"n_envs": n_envs, "rollout_len": engine.T,
               "dispatches": dispatches,
               "ondevice": {
                   "frames_per_sec":
                       round(dispatches * engine.T * engine.B / dt, 1),
                   "chunks_per_sec": round(engine.chunks / dt, 2),
                   "transitions_per_sec":
                       round(engine.transitions / dt, 1),
                   "seconds": round(dt, 2)}}

        for label, hb, steps in (("host_default", 1, 300),
                                 ("host_wide", n_envs, 40)):
            hcfg = ApexConfig(env=env_cfg,
                              actor=ActorConfig(n_actors=1,
                                                n_envs_per_actor=hb,
                                                send_interval=64))
            fam = VectorDQNWorkerFamily(
                hcfg, model_spec,
                seeds=[hcfg.env.seed + 1000 * (s + 1) for s in range(hb)],
                slot_ids=list(range(hb)),
                epsilons=actor_epsilons(max(hb, 1)), chunk_transitions=64)
            fam.reset_all()
            key = jax.random.key(7)
            for _ in range(5):
                key, k = jax.random.split(key)
                fam.step_all(params, k)
                fam.poll_msgs()
            t0 = time.perf_counter()
            for _ in range(steps):
                key, k = jax.random.split(key)
                fam.step_all(params, k)
                fam.poll_msgs()
            hdt = time.perf_counter() - t0
            out[label] = {"n_envs": hb,
                          "frames_per_sec": round(steps * hb / hdt, 1),
                          "seconds": round(hdt, 2)}
            fam.close()

        # lane 3: the fused train step — fresh engine/replay/train state
        # so the acting key chains match the ondevice lane's shape
        from apex_tpu.ondevice.fused import FusedStep
        from apex_tpu.replay.frame_pool import FramePoolReplay
        from apex_tpu.training.learner import LearnerCore
        spd = 2
        replay = FramePoolReplay(
            capacity=4096, frame_shape=frame_shape,
            frame_stack=frame_stack,
            frame_dtype=np.dtype(frame_dtype).name)
        fused = FusedStep(
            LearnerCore(apply_fn=model.apply, replay=replay,
                        optimizer=make_optimizer(), batch_size=64,
                        target_update_interval=500),
            replay, make_anakin_engine(cfg, rollout_len=rollout_len),
            warmup=256, beta=0.4, beta_anneal=50_000,
            steps_per_dispatch=spd)
        fts = create_train_state(model, make_optimizer(),
                                 jax.random.key(1),
                                 np.zeros((1,) + stacked, frame_dtype))
        frs, fkey = replay.init(), jax.random.key(3)
        fts, frs, fkey, _ = fused.dispatch(fts, frs, fkey)  # compile+warm
        base_steps, base_trans = fused.train_steps, fused.transitions
        fdisp = max(2, dispatches // 2)
        t0 = time.perf_counter()
        for _ in range(fdisp):
            fts, frs, fkey, _ = fused.dispatch(fts, frs, fkey)
        fdt = time.perf_counter() - t0
        out["ondevice_fused"] = {
            "n_envs": n_envs, "rollout_len": fused.engine.T,
            "steps_per_dispatch": spd, "dispatches": fdisp,
            "frames_per_sec":
                round(fdisp * spd * fused.engine.T * fused.engine.B
                      / fdt, 1),
            "train_steps_per_sec":
                round((fused.train_steps - base_steps) / fdt, 2),
            "transitions_per_sec":
                round((fused.transitions - base_trans) / fdt, 1),
            "seconds": round(fdt, 2)}

        ond = out["ondevice"]["frames_per_sec"]
        out["speedup"] = (round(ond
                                / out["host_default"]["frames_per_sec"],
                                2)
                          if out["host_default"]["frames_per_sec"]
                          else None)
        out["speedup_vs_wide"] = (
            round(ond / out["host_wide"]["frames_per_sec"], 2)
            if out["host_wide"]["frames_per_sec"] else None)
        out["fused_speedup"] = (
            round(out["ondevice_fused"]["frames_per_sec"]
                  / out["host_default"]["frames_per_sec"], 2)
            if out["host_default"]["frames_per_sec"] else None)
        return out

    toy = EnvConfig(env_id="ApexCatchSmall-v0", frame_stack=2,
                    clip_rewards=False, episodic_life=False)
    pixel = EnvConfig(env_id="ApexCatch-v0", frame_stack=FRAME_STACK,
                      clip_rewards=False, episodic_life=False)
    return {"effective_cores": _effective_cores(),
            "toy": ab(toy, 32),
            "pixel": ab(pixel, 16)}


# -- part 1f lane 4: fused macro-step x dp mesh (PR 17) ----------------------

FUSED_DP_DEVICES = int(os.environ.get("BENCH_FUSED_DP_DEVICES", 2))
FUSED_DP_TIMEOUT = float(os.environ.get("BENCH_FUSED_DP_TIMEOUT", 420.0))


def _fused_dp_child(dp: int) -> None:
    """Child body for one ``fused_dp`` width: a FusedApexTrainer at the
    given dp on the toy env, timed over warm dispatches.  One JSON line
    on stdout; the parent holds the hard timeout."""
    import jax

    from apex_tpu.config import (ActorConfig, ApexConfig, EnvConfig,
                                 LearnerConfig, ReplayConfig)
    from apex_tpu.ondevice.fused import FusedApexTrainer

    dispatches = int(os.environ.get("BENCH_FUSED_DP_STEPS", 8))
    spd = 2
    cfg = ApexConfig(
        env=EnvConfig(env_id="ApexCatchSmall-v0", frame_stack=2,
                      clip_rewards=False, episodic_life=False),
        replay=ReplayConfig(capacity=4096, warmup=256,
                            beta_anneal=50_000),
        learner=LearnerConfig(batch_size=64, compute_dtype="float32",
                              target_update_interval=500,
                              publish_interval=50, mesh_shape=(dp,)),
        actor=ActorConfig(n_actors=1, n_envs_per_actor=32,
                          send_interval=64))
    t = FusedApexTrainer(cfg, rollout_len=64, steps_per_dispatch=spd)
    t.train_state, t.replay_state, t.key, _ = t.fused.dispatch(
        t.train_state, t.replay_state, t.key)        # compile + warm
    base_steps = t.fused.train_steps
    base_trans = t.fused.transitions
    eng = t.fused.engine                             # full-width B
    t0 = time.perf_counter()
    for _ in range(dispatches):
        t.train_state, t.replay_state, t.key, _ = t.fused.dispatch(
            t.train_state, t.replay_state, t.key)
    dt = time.perf_counter() - t0
    print(json.dumps({
        "dp": dp, "devices": jax.device_count(),
        "n_envs": eng.B, "rollout_len": eng.T,
        "steps_per_dispatch": spd, "dispatches": dispatches,
        "frames_per_sec":
            round(dispatches * spd * eng.T * eng.B / dt, 1),
        "train_steps_per_sec":
            round((t.fused.train_steps - base_steps) / dt, 2),
        "transitions_per_sec":
            round((t.fused.transitions - base_trans) / dt, 1),
        "seconds": round(dt, 2)}), flush=True)


def bench_fused_dp() -> dict:
    """Part 1f lane 4 ``fused_dp``: the whole fused training cycle
    (rollout + ingest + prioritized sample + train + write-back) at dp=1
    vs dp=N, each width in its own subprocess on a CPU mesh emulated via
    ``--xla_force_host_platform_device_count`` (so the forced device
    count never leaks into this process's backend).  Leaf names end in
    ``per_sec`` so the ``obs.slo --check`` differ classifies both widths
    higher-better automatically; on a 1-core box ``dp_speedup`` ~1.0 is
    the honest reading and ``effective_cores`` contextualizes it — the
    lane exists so a multi-core / TPU artifact shows the scaling."""
    n_dp = max(2, FUSED_DP_DEVICES)
    out: dict = {"n_dp": n_dp, "effective_cores": _effective_cores()}
    for label, dp in (("dp1", 1), ("dpN", n_dp)):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count"
                            f"={n_dp}")
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--fused-dp-child", str(dp)],
            capture_output=True, text=True,
            timeout=FUSED_DP_TIMEOUT, env=env)
        out[label] = _child_json(p, f"fused_dp child {label}")
    f1 = out["dp1"].get("frames_per_sec")
    fn = out["dpN"].get("frames_per_sec")
    out["dp_speedup"] = round(fn / f1, 2) if f1 and fn else None
    return out


# -- part 2: end-to-end pixel pipeline -------------------------------------

def _fleet_section(trainer) -> dict | None:
    """Fleet control-plane view of the e2e run (apex_tpu/fleet): state
    counts, heartbeat gap percentiles, and rejoin count from the same
    registry the socket learner serves on ``--role status`` — the in-host
    worker fleet beats over the stat queue, so the section is live even
    without sockets."""
    summary = trainer.fleet_summary()
    if summary is None:
        return None
    m = summary["metrics"]
    out = {"peers": m["peers"], "alive": m["alive"],
           "suspect": m["suspect"], "dead": m["dead"],
           "parked": m["parked"], "rejoins": m["rejoins"],
           "hb_gap_p50_s": m["hb_gap_p50_s"],
           "hb_gap_p99_s": m["hb_gap_p99_s"],
           "wire_rejected": m.get("wire_rejected", 0)}
    if "replay_service" in m:
        # sharded replay service (apex_tpu/replay_service): shard count,
        # batches pulled, write-back/fallback counters, per-shard status
        # — a chaos-killed shard's death is legible here next to the
        # registry's dead count above
        out["replay_service"] = m["replay_service"]
    return out


WIRE_CODEC_TIMEOUT = float(os.environ.get("BENCH_WIRE_CODEC_TIMEOUT", 240.0))


def bench_wire_codec() -> dict:
    """Part 1g: the chunk wire codec A/B (runtime/codec.py) on REAL env
    chunks — no synthetic arrays, the exact bytes an actor ships.

    Two payload families, each recorded once by driving the real env
    through the real ``FrameChunkBuilder`` and replayed through every
    codec:

    - ``catch``: ApexCatchSmall-v0 single frames (42x42 u8, ~sparse
      binary rendering — the near-binary regime the delta codec's
      XOR+RLE targets; issue target >=5x bytes/transition vs raw).
    - ``pixel``: ApexRally-v0 flagship frames (84x84 u8 — the
      dictionary codec's regime; issue target >=2x).

    Per codec x family: bytes/transition on the wire, compression ratio
    (raw pickle bytes / shipped bytes — >=1.0 by construction, the
    encoder ships raw whenever compression does not win), and
    encode/decode microseconds per chunk.  ``ingest`` replays the same
    encoded stream through :func:`codec.decode_chunk` back-to-back and
    reports frames/s — the fused-ingest decode cost the replay shard
    pays per chunk; the acceptance gate is delta within 10% of raw.
    """
    import pickle
    import time as _time

    import numpy as np

    from apex_tpu.config import EnvConfig
    from apex_tpu.envs.registry import make_env
    from apex_tpu.replay.frame_chunks import FrameChunkBuilder
    from apex_tpu.runtime import codec as wire_codec

    n_chunks = int(os.environ.get("BENCH_CODEC_CHUNKS", 24))
    chunk_k = int(os.environ.get("BENCH_CODEC_CHUNK_K", 64))

    def record(env_id: str) -> list[dict]:
        """Real chunk messages (payload + priorities + n_trans), exactly
        the dicts ChunkSender.send_chunk ships."""
        env = make_env(env_id, EnvConfig(env_id=env_id), seed=0,
                       stack_frames=False)
        rng = np.random.default_rng(0)
        obs, _ = env.reset(seed=0)
        builder = FrameChunkBuilder(3, 0.99, 4, np.asarray(obs).shape,
                                    chunk_transitions=chunk_k,
                                    frame_dtype=np.uint8)
        builder.begin_episode(np.asarray(obs))
        msgs: list[dict] = []
        n_act = env.action_space.n
        while len(msgs) < n_chunks:
            a = int(rng.integers(n_act))
            obs, r, term, trunc, _ = env.step(a)
            builder.add_step(a, float(r),
                             rng.normal(size=n_act).astype(np.float32),
                             np.asarray(obs), terminated=term,
                             truncated=trunc)
            if term or trunc:
                obs, _ = env.reset()
                builder.begin_episode(np.asarray(obs))
            for chunk in builder.poll():
                prios = chunk.pop("priorities")
                msgs.append({"payload": chunk, "priorities": prios,
                             "n_trans": int(chunk["n_trans"])})
        env.close()
        return msgs[:n_chunks]

    def measure(msgs: list[dict], codec: str) -> dict:
        wire_total = raw_total = trans_total = frames_total = 0
        enc_s = dec_s = 0.0
        encoded: list[bytes] = []
        for msg in msgs:
            t0 = _time.perf_counter()
            payload, raw_n, wire_n = wire_codec.encode_chunk(msg, codec)
            enc_s += _time.perf_counter() - t0
            encoded.append(payload)
            wire_total += wire_n
            raw_total += raw_n
            trans_total += int(msg["n_trans"])
            frames_total += int(msg["payload"]["n_frames"])
        for payload in encoded:
            # full receiver-side decode cost: the wire unpickle both
            # paths pay, plus decode_chunk for compressed payloads (the
            # fused-ingest path the decoder threads run).
            # in-process replay of a stream this bench pickled itself
            t0 = _time.perf_counter()
            # apexlint: disable=C005 -- same-process bench stream
            kind, body = pickle.loads(payload)
            if kind == "chunkc":
                wire_codec.decode_chunk(body)
            dec_s += _time.perf_counter() - t0
        n = len(msgs)
        return {"bytes_per_transition": round(wire_total / trans_total, 1),
                "codec_ratio": round(raw_total / wire_total, 2),
                "encode_us_per_chunk": round(1e6 * enc_s / n, 1),
                "decode_us_per_chunk": round(1e6 * dec_s / n, 1),
                "wire_bytes": wire_total, "raw_bytes": raw_total,
                "frames": frames_total}

    def loopback(msgs: list[dict], codec: str, reps: int = 6) -> float:
        """Receiver-side ingest frames/s through the REAL transport: a
        pre-encoded stream (the actor's seal-time encode cost is the
        separate encode_us column) pushed at a ChunkReceiver, whose
        decoder pool runs compressed decode fused with ingest, off the
        socket/ack thread — the acceptance gate compares this number
        delta-vs-raw."""
        import socket as _socket

        import zmq

        from apex_tpu.config import CommsConfig
        from apex_tpu.runtime.transport import ChunkReceiver, _ctx

        with _socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        comms = CommsConfig(batch_port=port)
        recv = ChunkReceiver(comms, bind_ip="127.0.0.1",
                             queue_depth=4 * len(msgs))
        recv.start()
        sock = _ctx().socket(zmq.DEALER)
        sock.setsockopt(zmq.IDENTITY, b"bench-codec")
        sock.connect(f"tcp://127.0.0.1:{port}")
        encoded = [wire_codec.encode_chunk(m, codec)[0] for m in msgs]
        frames_per_rep = sum(int(m["payload"]["n_frames"]) for m in msgs)
        window = 32          # saturating producer, bounded in-flight
        try:
            total = reps * len(msgs)

            def drain() -> None:    # backpressure relief: the trainer's
                for _ in range(total):      # poll_chunks stand-in
                    recv.chunks.get(timeout=30.0)

            drainer = threading.Thread(target=drain, daemon=True)
            in_flight = 0
            t0 = _time.perf_counter()
            drainer.start()
            for r in range(reps):
                for payload in encoded:
                    while in_flight >= window:
                        sock.recv()
                        in_flight -= 1
                    sock.send(payload)
                    in_flight += 1
            drainer.join(timeout=60.0)
            dt = _time.perf_counter() - t0
            if drainer.is_alive():
                raise RuntimeError("codec loopback drain stalled")
        finally:
            sock.close(linger=0)
            recv.stop()
        return round(reps * frames_per_rep / dt, 1)

    out: dict = {"chunks": n_chunks, "chunk_transitions": chunk_k}
    for family, env_id in (("catch", "ApexCatchSmall-v0"),
                           ("pixel", "ApexRally-v0")):
        msgs = record(env_id)
        section = {c: measure(msgs, c) for c in wire_codec.CODECS}
        # the acceptance gate: end-to-end ingest through the real
        # transport (sender encode + socket + decoder pool) within 10%
        # of the raw path over the identical stream
        raw_fps = loopback(msgs, "raw")
        delta_fps = loopback(msgs, "delta")
        section["ingest_frames_per_sec"] = {"raw": raw_fps,
                                            "delta": delta_fps}
        section["ingest_delta_vs_raw"] = (round(delta_fps / raw_fps, 3)
                                          if raw_fps else None)
        out[family] = section
    return out


def bench_end_to_end(e2e_seconds: float) -> dict:
    """The real ApexTrainer pipeline — vectorized actor processes feeding
    the fused learner through the shm chunk plane — on the PIXEL env
    ``ApexCatch-v0`` (84x84x4 uint8, flagship geometry) for
    ``e2e_seconds`` wall (the soak target plus the compile margin — see
    :func:`e2e_budgets`).  Runs with the async ingest pipeline at its
    config default, so the number measured is the shipping hot loop."""
    from apex_tpu.config import (ActorConfig, ApexConfig, EnvConfig,
                                 LearnerConfig, ReplayConfig)
    from apex_tpu.training.apex import ApexTrainer

    n_actors, n_envs = 4, 8          # 32 ladder slots in 4 processes
    env_id = os.environ.get("BENCH_E2E_ENV", "ApexCatch-v0")
    # scan dispatch in the live pipeline only on TPU (cf. part 1's gate:
    # the XLA:CPU conv-backward-in-loop pathology would throttle the
    # whole e2e run, not just skew one measurement)
    scan_steps = int(os.environ.get("BENCH_E2E_SCAN",
                                    4 if RESULT.get("platform") == "tpu"
                                    else 1))
    cfg = ApexConfig(
        env=EnvConfig(env_id=env_id, frame_stack=FRAME_STACK,
                      clip_rewards=False, episodic_life=False),
        replay=ReplayConfig(capacity=min(2 ** 15, CAPACITY),
                            warmup=min(2048, 4 * BATCH), frame_pool=True),
        learner=LearnerConfig(batch_size=BATCH, ingest_chunk=BATCH,
                              compute_dtype="bfloat16",
                              target_update_interval=500,
                              scan_steps=scan_steps),
        actor=ActorConfig(n_actors=n_actors, n_envs_per_actor=n_envs,
                          send_interval=64),
    )
    trainer = ApexTrainer(cfg, publish_min_seconds=0.5)
    from apex_tpu.native.ring import ShmChunkQueue
    data_plane = ("shm" if isinstance(trainer.pool.chunk_queue,
                                      ShmChunkQueue) else "mp.Queue")
    shape = trainer.replay.frame_shape
    stacked = shape[:-1] + (trainer.replay.frame_stack * shape[-1],)
    geometry = ("x".join(map(str, stacked))
                + "_" + trainer.replay.frame_dtype)
    # sample the monotone totals every 15s from a sidecar thread: the
    # consecutive-sample deltas give per-window steps/s, whose spread is
    # the soak's stability evidence (a sliding-window rate alone can't
    # show whether the run was steady or saw-toothed)
    samples: list[tuple[float, int, int]] = []
    sampler_stop = threading.Event()

    def _sampler() -> None:
        while not sampler_stop.wait(15.0):
            samples.append((time.monotonic(), trainer.steps_rate.total,
                            trainer.frames_rate.total))

    sampler = threading.Thread(target=_sampler, daemon=True)
    sampler.start()
    t0 = time.monotonic()
    try:
        trainer.train(total_steps=10 ** 9, max_seconds=e2e_seconds,
                      log_every=10 ** 9)
    finally:
        # always unpin: a still-sampling daemon would otherwise keep the
        # trainer (and its HBM replay ring) alive through the pallas stage
        sampler_stop.set()
    dt = time.monotonic() - t0

    # steady state = windows after the first one in which the learner
    # stepped (compile + replay warmup fill the preceding ones)
    windows = []
    steady_start = None
    for (ta, sa, _fa), (tb, sb, _fb) in zip(samples, samples[1:]):
        if sa > 0:
            if steady_start is None:
                steady_start = (ta, sa)
            windows.append((sb - sa) / (tb - ta))
    steady = None
    if steady_start is not None and samples and samples[-1][1] > steady_start[1]:
        t_first, s_first = steady_start
        t_last, s_last, _ = samples[-1]
        steady = {
            "steps_per_sec": round((s_last - s_first) / (t_last - t_first), 2),
            "seconds": round(t_last - t_first, 1),
            "windows": {"n": len(windows),
                        "min": round(min(windows), 2),
                        "p50": round(float(statistics.median(windows)), 2),
                        "max": round(max(windows), 2)} if windows else None,
        }

    # steady-state rates from the sliding tick windows — first-compile time
    # (~20-40s of the wall budget) would otherwise dominate the average
    return {"env": env_id,
            "steady": steady,
            # obs plane: frame-age-at-train / param-propagation-lag
            # histograms (p50/p90/p99) + hot-loop dispatch-gap percentiles
            "latency": trainer.latency_summary(),
            "obs_geometry": geometry,
            "env_frames_per_sec": round(trainer.frames_rate.rate, 1),
            "learner_steps_per_sec": round(trainer.steps_rate.rate, 2),
            "transitions_per_sec":
                round(trainer.steps_rate.rate * BATCH, 1),
            "total_frames": trainer.ingested,
            "total_steps": trainer.steps_rate.total,
            "actors": n_actors, "envs_per_actor": n_envs,
            "data_plane": data_plane,
            "scan_steps": scan_steps,
            "scan_dispatches": trainer.scan_dispatches,
            "actor_plane": trainer.actor_plane(),
            "fleet": _fleet_section(trainer),
            "ingest_pipeline": trainer._pipeline_last_stats,
            "dispatch_gap": (trainer._dispatch_gap.snapshot()
                             if trainer._dispatch_gap is not None else None),
            "seconds": round(dt, 1)}


def main() -> None:
    threading.Thread(target=_watchdog, daemon=True).start()

    from apex_tpu.utils.compile_cache import ensure_compile_cache
    ensure_compile_cache()      # before the first jit; workers inherit it

    _arm("backend_init", INIT_TIMEOUT)
    device = init_backend()
    platform = device["platform"]
    with _print_lock:
        RESULT.update(device)

    # Stage ordering: a misbehaving pallas kernel can take the device with
    # it, so every other measurement runs FIRST on the XLA gather and the
    # pallas attempt comes LAST — a failure there exits non-zero but still
    # prints everything already recorded.  Any stage that raises ends the
    # bench the same way (the crash handler in run()).
    operator_forced = os.environ.get("APEX_GATHER_MODE") not in (
        None, "", "auto")
    if not operator_forced:
        os.environ["APEX_GATHER_MODE"] = "xla"

    _arm("fused_step", PART1_TIMEOUT)
    fused = bench_fused_step()
    best = _best_variant(fused)
    bps = best["value"]               # raw median of the winning variant
    with _print_lock:
        RESULT.update(_headline_fields(best))
        RESULT["gather"] = fused["gather"]
        if fused.get("scan") is not None:
            RESULT["scan_part1"] = fused["scan"]
    print(f"[bench] part 1 done: {json.dumps(RESULT)}",
          file=sys.stderr, flush=True)

    if os.environ.get("BENCH_SKIP_PIPELINE", "0") != "1":
        _arm("ingest_pipeline", PIPELINE_TIMEOUT)
        pipe = bench_ingest_pipeline()
        with _print_lock:
            RESULT["ingest_pipeline"] = pipe
        # dp>1 variant of the same A/B, in its own emulated-mesh child
        _arm("ingest_pipeline_dp", DP_PIPE_TIMEOUT + 30)
        pipe_dp = bench_ingest_pipeline_dp()
        with _print_lock:
            RESULT["ingest_pipeline_dp"] = pipe_dp

    if os.environ.get("BENCH_SKIP_ACTOR_AB", "0") != "1":
        # part 1d: the actor-plane scheduling A/B (double-buffer on/off)
        _arm("actor_plane_ab", ACTOR_AB_TIMEOUT)
        ab = bench_actor_plane()
        with _print_lock:
            RESULT["actor_plane_ab"] = ab

    if os.environ.get("BENCH_SKIP_INFER_AB", "0") != "1":
        # part 1e: the inference-plane remote/local A/B (frames/s +
        # round-trip and coalesce percentiles + measured effective_cores,
        # machine-readable for CI upload and cross-box diffing)
        _arm("infer_plane_ab", INFER_AB_TIMEOUT)
        iab = bench_infer_plane()
        with _print_lock:
            RESULT["infer_plane_ab"] = iab

    if os.environ.get("BENCH_SKIP_ONDEVICE", "0") != "1":
        # part 1f: the fused on-device rollout engine vs the host
        # vector-actor path (frames/s at the default and width-matched
        # host topologies + sealed chunk/s into replay + effective_cores)
        _arm("ondevice_rollout_ab", ONDEVICE_AB_TIMEOUT)
        oab = bench_ondevice_rollout()
        with _print_lock:
            RESULT["ondevice_rollout_ab"] = oab

        # part 1f lane 4: the fused macro-step sharded over the dp mesh
        # (dp=1 vs dp=N subprocesses on an emulated CPU mesh)
        _arm("fused_dp", 2 * FUSED_DP_TIMEOUT + 60)
        fdp = bench_fused_dp()
        with _print_lock:
            RESULT["fused_dp"] = fdp

    if os.environ.get("BENCH_SKIP_WIRE", "0") != "1":
        # part 1g: the chunk wire codec A/B on real Catch/Rally chunks
        # (bytes/transition, compression ratio, encode/decode us, fused
        # decode frames/s vs the raw unpickle)
        _arm("wire_codec", WIRE_CODEC_TIMEOUT)
        wc = bench_wire_codec()
        with _print_lock:
            RESULT["wire_codec"] = wc

    soak, e2e_train_seconds, e2e_stage_seconds = e2e_budgets(platform)
    _arm("e2e", e2e_stage_seconds)
    e2e = bench_end_to_end(e2e_train_seconds)
    with _print_lock:
        RESULT["e2e"] = e2e
        RESULT["e2e_budgets"] = {"soak": soak, "train": e2e_train_seconds,
                                 "stage": e2e_stage_seconds}

    if (platform == "tpu" and not operator_forced
            and os.environ.get("BENCH_SKIP_PALLAS", "0") != "1"):
        _arm("pallas_probe", PALLAS_PROBE_TIMEOUT)
        probe_pallas()
        os.environ["APEX_GATHER_MODE"] = "pallas"
        _arm("fused_step_pallas", PART1_TIMEOUT)
        pf = bench_fused_step()
        pbest = _best_variant(pf)
        with _print_lock:
            RESULT["pallas_part1"] = {
                "value": round(pf["median"], 2),
                "spread": {"min": pf["min"], "max": pf["max"],
                           "reps": pf["reps"]},
                "scan": pf.get("scan"), "mfu": pf["mfu"]}
            # compare raw medians — the rounded RESULT["value"]
            # could flip a sub-0.01 loss into a "win"
            if pbest["value"] > bps:             # strict upgrade
                RESULT.update(_headline_fields(pbest))
                RESULT["gather"] = "pallas"

    _finish()


def _best_variant(fused: dict) -> dict:
    """The faster of the single-dispatch and scan-dispatch measurements
    from one :func:`bench_fused_step` result, as headline-ready fields
    (``value`` stays the RAW median so comparisons never hinge on
    rounding)."""
    scan = fused.get("scan")
    if scan is not None and scan["median"] > fused["median"]:
        return dict(value=scan["median"],
                    spread={"min": scan["min"], "max": scan["max"],
                            "reps": fused["reps"]},
                    mfu=scan["mfu"], dispatch=f"scan{scan['k']}")
    return dict(value=fused["median"],
                spread={"min": fused["min"], "max": fused["max"],
                        "reps": fused["reps"]},
                mfu=fused["mfu"], dispatch="single")


def _headline_fields(best: dict) -> dict:
    return {"value": round(best["value"], 2),
            "vs_baseline": round(best["value"] / BASELINE_BPS, 2),
            "spread": best["spread"], "mfu": best["mfu"],
            "dispatch": best["dispatch"]}


def _finish() -> None:
    _stage["deadline"] = None
    _done.set()
    # same emitter as the watchdog/crash paths; os._exit because actor
    # worker processes may still be tearing down and a wedged child must
    # not hold the exit after the JSON line is out
    _emit_and_exit(0)


def run() -> None:
    """``main()`` under the crash handler: an exception prints the
    traceback, then the accumulated partial JSON, and exits non-zero."""
    try:
        main()
    except BaseException as exc:
        import traceback
        traceback.print_exc()
        RESULT.setdefault("error", f"{type(exc).__name__}: {exc}"[:400])
        _emit_and_exit(1)


if __name__ == "__main__":
    if "--dp-pipe-child" in sys.argv:
        _dp_pipe_child()           # one JSON line; no watchdog, the
        sys.exit(0)                # parent holds the hard timeout
    if "--fused-dp-child" in sys.argv:
        _fused_dp_child(int(sys.argv[sys.argv.index("--fused-dp-child")
                                     + 1]))
        sys.exit(0)                # parent holds the hard timeout
    run()
