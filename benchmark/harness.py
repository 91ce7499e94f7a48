"""One run of one cell: set-up, the checked steps, the window, the
comparison with the plain reference, the result line.

The system under test is reached only through the entry a user types:
``runtime.cli.build_parser -> config_from_args -> build_trainer ->
trainer.train()``; the argv comes from the cell's configuration and
traffic files.  Everything else (weights and rows from the seed, the
clock, counters read at the window's edges, the reference, the limits)
is the benchmark's own.
"""

from __future__ import annotations

import functools
import gc
import importlib
import json
import math
import os
import sys
import threading
import time
import warnings

import numpy as np

from benchmark import feed

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RUN_DIR = os.path.join(ROOT, ".bench_run")


class CompileMeter:
    """XLA backend-compile seconds and events (a persistent-cache hit
    costs its retrieval time), plus the cache's hit/miss counts.  Copied
    from ``chip_smoke.py``."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.seconds, self.events, self.hits, self.misses = 0.0, 0, 0, 0
        self.last_event = time.monotonic()
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.events += 1
            self.last_event = time.monotonic()

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """The cell's entry of ``BENCHMARK.json`` with its configuration and
    traffic files, found by name."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == cell["config"])
    config = load_json(ROOT, config_entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return dict(bench=bench, cell=cell, config=config, traffic=traffic)


def load_reader(name: str):
    """``benchmark/metrics/<name>.py``, by path (a name may hold dots)."""
    import importlib.util
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_for(bench: dict, workload: str, kind: str) -> list[dict]:
    """The cell's metrics of one kind (``end_to_end`` / ``per_layer``)."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


# -- leaves by parameter path, and their norms where they live ----------------

def _dict_keys(path) -> tuple[str, ...]:
    return tuple(str(k.key) for k in path if hasattr(k, "key"))


def by_path(tree) -> dict[tuple, object]:
    """The tree's leaves keyed by parameter path, where they are (device
    or host): nothing is copied."""
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {_dict_keys(p): x for p, x in flat}


def moment_by_path(opt_state, field: str) -> dict[tuple, object]:
    """The optimizer's first-moment leaves keyed by parameter path,
    wherever the optimizer's chain keeps them (``.mu`` of RMSprop's
    state)."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(opt_state)[0]:
        names = [getattr(k, "name", None) for k in path]
        if field not in names or not hasattr(leaf, "shape"):
            continue
        after = path[len(names) - 1 - names[::-1].index(field) + 1:]
        out[_dict_keys(after)] = leaf
    return out


@functools.cache
def _norm_programs():
    """The two jitted reductions ``leaf_norms`` runs: every leaf's L2 norm,
    and that of the difference of two trees, in float32."""
    import jax
    import jax.numpy as jnp

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    return (jax.jit(lambda xs: [norm(x) for x in xs]),
            jax.jit(lambda xs, ys: [norm(x - y) for x, y in zip(xs, ys)]))


def leaf_norms(leaves: dict, minus: dict | None = None) -> dict[tuple, float]:
    """The L2 norm of every leaf (of ``leaf - minus[path]`` where ``minus``
    is given), in float32, in one program on the device the leaves live
    on: a scalar a leaf comes to the host, no leaf does."""
    import jax
    norms, norms_of_change = _norm_programs()
    xs = list(leaves.values())
    got = (norms(xs) if minus is None else
           norms_of_change(xs, [minus[k] for k in leaves]))
    return {k: float(n) for k, n in zip(leaves, jax.device_get(got))}


def drop_norm_programs() -> None:
    """Unload what ``leaf_norms`` compiled.  A loaded executable holds
    device memory (194 KB each for ``dqn``'s tree): left loaded after the
    checked steps, the two would stand in the window's peak."""
    for program in _norm_programs():
        program.clear_cache()


# -- the compared numbers -------------------------------------------------------

def leaf_norm_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """Per leaf ``| ||prog|| - ||ref|| | / max(||ref||, median ||ref||)``
    from the two sides' norms by path (``leaf_norms``): the gap between
    the two norms, not the norm of the difference."""
    med = float(np.median(list(ref.values())))
    out = {}
    for k, rn in ref.items():
        if keep is not None and not keep(k):
            continue
        gap = float("inf")
        if k in prog:
            gap = abs(prog[k] - rn) / max(rn, med, 1e-30)
        out["/".join(k)] = gap if math.isfinite(gap) else float("inf")
    return out


def _worst(gaps: dict) -> tuple[float, str]:
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def readings(prog: dict, ref: dict) -> dict:
    """Every number a cell can be held to, ``{name: (value, detail)}``;
    the configuration's ``limits`` say which of them it is held to."""
    out = {}
    loss = [abs(p - r) / max(abs(r), 1e-30)
            for p, r in zip(prog["losses"], ref["losses"])]
    loss = [g if math.isfinite(g) else float("inf") for g in loss]
    out["loss_gap"] = (max(loss), f"step {int(np.argmax(loss)) + 1}")
    out["loss1_gap"] = (loss[0], "step 1")
    # the leaves whose priority a step wrote back, against the rows the
    # reference's step sampled: an exact comparison, every row of the batch
    miss = [len(np.setxor1d(p, r))
            for p, r in zip(prog["written"], ref["written"])]
    out["writeback_miss"] = (float(max(miss)),
                             f"step {int(np.argmax(miss)) + 1}")
    # mean Q of the actions taken, against the mean |Q|: no arg-max in it
    q = [abs(p - r) / max(a, 1e-30) for p, r, a in
         zip(prog["q_means"], ref["q_means"], ref["q_abs"])]
    q = [g if math.isfinite(g) else float("inf") for g in q]
    out["q_gap"] = (max(q), f"step {int(np.argmax(q)) + 1}")
    out["q1_gap"] = (q[0], "step 1")
    grad = leaf_norm_gaps(prog["first_grad_norm"], ref["first_grad_norm"])
    out["grad_gap"] = _worst(grad)
    out["grad_median_gap"] = (float(np.median(list(grad.values()))),
                              "median leaf")
    # leaves whose reference gradient is nought to rounding move under the
    # optimizer by round-off alone: left out by a rule on the gradient
    gnorm = ref["first_grad_norm"]
    gmed = float(np.median(list(gnorm.values())))
    dpar = leaf_norm_gaps(prog["dparam_norm"], ref["dparam_norm"],
                          keep=lambda k: gnorm[k] >= 1e-3 * gmed)
    out["dparam_gap"] = _worst(dpar)
    out["dparam_median_gap"] = (float(np.median(list(dpar.values()))),
                                "median leaf")
    return out


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """``{name: (value, limit, detail)}`` for every number compared."""
    got = readings(prog, ref)
    return {name: (got[name][0], limit, got[name][1])
            for name, limit in limits.items()}


def verdict(checks: dict, numbers: dict) -> tuple[bool, dict]:
    """``correct`` and the ``compared`` record: every liveness check and
    every number beside its limit."""
    correct, compared = True, {}
    for name, ok in checks.items():
        compared[name] = [int(bool(ok)), 1]
        correct = correct and bool(ok)
    for name, (value, limit, _detail) in numbers.items():
        compared[name] = [value, limit]
        correct = correct and bool(value <= limit)
    return correct, compared


# -- the run ----------------------------------------------------------------------

class Run:
    """State of one benchmark run; ``main`` drives it phase by phase."""

    def __init__(self, workload: str, seed: int, seconds: float,
                 trace: bool, rehearsal: bool, t_start: float):
        self.workload, self.seed = workload, int(seed)
        self.seconds, self.trace = float(seconds), bool(trace)
        self.rehearsal, self.t_start = rehearsal, t_start
        self.__dict__.update(load_cell(workload))
        self.family = importlib.import_module(
            f"benchmark.reference.{self.config['family']}")
        self.hyper = dict(self.config["hyper"])

    def say(self, text: str) -> None:
        print(f"bench[{self.workload} +{time.monotonic() - self.t_start:.1f}s]"
              f" {text}", file=sys.stderr, flush=True)

    # -- set-up -----------------------------------------------------------------

    def start(self) -> None:
        """Compile cache, trace ring, JAX, the platform check."""
        os.makedirs(RUN_DIR, exist_ok=True)
        # every program goes to the persistent cache, the small ones of
        # trainer construction too (JAX's default keeps only those that
        # took a second to compile), so a second run compiles nothing
        os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                              "0")
        if self.trace:
            os.environ["APEX_TRACE_DIR"] = os.path.join(RUN_DIR, "ring")
            os.environ.setdefault("APEX_TRACE_CAPACITY", "2000000")
        else:
            os.environ.pop("APEX_TRACE_DIR", None)
        # a refused donation is a second copy of a 7.5 GB ring: an error
        warnings.filterwarnings(
            "error", message=".*donated buffers were not usable.*")
        from apex_tpu.utils.compile_cache import ensure_compile_cache
        self.cache_dir = ensure_compile_cache()
        import jax

        self.meter = CompileMeter()
        devices = jax.devices()
        self.device = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)}
        chips = self.cell["chips"]
        want = "cpu" if self.rehearsal else "tpu"
        if self.device["platform"] != want or len(devices) < chips:
            raise SystemExit(
                f"bench[{self.workload}]: needs {chips} device(s) of "
                f"platform {want!r}, JAX found {self.device}")
        self.devices = devices[:chips]
        self.say(f"platform={self.device['platform']} "
                 f"kind={self.device['kind']!r} count={self.device['count']}"
                 f" compile_cache={self.cache_dir}"
                 + (" [REHEARSAL on cpu: no number here is a device "
                    "metric]" if self.rehearsal else ""))

    def argv(self) -> list[str]:
        extra = self.config.get("rehearsal_argv", []) if self.rehearsal \
            else []
        return (self.config["argv"] + self.traffic["argv"] + extra
                + ["--seed", str(feed.program_seed(self.seed))])

    def build(self) -> None:
        """The trainer, as a user builds it; then the seed's weights."""
        import jax

        from apex_tpu.runtime.cli import (build_parser, build_trainer,
                                          config_from_args)

        self.args = build_parser().parse_args(self.argv())
        self.cfg = config_from_args(self.args)
        self.trainer, self.train_kw = build_trainer(self.args, self.cfg)
        tr = self.trainer
        self.seed_weights()
        jax.block_until_ready((tr.train_state, tr.replay_state))
        # the window's edges: a one-op program queued behind every step
        # dispatched so far, so its result marks "all of them completed"
        self._fence = jax.jit(lambda x: x + 1)
        self._fence(jax.numpy.int32(0)).block_until_ready()
        self.say("trainer built, the seed's weights in place")

    def seed_weights(self) -> None:
        """The seed's weights in the trainer's place, one learner state at
        a time: the parameters, target and moments that are there are let
        go before their replacements are made (their shapes are all the
        draw needs), so set-up never holds more than the 16 bytes a
        parameter the run itself holds.  ``weights0`` is the host copy the
        reference starts from once the program's state is freed."""
        import jax
        tr = self.trainer
        shapes = jax.eval_shape(lambda p: p, tr.train_state.params)
        tr.train_state = tr.train_state.replace(
            params=None, target_params=None, opt_state=None)
        params = feed.make_weights(shapes, self.seed,
                                   getattr(self.family, "init_rule", None))
        self.weights0 = jax.device_get(params)
        tr.train_state = tr.train_state.replace(
            params=params,
            target_params=jax.tree.map(lambda x: x.copy(), params),
            opt_state=tr.core.optimizer.init(params),
            step=jax.numpy.int32(0))

    def checked_steps(self) -> None:
        """Drive the programs the window drives (the mix's
        ``checked_programs``: ``fused`` = ``trainer._fused``, ingest of one
        chunk + update in one program; ``train`` = ``trainer._train``)
        through the seed's rows and its first three updates, keeping what
        the program made of them (losses, first moment, parameters, the
        leaves it wrote back) and what the reference needs to follow
        (keys, the rows each step could sample, the indices it drew)."""
        import jax
        import jax.numpy as jnp

        tr, chk, cfg = self.trainer, self.config["check"], self.cfg
        rp = tr.replay
        from apex_tpu.replay.frame_chunks import FRAME_MARGIN  # Kf only
        from benchmark.reference.common import (stratified_indices,
                                                tree_leaves, with_leaves)
        programs = self.traffic["checked_programs"]
        k = cfg.actor.send_interval
        messages, self.rows = feed.make_chunks(
            self.seed, n_chunks=chk["chunks"] + programs.count("fused"),
            k=k, kf=k + FRAME_MARGIN,
            frame_dim=rp.frame_dim, stack=rp.frame_stack,
            n_steps=cfg.learner.n_steps, gamma=cfg.learner.gamma,
            action_count=chk["action_count"])
        messages = iter(messages)
        stage = jax.device_put if jax.default_backend() != "cpu" \
            else (lambda x: x)
        for _ in range(chk["chunks"]):
            msg = next(messages)
            tr.replay_state = tr._ingest(tr.replay_state, msg["payload"],
                                         jnp.asarray(msg["priorities"]))
        self.check_frame_spec = (tuple(rp.frame_shape), rp.frame_stack)
        beta = float(cfg.replay.beta)
        batch, cap = tr.core.batch_size, tr.replay.capacity
        alpha, eps = self.hyper["alpha"], self.hyper["replay_eps"]
        lo = self.seed & 0x7FFFFFFF
        self.steps, losses, q_means, written = [], [], [], []
        for i, program in enumerate(programs):
            key = jax.random.fold_in(jax.random.key(lo), 1000 + i)
            k_sample, k_update = self.family.step_keys(key)
            # the tree the step samples from: the program's, read before
            # the step, with (fused) the chunk's leaves as the benchmark
            # works them out, at the rows the ring's cursor says come next
            tree = np.asarray(tr.replay_state.sum_tree)
            size = int(tr.replay_state.size)
            if program == "fused":
                msg = next(messages)
                at = (int(tr.replay_state.pos) + np.arange(k)) % cap
                own = tree_leaves(msg["priorities"], alpha, eps)
                tree = with_leaves(tree, at, own)
                size = min(size + k, cap)
            idx = stratified_indices(tree, k_sample, batch, size)
            if program == "fused":
                # staged as the ingest pipeline stages a slot (on the
                # device, except on the CPU backend)
                tr.train_state, tr.replay_state, m = tr._fused(
                    tr.train_state, tr.replay_state, stage(msg["payload"]),
                    stage(np.asarray(msg["priorities"], np.float32)),
                    key, jnp.float32(beta))
            else:
                tr.train_state, tr.replay_state, m = tr._train(
                    tr.train_state, tr.replay_state, key, jnp.float32(beta))
            losses.append(float(m["loss"]))
            after = np.asarray(tr.replay_state.sum_tree)
            if program == "fused":
                # the device's power function may round a leaf another way
                # than numpy's.  Chunk rows the step did not sample still
                # show what the ingest wrote: where that is the benchmark's
                # own leaf to rounding, sample again from those very bits
                # (a leaf further off stays a row written wrongly)
                got = after[cap + at]
                kept = ~np.isin(at, idx)
                near = kept & (got != own) & (
                    np.abs(got - own) <= 1e-4 * np.abs(own))
                if near.any():
                    tree = with_leaves(tree, at[near], got[near])
                    idx = stratified_indices(tree, k_sample, batch, size)
                off = np.abs(got[kept] - own[kept]) / np.abs(own[kept])
                self.say(f"step {i + 1}: {int(near.sum())} of {k} ingested "
                         f"leaves rounded otherwise than numpy's, the "
                         f"farthest by {off.max():.3g} of its value")
            written.append(np.flatnonzero(after[cap:] != tree[cap:]))
            q_means.append(float(m["q_mean"]))
            self.steps.append(dict(idx=idx, key=k_update, beta=beta,
                                   size=size))
            if i == 0:
                mu = leaf_norms(moment_by_path(tr.train_state.opt_state,
                                               chk["first_moment"]))
                first_grad_norm = {p: n * chk["first_moment_scale"]
                                   for p, n in mu.items()}
        self.program = dict(
            losses=losses, q_means=q_means, written=written,
            first_grad_norm=first_grad_norm,
            dparam_norm=leaf_norms(by_path(tr.train_state.params),
                                   minus=by_path(self.weights0)))
        drop_norm_programs()
        self.say(f"checked steps {programs}: losses {losses}")

    # -- the window -----------------------------------------------------------------

    def counters(self) -> dict:
        tr = self.trainer
        out = dict(steps=tr.steps_rate.total, frames=tr.ingested,
                   compiles=self.meter.events, t=time.monotonic(),
                   wall=time.time())
        pipe = getattr(tr, "_pipeline", None)
        if pipe is not None:
            out["pipeline"] = dict(pipe.stats)
        return out

    def fence(self) -> dict:
        """Counters, then wait for the device to finish all that was
        dispatched before: the time it returns is when that much work was
        complete."""
        import jax.numpy as jnp
        c = self.counters()
        self._fence(jnp.int32(0)).block_until_ready()
        c["t"], c["wall"] = time.monotonic(), time.time()
        return c

    def run_window(self) -> None:
        tr, tf = self.trainer, self.traffic
        errors: list[BaseException] = []

        def target():
            try:
                tr.train(**{**self.train_kw, "total_steps": 2 ** 40,
                            "max_seconds": 3000.0})
            except BaseException as e:      # reported by the main thread
                errors.append(e)

        thread = threading.Thread(target=target, name="trainer", daemon=True)
        thread.start()
        warm = tf["warm"]
        deadline = time.monotonic() + warm["timeout_s"]
        steps0 = None
        while True:
            if errors:
                raise errors[0]
            if not thread.is_alive():
                raise RuntimeError("train() returned before the window")
            now = time.monotonic()
            if now > deadline:
                raise RuntimeError(
                    f"not warm after {warm['timeout_s']} s: steps "
                    f"{tr.steps_rate.total}, ingested {tr.ingested}")
            steps = tr.steps_rate.total
            if steps0 is None and steps > 0:
                steps0 = steps
                self.say(f"train(): first update, {tr.ingested} transitions "
                         f"in, last compile event "
                         f"{now - self.meter.last_event:.1f} s ago")
            # warm: the warm-up is in, min_steps updates ran since the
            # first, and the compile meter has been silent for quiet_s
            if (steps0 is not None and steps - steps0 >= warm["min_steps"]
                    and tr.ingested >= self.cfg.replay.warmup
                    and now - self.meter.last_event >= warm["quiet_s"]):
                break
            time.sleep(0.1)
        self.compile_s_setup = self.meter.seconds
        self.cache_hits, self.cache_misses = (self.meter.hits,
                                              self.meter.misses)
        profile_dir = os.path.join(RUN_DIR, "profile")
        self.open = self.fence()
        self.setup_s = self.open["t"] - self.t_start
        self.say(f"window open after {self.setup_s:.2f} s of set-up "
                 f"(compile {self.compile_s_setup:.2f} s, cache hits "
                 f"{self.cache_hits}, misses {self.cache_misses})")
        self.traced_s = None
        if self.trace:
            import shutil

            import jax
            shutil.rmtree(profile_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            t0 = time.monotonic()
            jax.profiler.start_trace(profile_dir, profiler_options=opts)
            time.sleep(min(tf.get("trace_seconds", 2.0), self.seconds))
            self._fence(jax.numpy.int32(0)).block_until_ready()
            self.traced_s = time.monotonic() - t0
            jax.profiler.stop_trace()
            self.profile_dir = profile_dir
        # one reading of the counters a second: the series goes to stderr,
        # so a run that reads far off shows when in the window it fell behind
        t_close = self.open["t"] + self.seconds
        series = [(self.open["t"], self.open["steps"], self.open["frames"])]
        while not errors:
            remaining = t_close - time.monotonic()
            if remaining <= 0:
                break
            time.sleep(min(1.0, remaining))
            series.append((time.monotonic(), tr.steps_rate.total,
                           tr.ingested))
        if errors:
            raise errors[0]
        self.close = self.fence()
        for name, col in (("updates", 1), ("frames", 2)):
            self.say(f"{name} by second of the window: " + " ".join(
                str(b[col] - a[col]) for a, b in zip(series, series[1:])))
        self.actor_timing = dict(getattr(tr, "actor_timing", None) or {})
        ring = None
        if self.trace:
            from apex_tpu.obs.trace import get_ring
            ring = get_ring().to_chrome()
        self.ring = ring
        tr.request_stop()
        thread.join(timeout=120)
        if thread.is_alive():
            raise RuntimeError("train() did not stop within 120 s")
        if errors:
            raise errors[0]

    def liveness(self) -> dict:
        """What ``chip_smoke.py`` checks of a healthy run."""
        tr = self.trainer
        o, c = self.open, self.close
        checks = {
            "no_compile_in_window": c["compiles"] == o["compiles"],
            "steps_in_window": c["steps"] > o["steps"],
            "frames_in_window": c["frames"] > o["frames"],
        }
        # updates of the window whose logged loss is not finite
        loss_log = tr.log.history.get("learner/loss") or []
        bad = [v for at, v in loss_log
               if o["steps"] < at <= c["steps"] and not math.isfinite(v)]
        checks["logged_losses_finite"] = not bad
        self.failed_updates = len(bad)
        # the window drove the very programs the checked steps drove: one
        # compiled program each, not a second one for other operand types
        sizes = {"_" + program: getattr(tr, "_" + program)._cache_size()
                 for program in dict.fromkeys(
                     self.traffic["checked_programs"])}
        self.say(f"programs held by the jitted steps: {sizes}")
        checks["one_program_each"] = set(sizes.values()) == {1}
        pool = getattr(tr.pool, "pool", tr.pool)
        if hasattr(pool, "worker_deaths"):
            from apex_tpu.native.ring import ShmChunkQueue
            fm = tr.fleet.metrics()
            checks["no_worker_deaths"] = pool.worker_deaths == 0
            checks["registry_saw_no_death"] = (fm["deaths"] == 0
                                               and fm["dead"] == 0)
            checks["shm_chunk_plane"] = isinstance(pool.chunk_queue,
                                                   ShmChunkQueue)
        return checks

    def memory_peak(self) -> int:
        peak = 0
        for d in self.devices:
            m = d.memory_stats() or {}
            peak = max(peak, int(m.get("peak_bytes_in_use", 0)))
        return peak

    # -- the reference --------------------------------------------------------------

    def free_program(self) -> None:
        self.trainer = None
        gc.collect()

    def reset_state(self, seed: int) -> None:
        """A fresh replay and the weights of another seed in the trainer
        that is there (the readings read many seeds in one process)."""
        tr = self.trainer
        self.seed = int(seed)
        tr.replay_state = None
        gc.collect()
        tr.replay_state = tr.replay.init()
        self.seed_weights()

    def reference(self, mode: str = "f32", fault: str | None = None) -> dict:
        """The plain reference over the same three steps: its own weights
        copy, its own optimizer state and priority chain; the rows and
        keys of the seed; the indices each step sampled."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference.common import ReplayView, is_weights

        fam, hp = self.family, self.hyper
        alpha, eps = hp["alpha"], hp["replay_eps"]
        view = ReplayView(self.rows, *self.check_frame_spec)
        leaves = np.maximum(self.rows["priority"].astype(np.float64),
                            eps) ** alpha
        params = jax.tree.map(jnp.asarray, self.weights0)
        # target buffers of their own and a state that is never read once
        # it is handed over: a family's step may donate what it is given
        state = dict(params=params,
                     target_params=jax.tree.map(jnp.copy, params),
                     opt=fam.init_opt(params, hp), step=0)
        del params
        losses, q_means, q_abs, written = [], [], [], []
        frozen = fault == "frozen"          # a step that returns its state
        with jax.default_matmul_precision("highest"):
            for i, st in enumerate(self.steps):
                idx = st["idx"]
                if fault == "half_batch":   # half left out, mean of the rest
                    idx = idx[:len(idx) // 2]
                batch = {k: jnp.asarray(v)
                         for k, v in view.batch(idx).items()}
                w = jnp.asarray(is_weights(leaves, st["size"], idx,
                                           st["beta"]))
                if frozen:                  # the state stays: a copy to eat
                    given = jax.tree.map(
                        lambda x: jnp.copy(x) if hasattr(x, "shape") else x,
                        state)
                else:
                    given, state = state, None
                new, out = fam.step(given, batch, w, st["key"], hp, mode)
                state = state if frozen else new
                del given, new
                losses.append(float(out["loss"]))
                q_means.append(float(out["q_mean"]))
                q_abs.append(float(out["q_abs"]))
                if i == 0:
                    first_grad_norm = leaf_norms(by_path(out["grads"]))
                pr = np.asarray(out["priorities"], np.float64)
                del out                     # the gradient goes with it
                leaves[idx] = np.maximum(pr, eps) ** alpha
                written.append(np.unique(idx))
        dparam_norm = leaf_norms(by_path(state["params"]),
                                 minus=by_path(self.weights0))
        drop_norm_programs()
        return dict(losses=losses, q_means=q_means, q_abs=q_abs,
                    written=written, first_grad_norm=first_grad_norm,
                    dparam_norm=dparam_norm)

    def judge(self, side: dict | None = None, ref: dict | None = None) -> dict:
        """The numbers compared, each beside its limit: ``side`` (the
        program's readings unless a control's are given) against the
        float32 reference."""
        ref = ref if ref is not None else self.reference("f32")
        return compare(side if side is not None else self.program, ref,
                       self.config["check"]["limits"])

    # -- per-layer metrics -------------------------------------------------------------

    def layer_context(self) -> dict:
        from benchmark import costs, xplane
        trace = None
        if self.trace:
            path = xplane.find_xplane(self.profile_dir)
            if path is not None:
                trace = xplane.reduce_file(path, chips=self.cell["chips"])
        peaks = None if self.rehearsal else costs.peaks_for(
            self.device["kind"])
        o, c = self.open, self.close
        gaps = []
        if self.ring is not None:
            lo, hi = o["wall"] * 1e6, c["wall"] * 1e6
            gaps = [ev["dur"] / 1e6 for ev in self.ring["traceEvents"]
                    if ev.get("name") == "host_gap" and ev.get("ph") == "X"
                    and lo <= ev["ts"] <= hi]
        return dict(
            window_s=c["t"] - o["t"], open=o, close=c, trace=trace,
            traced_s=self.traced_s, peaks=peaks, chips=self.cell["chips"],
            config=self.config, traffic=self.traffic, host_gaps_s=gaps,
            compile_s_setup=self.compile_s_setup,
            cache=(self.cache_hits, self.cache_misses),
            actor_timing=self.actor_timing, say=self.say)

    def read_layers(self, ctx: dict) -> dict:
        out = {}
        for m in metrics_for(self.bench, self.workload, "per_layer"):
            reader = load_reader(m["name"])
            value = reader.read(ctx)
            if value is None:
                self.say(f"per-layer {m['name']}: nothing to read")
                continue
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out


def main(workload: str, seed: int, seconds: float, trace: bool,
         rehearsal: bool, t_start: float) -> int:
    run = Run(workload, seed, seconds, trace, rehearsal, t_start)
    run.start()
    run.build()
    run.checked_steps()
    run.run_window()
    peak = run.memory_peak()
    checks = run.liveness()
    o, c = run.open, run.close
    window = c["t"] - o["t"]
    steps, frames = c["steps"] - o["steps"], c["frames"] - o["frames"]
    e2e = {"learner_steps_per_s": steps / window,
           "env_frames_per_s": frames / window,
           "peak_hbm_gb": peak / 1e9,
           "setup_s": run.setup_s}
    ctx = run.layer_context() if trace else None
    layers = run.read_layers(ctx) if trace else None
    failed = run.failed_updates
    run.free_program()
    t_ref = time.monotonic()
    numbers = run.judge()
    ref_s = time.monotonic() - t_ref

    device = dict(run.device, memory_peak_bytes=peak)
    correct, compared = verdict(checks, numbers)
    result = {"correct": correct, "attempted": steps, "failed": failed}
    if trace:
        tr = ctx["trace"]
        if tr is None and not rehearsal:
            raise SystemExit("traced run: no operation ran on the device")
        if tr is not None:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = run.traced_s
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in tr["top_ops"]],
                "idle_gaps": [[n, s] for n, s in tr["idle_gaps"]]}
        result["metrics"] = layers
    else:
        names = {m["name"]: m["unit"] for m in
                 metrics_for(run.bench, workload, "end_to_end")}
        result["metrics"] = {n: {"value": e2e[n], "unit": u}
                             for n, u in names.items()}
    result["device"] = device
    # the contract's own key: every number compared beside its limit, last
    result["compared"] = compared
    run.say(f"window {window:.3f} s: {steps} updates, {frames} frames; "
            f"reference took {ref_s:.2f} s")
    for name, ok in checks.items():
        run.say(f"check {name}: {'ok' if ok else 'FAILED'}")
    for name, (value, limit, detail) in numbers.items():
        run.say(f"compared {name} = {value:.6g} (limit {limit}) at {detail}"
                + ("" if value <= limit else "  <-- over the limit"))
    print(json.dumps(result), flush=True)
    return 0
