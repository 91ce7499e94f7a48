#!/usr/bin/env python3
"""Many seeds' readings of a cell whose learner state fills most of the
chip, in one process (``benchmark/tests/readings_big.py`` reads one seed a
process; this is how PR 35's ten seeds of call 6 were read in twenty
chip-minutes):

    chiprun --timeout 1500 -- python3 scripts/readings_many.py \
        --workload qnextq_ondevice --seeds 3500002003,3500002111,...

First every seed's checked steps through ONE trainer (only a leaf's norms
come to the host), then the trainer goes (``readings_big.let_the_device_go``)
and the float32 reference follows each seed's steps, the seed's weights
drawn again; the frozen fault on the second seed; then, as far as
``--extras-until`` allows, the reference with its operands rounded to
bfloat16 (what the configuration states) on each seed, those that read
highest first.  One JSON line a reading on stdout and in ``--out``;
``--rehearsal`` runs it on the CPU at the toy size.
"""
import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NAMES = ("writeback_miss", "loss_gap", "grad_median_gap", "dparam_median_gap",
         "dparam_gap", "grad_gap")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--program-until", type=float, default=700.0)
    ap.add_argument("--refs-until", type=float, default=1050.0)
    ap.add_argument("--extras-until", type=float, default=1200.0)
    ap.add_argument("--out", default="chiprun_out/tail.jsonl")
    a = ap.parse_args()
    if a.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    t0 = time.monotonic()
    import jax

    from benchmark import feed, harness
    spec = importlib.util.spec_from_file_location(
        "readings_big", os.path.join(ROOT, "benchmark", "tests",
                                     "readings_big.py"))
    rb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rb)

    seeds = [int(s) for s in a.seeds.split(",")]
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    out = open(a.out, "a")

    def emit(row):
        row["t"] = round(time.monotonic() - t0, 1)
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    run = harness.Run(a.workload, seeds[0], 0.0, False, a.rehearsal, t0)
    run.start()
    run.build()
    limits = run.config["check"]["limits"]
    kept = []
    for i, seed in enumerate(seeds):
        if i and time.monotonic() - t0 > a.program_until:
            break
        if i:
            run.reset_state(seed)
        run.checked_steps()
        kept.append(dict(seed=seed, program=run.program, steps=run.steps,
                         rows=run.rows))
    shapes = jax.eval_shape(lambda p: p, run.trainer.train_state.params)
    rule = getattr(run.family, "init_rule", None)
    rb.let_the_device_go(run)

    def load(k):
        run.seed, run.steps, run.rows = k["seed"], k["steps"], k["rows"]
        params = feed.make_weights(shapes, k["seed"], rule)
        run.weights0 = jax.device_get(params)
        del params

    def read(side, k, got):
        nums = harness.readings(got, k["ref"])
        correct = harness.verdict({}, harness.compare(got, k["ref"],
                                                      limits))[0]
        emit(dict(seed=k["seed"], side=side, correct=correct,
                  **{n: nums[n][0] for n in NAMES},
                  where={n: nums[n][1] for n in ("dparam_gap", "grad_gap",
                                                 "loss_gap")}))

    done = []
    for i, k in enumerate(kept):
        if i and time.monotonic() - t0 > a.refs_until:
            break
        load(k)
        k["ref"] = run.reference("f32")
        read("program", k, k["program"])
        done.append(k)
        if i == 1:      # the frozen fault on a second seed: the same program
            read("fault_frozen", k, rb.reference_side(run, "fault_frozen"))
    # what the configuration states, on the seeds that read highest
    done.sort(key=lambda k: -harness.readings(k["program"],
                                              k["ref"])["dparam_gap"][0])
    for k in done:
        if time.monotonic() - t0 > a.extras_until:
            break
        jax.clear_caches()
        load(k)
        read("as_stated_bf16", k, run.reference("bf16"))
    emit(dict(summary=True, seeds=[k["seed"] for k in done],
              limits=limits, device=run.device))
    return 0


if __name__ == "__main__":
    sys.exit(main())
