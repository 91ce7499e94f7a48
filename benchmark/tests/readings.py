#!/usr/bin/env python3
"""Readings the limits are set from, on the chip, in one process per cell:

    python3 benchmark/tests/readings.py --workload <cell> --seeds 12 [--controls 3] [--sides a,b]

For each seed: the programs the window drives through the checked steps
(the lower readings), and for the first ``--controls`` seeds the
reference put in the program's place (``--sides``): in fp8 (the control),
in bf16 (what the configuration states, for information), with half of
the batch left out and with its state frozen (the faults a training cell
can have).  Every side but the frozen one compiles a reference program of
its own.  One JSON line per reading on stdout, a summary at the end.  No
window.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


#: side -> (arithmetic, fault) of the reference put in the program's place
SIDES = {"control_fp8": ("fp8", None), "as_stated_bf16": ("bf16", None),
         "fault_half_batch": ("f32", "half_batch"),
         "fault_frozen": ("f32", "frozen")}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_123)
    ap.add_argument("--sides", default="control_fp8,as_stated_bf16,"
                    "fault_half_batch,fault_frozen")
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args()
    if a.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark import harness

    run = harness.Run(a.workload, a.first_seed, 0.0, False, a.rehearsal,
                      time.monotonic())
    run.start()
    run.build()
    rows = []
    for i in range(a.seeds):
        seed = a.first_seed + 7919 * i
        run.reset_state(seed)
        run.checked_steps()
        ref = run.reference("f32")
        sides = {"program": run.program}
        if i < a.controls:
            for side in a.sides.split(","):
                sides[side] = run.reference(*SIDES[side])
        for side, got in sides.items():
            nums = harness.readings(got, ref)
            row = dict(workload=a.workload, seed=seed, side=side,
                       **{k: v[0] for k, v in nums.items()},
                       where={k: v[1] for k, v in nums.items()})
            rows.append(row)
            print(json.dumps(row), flush=True)
    summary = {}
    for side in sorted({r["side"] for r in rows}):
        for name in ("writeback_miss", "loss_gap", "loss1_gap", "q_gap", "q1_gap", "grad_gap",
                     "grad_median_gap", "dparam_gap", "dparam_median_gap"):
            vals = [r[name] for r in rows if r["side"] == side]
            summary[f"{side}.{name}"] = dict(min=min(vals), max=max(vals),
                                             n=len(vals))
    print("READINGS " + json.dumps(dict(workload=a.workload,
                                        device=run.device, **summary)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
