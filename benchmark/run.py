#!/usr/bin/env python3
"""The benchmark's command: one cell, one run, one result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --list        # what BENCHMARK.json names, found or missing (no JAX)

Refuses any platform but ``tpu`` (exit 1, no result line).  ``--rehearsal``
is the CPU rehearsal at the configuration's ``rehearsal_argv`` sizes: its
result line says platform ``cpu``, and nothing in it is a device metric.
"""

from __future__ import annotations

import time

T_START = time.monotonic()          # set-up counts from here

import argparse                     # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def list_cells() -> int:
    """Every configuration, cell and metric ``BENCHMARK.json`` names, with
    the file each is found in; exit 1 if one is missing."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    missing = []

    def find(kind: str, name: str, path: str) -> None:
        ok = os.path.isfile(os.path.join(ROOT, path))
        print(f"{kind:<12} {name:<28} {path}" + ("" if ok else "  MISSING"))
        if not ok:
            missing.append(path)

    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        find("config", c["name"], c["file"])
        family = None
        if os.path.isfile(os.path.join(ROOT, c["file"])):
            with open(os.path.join(ROOT, c["file"])) as f:
                family = json.load(f)["family"]
        if family:
            find("reference", family, f"benchmark/reference/{family}.py")
            find("costs", family, f"benchmark/costs_{family}.py")
    for w in bench["workloads"]:
        if w["config"] not in configs:
            missing.append(w["config"])
            print(f"cell         {w['name']:<28} config {w['config']} "
                  f"not in BENCHMARK.json  MISSING")
        find("cell", w["name"], f"benchmark/traffic/{w['traffic']}.json")
    for m in bench["per_layer"]:
        find("per_layer", m["name"], f"benchmark/metrics/{m['name']}.py")
    for m in bench["end_to_end"]:
        print(f"{'end_to_end':<12} {m['name']:<28} bound {m['bound']}")
    if missing:
        print(f"{len(missing)} file(s) missing", file=sys.stderr)
        return 1
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args()
    if a.list:
        return list_cells()
    if not a.workload:
        ap.error("--workload is required")
    if not os.path.isdir(os.path.join(ROOT, "apex_tpu")):
        print("benchmark/run.py: the program (apex_tpu/) is not in this "
              "checkout", file=sys.stderr)
        return 1
    sys.path.insert(0, ROOT)
    if a.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark import harness
    seconds = a.seconds
    if seconds is None:
        seconds = harness.load_json(ROOT, "BENCHMARK.json")["run_seconds"]
    return harness.main(a.workload, a.seed, seconds, bool(a.trace),
                        a.rehearsal, T_START)


if __name__ == "__main__":
    sys.exit(main())
