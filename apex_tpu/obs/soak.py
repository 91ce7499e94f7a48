"""Standing saturation soak: the load study ROADMAP has owed since PR 10.

Drives a loadgen-saturated localhost fleet through ``scripts/
run_local.sh`` for a configurable WALL budget (not a step target — the
learner's step count is an outcome, not an input), samples the fleet SLO
engine (:mod:`apex_tpu.obs.slo`) off the learner's status port every
tick, and emits one machine-readable ``SOAK_*.json``: SLO compliance %
per objective, the alert timeline, and throughput vs offered load.

The topology is whatever ``run_local.sh`` env twins say — the soak adds
``APEX_LOADGEN=N`` (on-device traffic sources saturating the chunk
plane) and a huge step target so only the wall budget ends the run.
Chaos composes for free: export ``CHAOS_SEED``/``CHAOS_SPEC`` before
launching and the soak records how the SLO engine rode the fault out —
the CI ``slo-smoke`` drill is exactly that (a seeded kill of the
supervised infer server, asserted BURNING -> BREACHED -> RESOLVED from
the artifact this module writes).

Teardown is SIGINT-first to the whole process group: the learner's
train() finally then dumps the final ``fleet_summary.json`` (with the
engine's timeline) that the artifact folds in — a SIGKILL would cost the
last few ticks of evidence.

Usage::

    python -m apex_tpu.obs.soak --seconds 600 --env-id ApexCatchSmall-v0 \
        --actors 2 --envs-per-actor 2 --loadgen 1 --out SOAK_local.json
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import subprocess
import time


def _repo_root() -> str:
    return os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", ".."))


# -- sampling ----------------------------------------------------------------


def sample_status(status_port: int, learner_ip: str = "127.0.0.1",
                  timeout_s: float = 2.0) -> dict | None:
    """One status round-trip to the learner (the trainer's full fleet
    summary, ``slo`` section included), or None while nothing answers
    (pre-barrier, post-teardown)."""
    from apex_tpu.config import CommsConfig
    from apex_tpu.fleet.registry import status_request

    comms = dataclasses.replace(CommsConfig(), status_port=status_port)
    try:
        return status_request(comms, learner_ip=learner_ip,
                              timeout_s=timeout_s)
    except Exception:
        return None


def offered_frames(summary: dict) -> int:
    """Offered load: frames the loadgen plane has SEALED device-side
    (its heartbeat gauges), independent of what the learner accepted —
    the offered-vs-ingested gap is the saturation headroom the soak
    measures."""
    total = 0
    for p in summary.get("peers") or []:
        if p.get("role") == "loadgen":
            v = (p.get("gauges") or {}).get("ondevice_frames")
            if isinstance(v, (int, float)):
                total += int(v)
    return total


def make_sample(summary: dict, t_s: float) -> dict:
    """One tick's record in the artifact's ``samples`` array."""
    slo = summary.get("slo") or {}
    return {
        "t_s": round(t_s, 2),
        "steps": summary.get("steps"),
        "ingested": summary.get("ingested"),
        "offered_frames": offered_frames(summary),
        "rates": summary.get("rates") or {},
        "severity": slo.get("severity"),
        "states": {o["name"]: o["state"]
                   for o in slo.get("objectives", [])},
        "alive": (summary.get("metrics") or {}).get("alive"),
        "dead": (summary.get("metrics") or {}).get("dead"),
    }


# -- the artifact ------------------------------------------------------------


def build_artifact(meta: dict, samples: list[dict],
                   final_summary: dict | None) -> dict:
    """The SOAK_*.json body.  Pure — the schema pin in tests/test_slo.py
    drives this directly, no subprocess."""
    final_summary = final_summary or {}
    slo = final_summary.get("slo") or {}
    objectives = slo.get("objectives", [])
    compliance = {o["name"]: o["compliance_pct"] for o in objectives
                  if o.get("compliance_pct") is not None}
    breaches = {o["name"]: o["breaches"] for o in objectives
                if o.get("breaches")}
    steps = final_summary.get("steps") or 0
    ingested = final_summary.get("ingested") or 0
    offered = (samples[-1]["offered_frames"] if samples
               else offered_frames(final_summary))
    span = samples[-1]["t_s"] - samples[0]["t_s"] if len(samples) > 1 \
        else 0.0
    d_steps = (samples[-1]["steps"] or 0) - (samples[0]["steps"] or 0) \
        if len(samples) > 1 else 0
    d_ing = ((samples[-1]["ingested"] or 0)
             - (samples[0]["ingested"] or 0)) if len(samples) > 1 else 0
    d_off = (samples[-1]["offered_frames"]
             - samples[0]["offered_frames"]) if len(samples) > 1 else 0
    return {
        "kind": "apex_soak",
        "version": 1,
        "meta": meta,
        "samples": samples,
        "slo": {
            "compliance": compliance,
            "breaches": breaches,
            "timeline": slo.get("timeline", []),
            "severity_final": slo.get("severity"),
            "objectives": objectives,
        },
        "throughput": {
            "steps_final": steps,
            "ingested_final": ingested,
            "offered_frames_final": offered,
            "steps_per_s": round(d_steps / span, 3) if span > 0 else None,
            "ingest_per_s": round(d_ing / span, 3) if span > 0 else None,
            "offered_per_s": round(d_off / span, 3) if span > 0 else None,
            # loadgen-offered vs fleet-ingested over the sampled span:
            # the share of accepted traffic the device-rate plane
            # supplied (> 1 = loadgen alone outran the learner and the
            # credit windows held the excess back; host-actor chunks in
            # the denominator pull it under 1 on mixed topologies)
            "saturation": (round(d_off / d_ing, 3)
                           if d_ing > 0 and d_off > 0 else None),
        },
    }


# -- the drive ---------------------------------------------------------------


def _stop_group(proc: subprocess.Popen) -> None:
    """SIGINT first (learner finally -> final summary dump), escalate to
    SIGTERM/SIGKILL only for stragglers."""
    for sig, wait_s in ((signal.SIGINT, 25.0), (signal.SIGTERM, 10.0),
                        (signal.SIGKILL, 5.0)):
        try:
            os.killpg(proc.pid, sig)
        except (ProcessLookupError, PermissionError):
            return
        try:
            proc.wait(timeout=wait_s)
            return
        except subprocess.TimeoutExpired:
            continue


def run_soak(args: argparse.Namespace) -> dict:
    root = _repo_root()
    trace_dir = os.environ.get(
        "APEX_TRACE_DIR", os.path.join("/tmp", f"apex-soak-{os.getpid()}"))
    os.makedirs(trace_dir, exist_ok=True)
    env = dict(os.environ,
               APEX_TRACE_DIR=trace_dir,
               APEX_LOADGEN=str(args.loadgen))
    meta = {
        "env_id": args.env_id, "actors": args.actors,
        "envs_per_actor": args.envs_per_actor, "loadgen": args.loadgen,
        "budget_s": args.seconds, "tick_s": args.tick,
        "started_unix": round(time.time(), 1),
        "chaos_seed": os.environ.get("CHAOS_SEED") or None,
        "chaos_spec": os.environ.get("CHAOS_SPEC") or None,
        "remote_policy": os.environ.get("APEX_REMOTE_POLICY") or None,
    }
    cmd = ["bash", os.path.join(root, "scripts", "run_local.sh"),
           args.env_id, str(args.actors), str(args.steps),
           str(args.envs_per_actor)]
    print(f"soak: {args.seconds:.0f}s budget, topology "
          f"{args.actors} actors x {args.envs_per_actor} envs + "
          f"{args.loadgen} loadgen on {args.env_id} "
          f"(trace dir {trace_dir})", flush=True)
    proc = subprocess.Popen(cmd, env=env, start_new_session=True)
    t0 = time.monotonic()
    deadline = t0 + args.seconds
    samples: list[dict] = []
    try:
        while time.monotonic() < deadline and proc.poll() is None:
            time.sleep(args.tick)
            got = sample_status(args.status_port)
            if got is None:
                continue
            s = make_sample(got, time.monotonic() - t0)
            samples.append(s)
            if args.verbose:
                print(f"soak t={s['t_s']:7.1f}s steps={s['steps']} "
                      f"offered={s['offered_frames']} "
                      f"severity={s['severity']}", flush=True)
    finally:
        if proc.poll() is None:
            _stop_group(proc)
    final = None
    summary_path = os.path.join(trace_dir, "fleet_summary.json")
    try:
        with open(summary_path, "r", encoding="utf-8") as fh:
            final = json.load(fh)
    except (OSError, ValueError):
        pass                         # a dead-on-arrival fleet still
    #                                  yields the sampled half
    artifact = build_artifact(meta, samples, final)
    out = args.out or f"SOAK_{args.env_id}_{int(meta['started_unix'])}.json"
    tmp = out + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(artifact, fh, indent=2)
    os.replace(tmp, out)
    comp = artifact["slo"]["compliance"]
    print(f"soak: wrote {out} — {len(samples)} samples, "
          f"steps={artifact['throughput']['steps_final']}, "
          f"saturation={artifact['throughput']['saturation']}, "
          f"compliance={ {k: comp[k] for k in sorted(comp)} }",
          flush=True)
    return artifact


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m apex_tpu.obs.soak",
        description="loadgen saturation soak with SLO sampling "
                    "(emits SOAK_*.json)")
    p.add_argument("--seconds", type=float, default=600.0,
                   help="wall budget (default 600)")
    p.add_argument("--env-id", default="ApexCatchSmall-v0",
                   help="jittable env when --loadgen > 0 (the loadgen "
                        "role fails loud otherwise)")
    p.add_argument("--actors", type=int, default=2)
    p.add_argument("--envs-per-actor", type=int, default=2)
    p.add_argument("--loadgen", type=int, default=1,
                   help="standalone on-device traffic sources "
                        "(APEX_LOADGEN twin; 0 = host actors only)")
    p.add_argument("--steps", type=int, default=10_000_000,
                   help="learner step TARGET handed to run_local.sh — "
                        "deliberately unreachable so the wall budget "
                        "ends the run")
    p.add_argument("--tick", type=float, default=2.0,
                   help="status sampling period, s")
    p.add_argument("--status-port", type=int, default=52003)
    p.add_argument("--out", default=None,
                   help="artifact path (default SOAK_<env>_<ts>.json)")
    p.add_argument("--verbose", action="store_true")
    args = p.parse_args(argv)
    artifact = run_soak(args)
    # a soak that never got one sample is a failed soak, loudly
    return 0 if artifact["samples"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
