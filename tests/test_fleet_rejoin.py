"""The chaos rejoin proof (ISSUE 5 acceptance): a seeded fault schedule
kills one actor and then the learner mid-run; the actor's host supervisor
respawns it, the restarted learner resumes from its newest checkpoint, and
every surviving role reattaches through the park/rejoin path — no operator
action anywhere.

Everything runs as real ``python -m apex_tpu.runtime`` subprocesses over
TCP, exactly the deploy topology: learner + 2 actors (actor-0 under
``python -m apex_tpu.fleet.supervise``) + 1 evaluator.  The learner's
periodic ``fleet_summary.json`` dumps are the observability spine: the
SIGKILLed phase-1 learner's last dump proves its registry saw actor-0 die
and rejoin (DEAD -> ALIVE), and the phase-2 learner's final dump proves
the whole fleet reattached with ``fleet_rejoins >= 2`` and the run
reaching its step target.

Only in-host worker death was covered before (tests/test_failure.py);
this is the cross-host story.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parent.parent

# the seeded schedule: actor-0 dies at its 5th chunk send (early, so its
# DEAD -> ALIVE rejoin is on the books well before the learner dies at
# its 150th param publish, ~30-60s in — checkpoints land every 20 steps
# long before that)
CHAOS_SEED = "7"
CHAOS_SPEC = '{"kill": {"actor-0": 5, "learner": 150}}'


def _free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _wait(cond, timeout, what, also_check=None):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        if also_check is not None:
            also_check()
        time.sleep(0.5)
    pytest.fail(f"timed out waiting for {what}")


def _summary(logdir: Path) -> dict | None:
    path = logdir / "fleet_summary.json"
    if not path.exists():
        return None
    try:
        return json.loads(path.read_text())
    except (OSError, json.JSONDecodeError):
        return None          # mid-replace read; the dump is atomic, retry


def test_chaos_kills_actor_and_learner_fleet_rejoins(tmp_path):
    batch, param, barrier, status = _free_ports(4)
    ckpt = tmp_path / "ckpt"
    log1, log2 = tmp_path / "log1", tmp_path / "log2"
    for d in (ckpt, log1, log2):
        d.mkdir()

    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        APEX_BATCH_PORT=str(batch), APEX_PARAM_PORT=str(param),
        APEX_BARRIER_PORT=str(barrier), APEX_STATUS_PORT=str(status),
        # snappy control plane so the drama fits a CI soak
        APEX_HEARTBEAT_INTERVAL="0.5", APEX_SUSPECT_AFTER="2",
        APEX_DEAD_AFTER="4", APEX_PARK_AFTER="5",
        CHAOS_SEED=CHAOS_SEED, CHAOS_SPEC=CHAOS_SPEC,
    )
    common = ["--env-id", "ApexCartPole-v0", "--frame-stack", "1",
              "--no-clip-rewards", "--no-episodic-life",
              "--n-actors", "2", "--n-evaluators", "1",
              "--warmup", "128", "--capacity", "2048",
              "--batch-size", "32", "--barrier-timeout", "180"]

    def runtime(*extra):
        return [sys.executable, "-m", "apex_tpu.runtime",
                *common, *extra]

    def learner_cmd(logdir, *extra):
        return runtime("--role", "learner", "--save-interval", "20",
                       "--train-ratio", "8", "--max-seconds", "600",
                       "--checkpoint-dir", str(ckpt),
                       "--logdir", str(logdir), *extra)

    procs: list[subprocess.Popen] = []

    def spawn(cmd, **kw):
        p = subprocess.Popen(cmd, env=dict(env, **kw.pop("extra_env", {})),
                             cwd=REPO, **kw)
        procs.append(p)
        return p

    learner = spawn(learner_cmd(log1, "--total-steps", "1000000"))
    # actor-0 under the real host supervisor: the chaos kill at chunk 5
    # exercises respawn + barrier-less rejoin; APEX_RESPAWN_COUNT from the
    # supervisor disarms the kill on the second life
    spawn([sys.executable, "-m", "apex_tpu.fleet.supervise",
           "--max-respawns", "5", "--window", "600",
           "--min-uptime", "0.5", "--backoff", "0.5",
           "--backoff-max", "1", "--",
           *runtime("--role", "actor", "--actor-id", "0")])
    spawn(runtime("--role", "actor", "--actor-id", "1"))
    spawn(runtime("--role", "evaluator", "--episodes", "0"))

    def learner_must_live():
        if learner.poll() is not None and learner.returncode != 137:
            pytest.fail(f"phase-1 learner died unexpectedly "
                        f"rc={learner.returncode}")

    try:
        # phase 1: fleet up, actor-0 chaos-killed + respawned -> the
        # learner's registry must record the DEAD -> ALIVE rejoin in its
        # periodic on-disk dump (which survives the learner's own death)
        _wait(lambda: (_summary(log1) or {}).get("metrics", {})
              .get("dead_to_alive", 0) >= 1,
              240, "phase-1 registry DEAD->ALIVE for chaos-killed actor-0",
              also_check=learner_must_live)

        # phase 2: the seeded schedule kills the learner at publish 150
        _wait(lambda: learner.poll() is not None, 240,
              "chaos learner kill (publish 150)")
        assert learner.returncode == 137, learner.returncode
        s1 = _summary(log1)
        assert s1 is not None and s1["metrics"]["dead_to_alive"] >= 1
        assert any(c.name.startswith("ckpt_")
                   or c.suffix for c in ckpt.iterdir()), \
            "no checkpoint on disk before the learner died"

        # restart from the newest checkpoint: 200 MORE steps, then a
        # clean exit.  The parked fleet (actor-1, evaluator, respawned
        # actor-0) must reattach on its own via the barrier/param race.
        learner2 = spawn(learner_cmd(log2, "--total-steps", "200",
                                     "--restore"),
                         extra_env={"APEX_RESPAWN_COUNT": "1"})
        _wait(lambda: learner2.poll() is not None, 420,
              "restarted learner completing its step target")
        assert learner2.returncode == 0, learner2.returncode

        s2 = _summary(log2)
        assert s2 is not None, "restarted learner wrote no fleet summary"
        m = s2["metrics"]
        # every surviving role reattached without operator action …
        assert m["peers"] >= 3, s2
        assert m["alive"] >= 2, s2
        # … and the fleet's self-reported park->resume cycles survive the
        # registry restart: at least actor-1 and the evaluator each
        # parked during the learner outage and rejoined
        assert m["rejoins"] >= 2, s2
        # the run resumed from the checkpoint and reached its target
        assert s2["steps"] >= 200, s2
        assert s2["steps"] >= s1["steps"], (s1["steps"], s2["steps"])
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + 15
        for p in procs:
            if p.poll() is None and time.monotonic() < deadline:
                try:
                    p.wait(timeout=max(0.1,
                                       deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    pass
        for p in procs:
            if p.poll() is None:
                p.kill()
