"""bench.py failure plumbing — a stage that raises, a missed watchdog
deadline and a silently-CPU backend all end the bench non-zero with the
partial JSON printed — and the e2e budget math, plus a slow part-1d smoke
that runs the real actor-plane A/B at toy scale."""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

BENCH_PATH = Path(__file__).resolve().parent.parent / "bench.py"


def _load_bench():
    """Fresh module instance per test (bench keeps mutable module state:
    RESULT, stage dict)."""
    spec = importlib.util.spec_from_file_location("_bench_under_test",
                                                  BENCH_PATH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_bench_under_test"] = mod
    spec.loader.exec_module(mod)
    return mod


# -- no fallback, no exit-0 on failure ---------------------------------------

def _capture_exit(monkeypatch, bench):
    """bench ends through os._exit (wedged children must not hold it);
    turn that into a SystemExit the test can catch."""
    def fake_exit(code):
        raise SystemExit(code)
    monkeypatch.setattr(bench.os, "_exit", fake_exit)


def test_stage_that_raises_exits_nonzero_with_partial_json(
        monkeypatch, capsys, tmp_path):
    """A stage exception must not be caught and carried on: run() prints
    what was recorded so far as the final JSON line and exits non-zero."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    bench = _load_bench()
    _capture_exit(monkeypatch, bench)

    def boom():
        raise RuntimeError("stage blew up")

    monkeypatch.setattr(bench, "bench_fused_step", boom)
    try:
        with pytest.raises(SystemExit) as exc:
            bench.run()
    finally:
        bench._done.set()           # park the watchdog thread main() started
    assert exc.value.code == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["error"] == "RuntimeError: stage blew up"
    assert out["platform"] == "cpu" and out["device_count"] >= 1
    assert out["value"] is None
    assert list(out)[-1] == "claim" and out["claim"] is None


def test_watchdog_deadline_exits_nonzero(monkeypatch, capsys):
    bench = _load_bench()
    _capture_exit(monkeypatch, bench)
    bench._arm("stuck_stage", -1.0)             # already past its deadline
    with pytest.raises(SystemExit) as exc:
        bench._watchdog()
    assert exc.value.code == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "stuck_stage" in out["error"]


def test_init_backend_refuses_a_silent_cpu(monkeypatch):
    """No TPU and no explicit JAX_PLATFORMS=cpu from the operator: the
    bench fails instead of measuring the wrong device."""
    bench = _load_bench()
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        bench.init_backend()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # the CI toy lane
    assert bench.init_backend()["platform"] == "cpu"


# -- e2e budget math --------------------------------------------------------

def test_e2e_budgets_leave_compile_margin(monkeypatch):
    monkeypatch.delenv("BENCH_E2E_SECONDS", raising=False)
    bench = _load_bench()
    for platform in ("tpu", "cpu"):
        soak, train_s, stage_s = bench.e2e_budgets(platform)
        assert soak == bench._e2e_seconds(platform)
        # the soak must sit INSIDE the train budget with the compile
        # margin to spare, and the stage must contain the train run with
        # room for trainer construction + actor spawn + teardown
        assert train_s == soak + bench.E2E_COMPILE_MARGIN
        assert bench.E2E_COMPILE_MARGIN >= 60.0
        assert stage_s == train_s + bench.PART2_MARGIN
        assert bench.PART2_MARGIN >= 120.0


def test_e2e_budgets_honor_env_override(monkeypatch):
    monkeypatch.setenv("BENCH_E2E_SECONDS", "30")
    bench = _load_bench()
    soak, train_s, stage_s = bench.e2e_budgets("tpu")
    assert soak == 30.0
    assert train_s > soak and stage_s > train_s


# -- part 1d: actor-plane A/B -----------------------------------------------

@pytest.mark.slow
def test_actor_plane_ab_smoke(monkeypatch):
    """Part 1d end to end at toy scale: both geometries report per-mode
    frames/s + overlap fractions and a speedup ratio.  The effective-core
    probe is stubbed: its spawn children resolve ``_burn_child`` by
    importing ``bench`` under its real module name, which this loader's
    alias breaks — the probe's own bounded-wait fallback (0.0) covers
    that in production, and here a stub keeps the smoke fast.  Slow:
    compiles the pixel policy."""
    monkeypatch.setenv("BENCH_ACTOR_STEPS", "3")
    monkeypatch.setenv("BENCH_ACTOR_REPS", "1")
    bench = _load_bench()
    monkeypatch.setattr(bench, "_effective_cores", lambda: 1.0)
    out = bench.bench_actor_plane()
    assert out["effective_cores"] == 1.0
    for lane in ("toy", "pixel"):
        d = out[lane]
        assert d["speedup"] is None or d["speedup"] > 0
        for mode in ("off", "on"):
            m = d[mode]
            assert m["frames_per_sec"] > 0
            assert 0.0 <= m["policy_wait_frac"] <= 1.0
            assert 0.0 <= m["env_step_frac"] <= 1.0
            assert len(m["reps"]) == 1
