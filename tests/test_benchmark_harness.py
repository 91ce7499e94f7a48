"""The benchmark harness's own CPU tests, counted and guarded by tier-1.

``benchmark/tests/test_harness.py`` (the driver's tier-1 command collects
``tests/`` only) keeps its 17 cases where a ``benchmark`` PR edits them;
this module imports them, fixtures included, so that every PR runs them.
Plus the new cell's CPU rehearsal: ``glmq_ondevice`` through ``build()``,
``checked_steps()``, ``reference()`` and ``judge()`` reads ``correct``, and
reads not ``correct`` with the routed experts left out of the program's
side.
"""

from __future__ import annotations

import importlib.util
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _load(name: str):
    path = os.path.join(ROOT, "benchmark", "tests", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_tests_" + name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


_harness_tests = _load("test_harness")
globals().update({
    name: obj for name, obj in vars(_harness_tests).items()
    if name.startswith("test_") or name in ("checked", "consuming")})


from apex_tpu.runtime import cli  # noqa: E402
from benchmark import feed, harness  # noqa: E402

#: what the stand-in's module-long fixture patches, as it was before
_REAL = ((cli, "build_trainer", cli.build_trainer),
         (harness, "load_cell", harness.load_cell),
         (feed, "make_weights", feed.make_weights))


@pytest.fixture(scope="module")
def glmq():
    """The new cell at its rehearsal size, built once, through the real
    ``load_cell`` and ``build_trainer`` whatever order the cases run in."""
    with pytest.MonkeyPatch.context() as mp:
        for owner, name, real in _REAL:
            mp.setattr(owner, name, real)
        run = harness.Run("glmq_ondevice", 2_900_000_011, 0.0, False, True,
                          time.monotonic())
        run.start()
        run.build()
    return run


def _verdict(run):
    numbers = run.judge()
    return harness.verdict({}, numbers)[0], numbers


def test_glmq_ondevice_rehearsal_reads_correct(glmq):
    run = glmq
    assert run.cfg.learner.torso == "glm47_flash_tiny"
    assert run.cfg.env.token_vocab == run.config["check"]["action_count"]
    assert type(run.trainer.pool).__name__ == "AnakinPool"
    run.checked_steps()
    ok, numbers = _verdict(run)
    assert ok, numbers
    assert set(numbers) == {"writeback_miss", "loss_gap", "grad_gap",
                            "dparam_gap"}
    # the three checked updates went through the programs the window drives
    assert run.trainer._fused._cache_size() == 1
    assert run.trainer._train._cache_size() == 1


def test_glmq_ondevice_without_its_routed_experts_is_not_correct(glmq):
    run = glmq
    real = run.trainer._fused, run.trainer._train
    put_back = _load("readings_big").plant_left_out_experts(run)
    try:
        run.reset_state(2_900_000_012)
        run.checked_steps()
        ok, numbers = _verdict(run)
    finally:
        put_back()
        run.trainer._fused, run.trainer._train = real
    assert not ok, numbers
    # the experts' gradient is nought on the program's side
    assert numbers["grad_gap"][0] > numbers["grad_gap"][1]
