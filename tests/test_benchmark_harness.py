"""The benchmark harness's own CPU tests, counted and guarded by tier-1.

``benchmark/tests/test_harness.py`` (the driver's tier-1 command collects
``tests/`` only) keeps its 17 cases where a ``benchmark`` PR edits them;
this module imports them, fixtures included, so that every PR runs them.
Plus the token cells' CPU rehearsals: ``glmq_ondevice``,
``twotowerq_ondevice``, ``qnextq_ondevice`` and ``lfm2q_ondevice`` through
``build()``, ``checked_steps()``,
``reference()`` and ``judge()`` read ``correct``, and read not ``correct``
with the routed experts left out of the program's side.
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


def _rehearsal(workload: str, seed: int):
    """A token cell at its rehearsal size, built through the real
    ``load_cell`` and ``build_trainer`` whatever order the cases run in."""
    with pytest.MonkeyPatch.context() as mp:
        for owner, name, real in _REAL:
            mp.setattr(owner, name, real)
        run = harness.Run(workload, seed, 0.0, False, True, time.monotonic())
        run.start()
        run.build()
    return run


@pytest.fixture(scope="module")
def glmq():
    return _rehearsal("glmq_ondevice", 2_900_000_011)


@pytest.fixture(scope="module")
def twotowerq():
    return _rehearsal("twotowerq_ondevice", 3_300_000_014)


@pytest.fixture(scope="module")
def qnextq():
    return _rehearsal("qnextq_ondevice", 3_500_000_016)


@pytest.fixture(scope="module")
def lfm2q():
    return _rehearsal("lfm2q_ondevice", 4_100_000_016)


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


def test_twotowerq_ondevice_rehearsal_reads_correct(twotowerq):
    """The Nemotron-H cell on the CPU at the toy preset: the family's
    reference and costs are found by the config's ``family``, the trainer
    acts on the device, holds its share, and the three checked updates
    through the window's own programs (the chunked scan) agree with the
    float32 reference (the recurrence) inside the cell's limits."""
    run = twotowerq
    assert run.config["family"] == "nemotron_h_q"
    assert run.family.__name__ == "benchmark.reference.nemotron_h_q"
    assert run.cfg.learner.torso == "nemotron_h_tiny"
    assert run.cfg.env.token_context == 32
    assert run.cfg.env.token_vocab == run.config["check"]["action_count"]
    assert type(run.trainer.pool).__name__ == "AnakinPool"
    layout = run.trainer.model.torso_layout()
    assert (layout["mamba_heads"], layout["experts"]) == ("2/4", "2/8")
    run.checked_steps()
    ok, numbers = _verdict(run)
    assert ok, numbers
    # the numbers the cell is held to: the loss, the gradient and the
    # parameters' change by their MEDIAN leaf (what tells precisions apart)
    # and the change by its worst leaf (what tells a leaf left unchanged);
    # the worst leaf's gradient is not held: one pick that falls the other
    # way on a last-position token moves it as far as the fp8 control does
    # (PERF.md section 6)
    assert set(numbers) == {"writeback_miss", "loss_gap", "grad_median_gap",
                            "dparam_median_gap", "dparam_gap"}
    assert run.trainer._fused._cache_size() == 1
    assert run.trainer._train._cache_size() == 1
    # the benchmark's own counts of the configuration it runs
    from benchmark import costs
    cost = costs.step_cost(run.config)
    assert cost["params"] == 577_780_864
    assert costs.program_cost(run.config, dict(
        learner_steps=1, acting_forwards=16))["flops"] > cost["flops"]


def test_twotowerq_ondevice_without_its_routed_experts_is_not_correct(
        twotowerq):
    """The fault ``readings_big.py`` plants swaps the ONE expert layer's
    ``routed``: it reaches this torso's two-matrix experts too."""
    run = twotowerq
    real = run.trainer._fused, run.trainer._train
    put_back = _load("readings_big").plant_left_out_experts(run)
    try:
        run.reset_state(3_300_000_015)
        run.checked_steps()
        ok, numbers = _verdict(run)
    finally:
        put_back()
        run.trainer._fused, run.trainer._train = real
    assert not ok, numbers
    # the experts' gradient is nought on the program's side: their leaves
    # stay where they were, which the worst leaf's change reads as 1.0
    assert numbers["dparam_gap"][0] == pytest.approx(1.0)
    assert numbers["dparam_gap"][0] > numbers["dparam_gap"][1]


def test_qnextq_ondevice_rehearsal_reads_correct(qnextq):
    """The Qwen3-Next cell on the CPU at the toy preset: the family's
    reference, costs and scope table are found by the config's ``family``,
    the trainer acts on the device, holds its share, and the three checked
    updates through the window's own programs (the chunked delta rule, each
    part of a layer over blocks of contexts) agree with the float32
    reference (the recurrence) inside the cell's limits."""
    run = qnextq
    assert run.config["family"] == "qwen3_next_q"
    assert run.family.__name__ == "benchmark.reference.qwen3_next_q"
    assert run.cfg.learner.torso == "qwen3_next_tiny"
    assert run.cfg.env.token_context == 32
    assert run.cfg.env.token_vocab == run.config["check"]["action_count"]
    assert type(run.trainer.pool).__name__ == "AnakinPool"
    layout = run.trainer.model.torso_layout()
    assert (layout["pattern"], layout["key_heads"], layout["experts"]) == (
        "DDDA", "2/4", "2/32")
    run.checked_steps()
    ok, numbers = _verdict(run)
    assert ok, numbers
    assert set(numbers) == {"writeback_miss", "loss_gap", "grad_median_gap",
                            "dparam_median_gap", "dparam_gap"}
    assert run.trainer._fused._cache_size() == 1
    assert run.trainer._train._cache_size() == 1
    # the benchmark's own counts of the configuration it runs
    from benchmark import costs, family_scopes
    cost = costs.step_cost(run.config)
    assert cost["params"] == 561_458_144
    assert costs.program_cost(run.config, dict(
        learner_steps=1, acting_forwards=16))["flops"] > cost["flops"]
    assert "delta" in family_scopes.table_for(run.config["family"]).SCOPES
    # every catalog key the configuration's file holds is the published
    # one, or is listed as reduced
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "qwen3_next_q_ep16")
    assert set(entry["reduced"]) == set(run.config["reduced_why"])
    assert set(run.config["published"]) == set(entry["reduced"])


def test_qnextq_ondevice_without_its_routed_experts_is_not_correct(qnextq):
    """The fault ``readings_big.py`` plants swaps the ONE expert layer's
    ``routed``: it reaches this torso's softmax-routed experts too."""
    run = qnextq
    real = run.trainer._fused, run.trainer._train
    put_back = _load("readings_big").plant_left_out_experts(run)
    try:
        run.reset_state(3_500_000_015)
        run.checked_steps()
        ok, numbers = _verdict(run)
    finally:
        put_back()
        run.trainer._fused, run.trainer._train = real
    assert not ok, numbers
    assert numbers["dparam_gap"][0] == pytest.approx(1.0)
    assert numbers["dparam_gap"][0] > numbers["dparam_gap"][1]


def test_qnextq_ondevice_rehearsal_reads_correct_where_a_pick_flipped(qnextq):
    """The seed on which the toy reads highest: its worst leaf is a
    router's (change off by 0.065, as a top-k pick that fell the other way
    between the program's scores and the float32 ones would leave it) and
    its median leaf's change reads 0.0027: inside the cell's limits all the
    same."""
    run = qnextq
    run.reset_state(3_500_000_014)
    run.checked_steps()
    ok, numbers = _verdict(run)
    assert ok, numbers
    assert numbers["dparam_gap"][2].endswith("router_kernel")
    assert 0.02 < numbers["dparam_gap"][0] < numbers["dparam_gap"][1]
    assert 0.002 < numbers["dparam_median_gap"][0]


def test_lfm2q_ondevice_rehearsal_reads_correct(lfm2q):
    """The LFM2 cell on the CPU at the toy preset: the family's reference,
    costs and scope table are found by the config's ``family``, the
    trainer acts on the device and holds its share, and the three checked
    updates through the window's own programs agree with the float32
    reference inside the cell's limits."""
    run = lfm2q
    assert run.config["family"] == "lfm2_moe_q"
    assert run.family.__name__ == "benchmark.reference.lfm2_moe_q"
    assert run.cfg.learner.torso == "lfm2_tiny"
    assert run.cfg.env.token_context == 32
    assert run.cfg.env.token_vocab == run.config["check"]["action_count"]
    assert type(run.trainer.pool).__name__ == "AnakinPool"
    layout = run.trainer.model.torso_layout()
    assert (layout["pattern"], layout["experts"], layout["tied_head"]) == (
        "CACCC", "2/8", 1)
    run.checked_steps()
    ok, numbers = _verdict(run)
    assert ok, numbers
    assert set(numbers) == {"writeback_miss", "loss_gap", "grad_median_gap",
                            "dparam_median_gap", "dparam_gap"}
    assert run.trainer._fused._cache_size() == 1
    assert run.trainer._train._cache_size() == 1
    from benchmark import costs, family_scopes
    cost = costs.step_cost(run.config)
    assert cost["params"] == 507_820_288
    assert costs.program_cost(run.config, dict(
        learner_steps=1, acting_forwards=16))["flops"] > cost["flops"]
    table = family_scopes.table_for(run.config["family"])
    assert {"shortconv", "conv", "qk_norm_attention"} <= set(table.SCOPES)
    # every catalog key the configuration's file holds is the published
    # one, or is listed as reduced
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "lfm2_8b_a1b_q_ep4")
    assert set(entry["reduced"]) == set(run.config["reduced_why"])
    assert set(entry["reduced"]) <= set(run.config["published"])


def test_lfm2q_ondevice_without_its_routed_experts_is_not_correct(lfm2q):
    """The fault ``readings_big.py`` plants swaps the ONE expert layer's
    ``routed``: with no shared expert, the expert layers then add nothing
    at all, and the worst leaf's change reads 1.0."""
    run = lfm2q
    real = run.trainer._fused, run.trainer._train
    put_back = _load("readings_big").plant_left_out_experts(run)
    try:
        run.reset_state(4_100_000_015)
        run.checked_steps()
        ok, numbers = _verdict(run)
    finally:
        put_back()
        run.trainer._fused, run.trainer._train = real
    assert not ok, numbers
    assert numbers["dparam_gap"][0] == pytest.approx(1.0)
    assert numbers["dparam_gap"][0] > numbers["dparam_gap"][1]
