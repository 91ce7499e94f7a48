"""``correct`` has to come out false where it should: the control (the
reference in fp8 put in the program's place) and each fault a training
cell can have, planted under a run that skips only the harness's look for
a chip.  CPU, at the configuration's rehearsal size:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_correct.py -q

The faults are planted in the programs the window drives (``_fused``, the
ingest+train program, and ``_train``).  The same readings at the cell's
own size come from ``readings.py`` on the chip.  The exchange between chips and an altered token do not apply: every
cell is a one-chip training cell.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

SEEDS = (11, 2_147_483_659, 4_000_000_007)


@pytest.fixture(scope="module")
def run():
    from benchmark import harness

    r = harness.Run("dqn_hostfed", SEEDS[0], 0.0, False, True,
                    time.monotonic())
    r.start()
    r.build()
    return r


def verdict(run, side=None) -> tuple[bool, dict]:
    from benchmark import harness

    numbers = run.judge(side)
    return harness.verdict({}, numbers)[0], numbers


def planted(run, seed: int, **programs) -> tuple[bool, dict]:
    """The checked steps with ``trainer._fused`` / ``trainer._train``
    replaced by broken ones, then the verdict."""
    tr = run.trainer
    real = {name: getattr(tr, name) for name in programs}
    for name, fn in programs.items():
        setattr(tr, name, fn)
    try:
        run.reset_state(seed)
        run.checked_steps()
        return verdict(run)
    finally:
        for name, fn in real.items():
            setattr(tr, name, fn)


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_program_is_correct_and_fp8_control_is_not(run, seed):
    run.reset_state(seed)
    run.checked_steps()
    ok, numbers = verdict(run)
    assert ok, numbers
    ok, numbers = verdict(run, run.reference("fp8"))
    assert not ok, numbers


@pytest.mark.parametrize("which", ["_fused", "_train", "both"])
def test_state_returned_unchanged_is_not_correct(run, which):
    import jax
    import jax.numpy as jnp

    def frozen(real):
        def step(ts, *rest):
            keep = jax.tree.map(jnp.copy, ts)
            _, rs, metrics = real(ts, *rest)
            return keep, rs, metrics
        return step

    names = ("_fused", "_train") if which == "both" else (which,)
    ok, numbers = planted(run, SEEDS[1], **{
        name: frozen(getattr(run.trainer, name)) for name in names})
    assert not ok, numbers
    if which == "both":
        assert numbers["dparam_gap"][0] > 0.9


@pytest.mark.parametrize("which", ["_fused", "_train"])
def test_half_of_the_batch_left_out_is_not_correct(run, which):
    half = dataclasses.replace(run.trainer.core,
                               batch_size=run.trainer.core.batch_size // 2)
    fn = half.jit_fused_step() if which == "_fused" \
        else half.jit_train_step()
    ok, numbers = planted(run, SEEDS[2], **{which: fn})
    assert not ok, numbers
    assert numbers["writeback_miss"][0] > 0


def test_sampling_before_the_ingest_is_not_correct(run):
    """A fused step in the wrong order: the update samples the replay as
    it stood, the chunk goes in afterwards."""
    import jax

    core = run.trainer.core

    def wrong_order(ts, rs, payload, prios, key, beta):
        ts, rs, metrics = core.train_step(ts, rs, key, beta)
        return ts, core.ingest(rs, payload, prios), metrics

    ok, numbers = planted(
        run, SEEDS[0], _fused=jax.jit(wrong_order, donate_argnums=(0, 1)))
    assert not ok, numbers
    assert numbers["writeback_miss"][0] > 0


def test_a_chunk_ingested_at_the_wrong_priorities_is_not_correct(run):
    """An ingest that writes other leaves than the chunk's priorities
    give: the step then samples other rows than the benchmark's sampler
    draws from the tree it expects."""
    real = run.trainer._fused

    def wrong_leaves(ts, rs, payload, prios, key, beta):
        return real(ts, rs, payload, prios[::-1], key, beta)

    ok, numbers = planted(run, SEEDS[1], _fused=wrong_leaves)
    assert not ok, numbers
    assert numbers["writeback_miss"][0] > 0
