#!/usr/bin/env python3
"""``readings.py`` for a cell whose learner state fills most of the chip:
one seed a process, the trainer let go before any reference runs (a
reference holds 16 bytes a parameter of its own, returns a gradient of 4
more and reserves its program's temporaries beside them), and one more
fault is read, the one only a torso with routed experts can have.  On the
chip:

    python3 benchmark/tests/readings_big.py --workload glmq_ondevice \
        --seed 2900000301

First the programs the window drives through the checked steps (side
``program``), then, where asked, the checked steps again through step
programs jitted around a torso WITHOUT its routed experts
(``fault_experts_left_out``: the same parameter tree, the shared expert
and attention in place).  Then the trainer goes, and the float32 reference
follows the sound run's steps; after it the reference put in the program's
place in fp8 (the control), with its state frozen and with half of the
batch left out, each compared with the float32 reference.  One JSON line
per reading on stdout as ``readings.py`` prints them, ``correct`` beside
each, a summary at the end.  Exit 1 if the sound run reads not ``correct``
or a control or fault reads ``correct``.  ``--window SECONDS [--trace]``
makes the sound side a whole benchmark run instead (``harness.main``: the
window and its result line included) and reads the reference's sides after
it, in the same process: one trainer, one float32 reference for both.
``tests/test_benchmark_harness.py`` plants the left-out fault on the CPU
through :func:`plant_left_out_experts`.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

#: side -> (arithmetic, fault) of the reference put in the program's place
SIDES = {"control_fp8": ("fp8", None),
         "fault_frozen": ("f32", "frozen"),
         "fault_half_batch": ("f32", "half_batch")}
LEFT_OUT = "fault_experts_left_out"


def plant_left_out_experts(run):
    """Replace the built trainer's step programs by ones traced while the
    torso's expert layer adds nothing for its routed experts (the layer's
    own ``routed`` swapped for a stub from here: the program has no flag
    for it).  Returns the function that puts ``routed`` back; the step
    programs trace at their first call, so call it after
    ``checked_steps()``."""
    import jax.numpy as jnp

    from apex_tpu.models import glm4_moe_lite as glm

    def nothing(self, h, picks, *_):
        return (jnp.zeros((picks.shape[0], h.shape[-1]), jnp.float32),
                jnp.zeros(self.n_held_experts, jnp.int32))

    real = glm.MoE.routed
    glm.MoE.routed = nothing
    tr = run.trainer
    core = dataclasses.replace(tr.core)     # fresh jits: nothing traced yet
    tr._fused, tr._train = core.jit_fused_step(), core.jit_train_step()
    return lambda: setattr(glm.MoE, "routed", real)


class FrozenFamily:
    """The family with a step that leaves its state as it was.  The
    harness's own ``frozen`` fault hands each step a copy of the state
    (twice 16 bytes a parameter: over the chip at 591 M); here the step
    eats the state it is given and hands back the start state: parameters
    from the host's ``weights0`` again, moments at nought, the target it
    was given (no step of the three reaches a target sync).  The same
    thing three times over, within the bytes a sound step holds."""

    def __init__(self, family, weights0, hyper):
        self._family, self._weights0, self._hyper = family, weights0, hyper

    def __getattr__(self, name):
        return getattr(self._family, name)

    def step(self, state, batch, weights, key, hp, mode):
        import jax
        import jax.numpy as jnp
        new, out = self._family.step(state, batch, weights, key, hp, mode)
        target = new["target_params"]
        del state, new
        params = jax.tree.map(jnp.asarray, self._weights0)
        return dict(params=params, target_params=target,
                    opt=self._family.init_opt(params, self._hyper),
                    step=0), out


def reference_side(run, side: str) -> dict:
    """The reference in the program's place, by side."""
    mode, fault = SIDES[side]
    if fault != "frozen":
        return run.reference(mode, fault)
    family = run.family
    run.family = FrozenFamily(family, run.weights0, run.hyper)
    try:
        return run.reference(mode)
    finally:
        run.family = family


def let_the_device_go(run) -> None:
    """The trainer, every compiled program, and every large device array
    that outlives the trainer: nothing of the program's is read from here
    on (its readings are numbers on the host).  The on-device rollout's
    acting snapshot and carry do outlive it where ``train()`` never ran:
    2.7 GB at 591 M parameters, which left the fp8 reference's program
    0.13 GB short of its 4.86 GB of temporaries (my chip runs, PR 29)."""
    import jax

    run.free_program()
    jax.clear_caches()
    left = [a for a in jax.live_arrays() if a.nbytes >= 1 << 20]
    run.say(f"{sum(a.nbytes for a in left) / 1e9:.3f} GB in {len(left)} "
            f"device arrays outlived the trainer: deleted")
    for a in left:
        a.delete()


def whole_run(a):
    """``harness.main`` as ``run.py`` calls it, keeping the run and the
    float32 reference it judged by."""
    from benchmark import harness

    kept = {}
    judge = harness.Run.judge

    def keeping(self, side=None, ref=None):
        import jax
        # a traced run keys its programs by their scopes too and compiles
        # them itself; the reference carries none: the cache may serve it
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        kept["run"], kept["ref"] = self, self.reference("f32")
        return judge(self, side, kept["ref"])

    harness.Run.judge = keeping
    try:
        rc = harness.main(a.workload, a.seed, a.window, a.trace,
                          a.rehearsal, time.monotonic())
    finally:
        harness.Run.judge = judge
    if rc:
        raise SystemExit(rc)
    return kept["run"], kept["ref"]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2_900_000_301)
    ap.add_argument("--sides", default=f"{LEFT_OUT},control_fp8,"
                    "fault_frozen,fault_half_batch")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--window", type=float, default=0.0)
    ap.add_argument("--trace", action="store_true")
    a = ap.parse_args()
    if a.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from benchmark import harness

    sides = [s for s in a.sides.split(",") if s]
    if a.window:
        sides = [s for s in sides if s != LEFT_OUT]     # the trainer is gone
        run, ref = whole_run(a)
    else:
        run = harness.Run(a.workload, a.seed, 0.0, False, a.rehearsal,
                          time.monotonic())
        run.start()
        run.build()
        run.checked_steps()
    programs = {"program": run.program}
    steps, rows = run.steps, run.rows       # what the reference follows
    if LEFT_OUT in sides:
        try:
            put_back = plant_left_out_experts(run)
            try:
                run.reset_state(a.seed)
                run.checked_steps()
            finally:
                put_back()
            programs[LEFT_OUT] = run.program
        except Exception:                   # the other sides still read
            traceback.print_exc()
    run.steps, run.rows = steps, rows
    let_the_device_go(run)
    if not a.window:
        ref = run.reference("f32")

    limits = run.config["check"]["limits"]
    out, wrong = [], 0

    def read(side: str, got: dict, ref: dict) -> None:
        nonlocal wrong
        nums = harness.readings(got, ref)
        correct = harness.verdict({}, harness.compare(got, ref, limits))[0]
        wrong += correct != (side == "program")
        row = dict(workload=a.workload, seed=a.seed, side=side,
                   correct=correct, **{k: v[0] for k, v in nums.items()},
                   where={k: v[1] for k, v in nums.items()})
        out.append(row)
        print(json.dumps(row), flush=True)

    for side, got in programs.items():
        read(side, got, ref)
    for side in sides:
        if side == LEFT_OUT:
            wrong += side not in programs
            continue
        # the program before goes first: a loaded executable keeps its
        # temporaries reserved at the bottom of device memory
        jax.clear_caches()
        try:
            read(side, reference_side(run, side), ref)
        except Exception:
            traceback.print_exc()
            wrong += 1
    summary = {f"{r['side']}.{name}": r[name] for r in out for name in
               ("writeback_miss", "loss_gap", "grad_gap", "dparam_gap")}
    print("READINGS " + json.dumps(dict(workload=a.workload, seed=a.seed,
                                        device=run.device, limits=limits,
                                        **summary)), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
