#!/usr/bin/env python3
"""On the chip, by hand: ``test_harness.py``'s stand-in family at a size
whose learner state fills most of a chip, through set-up, the checked
steps and a reference whose step donates, with the device's peak bytes after
each.

    python3 benchmark/tests/big_state.py [--experts 20] [--dim 2048] [--hidden 6144] [--vocab 19360]

The default is 5.4e8 parameters: 8.7 GB at the 16 bytes a parameter a
learner state holds, of 16.9 GB.  A harness that holds two states in
set-up (32 bytes a parameter, PR 27 and before) runs out of memory in
``build()``.  One JSON line per phase on stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "tpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", type=int, default=20)
    ap.add_argument("--dim", type=int, default=2048)
    ap.add_argument("--hidden", type=int, default=6144)
    ap.add_argument("--vocab", type=int, default=19360)
    ap.add_argument("--seed", type=int, default=2_800_000_777)
    a = ap.parse_args()
    import pytest

    import test_harness as t
    t.EXPERTS, t.DIM, t.HIDDEN, t.VOCAB = a.experts, a.dim, a.hidden, a.vocab
    import jax

    device = jax.devices()[0]

    def say(phase: str, **more) -> None:
        # the CPU rehearsal has no memory statistics: what is live stands in
        peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
        print(json.dumps(dict(
            phase=phase, peak_bytes=peak, live_bytes=t.live_bytes(),
            peak_bytes_a_parameter=peak and peak / n_params, **more)),
            flush=True)

    with pytest.MonkeyPatch.context() as mp:
        n_params = (a.vocab * a.dim + a.dim
                    + 2 * a.experts * a.dim * a.hidden
                    + a.dim * t.ACTIONS + t.ACTIONS)
        print(json.dumps(dict(device=device.device_kind, n_params=n_params,
                              state_gb=16 * n_params / 1e9)), flush=True)
        run = t.make_run(mp)
        assert run.n_params == n_params
        say("build")
        run.reset_state(a.seed)
        say("reset_state")
        run.checked_steps()
        say("checked_steps", losses=run.program["losses"])
        run.free_program()
        mp.setattr(run, "family", t.make_family(donates=True))
        # value, the CPU test's limit (the chip multiplies float32 in
        # bfloat16 passes: `loss_gap` reads past it), where
        say("reference", compared={k: list(v) for k, v in
                                   run.judge().items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
