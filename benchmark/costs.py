"""Operations and bytes a learner step needs, from shapes alone.

These are the yardstick's own counts: what the algorithm requires, not
what an implementation happens to execute (XLA's ``cost_analysis`` moves
when a PR removes work; this does not).  A multiply-add is two
operations.  Backward = twice the forward of the layers that carry a
gradient; the target network's forward carries none.

What belongs to one family of models (its forward pass and parameter
count) sits in ``costs_<family>.py`` beside this file, found by the
``family`` the configuration names, so a new family brings a file and
edits none.

``python benchmark/costs.py`` runs the self-check against the hand-worked
numbers of the Nature trunk.
"""

from __future__ import annotations

import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def conv_out(size: int, kernel: int, stride: int) -> int:
    return (size - kernel) // stride + 1


def family_costs(family: str):
    """``benchmark/costs_<family>.py``: ``forward_macs(shapes)``,
    ``param_count(shapes)``, ``step_macs(shapes)`` and, where the family
    can act on the device, ``acting_cost(shapes)``."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    return importlib.import_module(f"benchmark.costs_{family}")


def gather_bytes(shapes: dict) -> int:
    """Random ring rows a step reads: obs and next_obs stacks, padded rows."""
    return 2 * shapes["batch"] * shapes["frame_stack"] * shapes["row_bytes"]


def step_cost(config: dict) -> dict:
    """``flops`` (forward + backward of the update) and ``bytes`` (rows
    gathered, parameters read for 3 passes and written once with their
    optimizer moments) of ONE learner step."""
    s = config["shapes"]
    fam = family_costs(config["family"])
    n_param = fam.param_count(s)
    nbytes = gather_bytes(s) + n_param * 4 * (3 + 1 + 2 * s["opt_moments"])
    return dict(flops=2 * fam.step_macs(s), bytes=nbytes, params=n_param)


def acting_cost(config: dict) -> dict:
    """One acting forward of one env lane (on-device rollout)."""
    return family_costs(config["family"]).acting_cost(config["shapes"])


def program_cost(config: dict, program: dict) -> dict:
    """Cost of one call of a step program: ``learner_steps`` updates plus
    ``acting_forwards`` lane-steps of on-device rollout."""
    step = step_cost(config)
    flops = program["learner_steps"] * step["flops"]
    nbytes = program["learner_steps"] * step["bytes"]
    if program.get("acting_forwards"):
        act = acting_cost(config)
        flops += program["acting_forwards"] * act["flops"]
        nbytes += program["acting_forwards"] * act["bytes"]
    return dict(flops=flops, bytes=nbytes)


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks recorded for device_kind {device_kind!r}; "
                       f"add it to benchmark/peaks.json with its source")
    return table[device_kind]


def self_check() -> None:
    """Hand-worked: 84x84x4 -> conv1 20x20x32x(8*8*4) = 3,276,800;
    conv2 9x9x64x(4*4*32) = 2,654,208; conv3 7x7x64x(3*3*64) = 1,806,336
    MACs a sample; gather 2 x 512 x 4 rows x 7,168 B = 29,360,128 B."""
    dqn = family_costs("dqn")
    forward_macs, nature_trunk_macs = dqn.forward_macs, dqn.nature_trunk_macs
    trunk = nature_trunk_macs(84, 84, 4)
    assert trunk == [3_276_800, 2_654_208, 1_806_336], trunk
    shapes = dict(frame_shape=[84, 84, 1], frame_stack=4, num_actions=3,
                  batch=512, row_bytes=7168, opt_moments=2)
    assert gather_bytes(shapes) == 29_360_128
    fwd = forward_macs(shapes)
    assert fwd == sum(trunk) + 2 * 3136 * 128 + 128 * 3 + 128, fwd
    cost = step_cost(dict(family="dqn", shapes=shapes))
    assert cost["flops"] == 2 * 7 * 512 * fwd
    for kind in ("TPU v5 lite",):
        assert peaks_for(kind)["flops_per_s"] == 197e12
    try:
        peaks_for("TPU v9")
    except KeyError:
        pass
    else:
        raise AssertionError("unknown device must be an error")


if __name__ == "__main__":
    self_check()
    print("costs self-check passed")
