"""Operations of the Ape-X DQN family (dueling Nature-CNN), from shapes."""

from __future__ import annotations

import math

from benchmark.costs import conv_out


def nature_trunk_macs(h: int, w: int, c_in: int,
                      features=(32, 64, 64)) -> list[int]:
    """MACs per sample of conv 8x8/4, 4x4/2, 3x3/1 (VALID)."""
    out, c = [], c_in
    for f, k, s in zip(features, (8, 4, 3), (4, 2, 1)):
        h, w = conv_out(h, k, s), conv_out(w, k, s)
        out.append(h * w * f * k * k * c)
        c = f
    return out


def _flat(shapes: dict) -> int:
    h = shapes["frame_shape"][0]
    h3 = conv_out(conv_out(conv_out(h, 8, 4), 4, 2), 3, 1)
    return h3 * h3 * 64


def forward_macs(shapes: dict) -> int:
    h, w, c = shapes["frame_shape"]
    trunk = nature_trunk_macs(h, w, c * shapes["frame_stack"])
    heads = 2 * _flat(shapes) * 128 + 128 * shapes["num_actions"] + 128
    return sum(trunk) + heads


def param_count(shapes: dict) -> int:
    cin = shapes["frame_shape"][2] * shapes["frame_stack"]
    return (8 * 8 * cin * 32 + 4 * 4 * 32 * 64 + 3 * 3 * 64 * 64
            + 2 * _flat(shapes) * 128 + 128 * shapes["num_actions"] + 128)


def step_macs(shapes: dict) -> int:
    """Online forward on 2B stacks with its backward (twice the forward),
    target forward on B."""
    b, fwd = shapes["batch"], forward_macs(shapes)
    return 2 * b * fwd * 3 + b * fwd


def acting_cost(shapes: dict) -> dict:
    frame = math.prod(shapes["frame_shape"]) * shapes["frame_stack"]
    return dict(flops=2 * forward_macs(shapes), bytes=frame)
