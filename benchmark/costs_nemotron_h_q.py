"""Operations of the ``nemotron_h_q`` family (the causal tower of
Nemotron-Labs-TwoTower-30B-A3B as the Q-network of Ape-X DQN, one chip's
share of each layer), from shapes: every count is over the HELD widths
(``shapes["model"]``: heads, groups, key/value heads and experts as this
chip holds them).

A multiply-add is one MAC; ``costs.py`` doubles them.  Routed experts are
counted at their EXPECTED local share, ``k x held / published`` experts a
token (0.375 here); ``expert_macs(pairs)`` counts the pairs a run really
had.  Attention and the scan's quadratic form are counted causal (``T (T +
1) / 2`` score pairs a head; ``Q (Q + 1) / 2`` a chunk of ``Q``): what the
algorithm needs, whatever an implementation computes and masks.
Rematerialised forward passes do not count in ``step_macs`` (what the step
is for); they do in ``EXPERT_UNITS`` / ``SSD_UNITS`` (what a kernel was
asked to do, for its own roofline).

``python benchmark/costs_nemotron_h_q.py`` runs the self-check against the
hand-worked numbers of ISSUE 33's table.
"""

from __future__ import annotations


def _mamba_widths(m: dict) -> tuple[int, int]:
    """(inner width, convolution channels) of the heads held."""
    inner = m["mamba_num_heads"] * m["mamba_head_dim"]
    return inner, inner + 2 * m["n_groups"] * m["ssm_state_size"]


def _mamba_matrix_params(m: dict) -> int:
    """In-projection ``[z | xBC | dt]`` and out-projection."""
    inner, conv = _mamba_widths(m)
    d = m["hidden_size"]
    return d * (inner + conv + m["mamba_num_heads"]) + inner * d


def _attention_matrix_params(m: dict) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    return 2 * d * hd * (m["num_attention_heads"] + m["num_key_value_heads"])


def _expert_params(m: dict) -> int:
    """One routed ``relu^2`` expert: up, down."""
    return 2 * m["hidden_size"] * m["moe_intermediate_size"]


def _shared_params(m: dict) -> int:
    return (m["n_shared_experts"] * 2 * m["hidden_size"]
            * m["moe_shared_expert_intermediate_size"])


def _kinds(shapes: dict) -> dict[str, int]:
    pattern = shapes["model"]["pattern"]
    return {kind: pattern.count(kind) for kind in "ME*"}


def ssd_macs(shapes: dict, tokens: float) -> float:
    """The chunked scan's products for ``tokens`` positions of whole
    contexts, one layer, one forward pass, at the config's ``chunk_size``
    ``Q``: inside a chunk ``C B^T`` over the state width a group and ``(L o
    C B^T)(dt x)`` over the head width a head, both causal (``(Q + 1) / 2``
    pairs a position); between chunks the state a chunk adds and the output
    the entering state gives, ``H P N`` a position each, for the chunks that
    have a neighbour (the last adds to no one, the first is entered by
    nought)."""
    m = shapes["model"]
    h, p = m["mamba_num_heads"], m["mamba_head_dim"]
    g, n, q = m["n_groups"], m["ssm_state_size"], m["chunk_size"]
    chunks = shapes["context"] // q
    inside = (g * n + h * p) * (q + 1) / 2
    between = 2 * h * p * n * (chunks - 1) / chunks
    return tokens * (inside + between)


def ssd_bytes(shapes: dict, tokens: float, passes: float) -> float:
    """The least any implementation of the scan moves for ``tokens``
    positions a pass, over ``passes`` forward-sized passes of one layer:
    read ``x``, ``B``, ``C`` (compute dtype, 2 bytes) and ``dt`` (float32),
    write ``y`` (compute dtype) and the float32 state carried out of every
    chunk.  ``z``, the convolution and the gated norm lie outside the scope
    ``ssd``."""
    m = shapes["model"]
    h, p = m["mamba_num_heads"], m["mamba_head_dim"]
    g, n, q = m["n_groups"], m["ssm_state_size"], m["chunk_size"]
    a_token = 2 * (h * p + 2 * g * n) + 4 * h + 2 * h * p
    carried = 4 * h * p * n / q
    return passes * tokens * (a_token + carried)


def token_macs(shapes: dict) -> float:
    """Products a token, all layers: the matrices, the convolution, the
    scan, routed experts at their expected local share."""
    m = shapes["model"]
    d, kinds = m["hidden_size"], _kinds(shapes)
    _inner, conv = _mamba_widths(m)
    mamba = (_mamba_matrix_params(m) + m["conv_kernel"] * conv
             + ssd_macs(shapes, 1))
    routed = (m["num_experts_per_tok"] * m["n_routed_experts"]
              * _expert_params(m)) / shapes["n_routed_published"]
    expert = d * shapes["n_routed_published"] + _shared_params(m) + routed
    return (kinds["M"] * mamba + kinds["E"] * expert
            + kinds["*"] * _attention_matrix_params(m))


def score_macs(shapes: dict) -> int:
    """Causal attention of one context: q.k and p.v over the head width a
    (query, key) pair, ``T (T + 1) / 2`` pairs a query head, every ``*``
    layer."""
    m, t = shapes["model"], shapes["context"]
    return (_kinds(shapes)["*"] * m["num_attention_heads"] * 2
            * m["head_dim"] * t * (t + 1) // 2)


def forward_macs(shapes: dict) -> float:
    """One context through the torso; the head once (last position)."""
    m = shapes["model"]
    return (token_macs(shapes) * shapes["context"] + score_macs(shapes)
            + m["hidden_size"] * m["vocab_size"])


def param_count(shapes: dict) -> int:
    """Every parameter the learner holds (ISSUE 33's table): the matrices;
    a Mamba-2 layer's convolution with its bias, ``A_log`` / ``D`` /
    ``dt_bias`` and the gated norm's gain; the router's bias; one pre-norm
    a layer and the final norm."""
    m = shapes["model"]
    d, kinds = m["hidden_size"], _kinds(shapes)
    inner, conv = _mamba_widths(m)
    mamba = (_mamba_matrix_params(m) + (m["conv_kernel"] + 1) * conv
             + 3 * m["mamba_num_heads"] + inner + d)
    expert = (d * shapes["n_routed_published"] + shapes["n_routed_published"]
              + _shared_params(m) + m["n_routed_experts"] * _expert_params(m)
              + d)
    attention = _attention_matrix_params(m) + d
    return (kinds["M"] * mamba + kinds["E"] * expert
            + kinds["*"] * attention + 2 * m["vocab_size"] * d + d)


def step_macs(shapes: dict) -> float:
    """Online forward on the batch with its backward (twice the forward),
    online forward on the next states without one, target forward: five
    forward-equivalents a context."""
    return 5 * shapes["batch"] * forward_macs(shapes)


def acting_cost(shapes: dict) -> dict:
    """One lane-step of the on-device rollout: one whole-context forward;
    the context's bytes and the lane's share of one read of the acting
    snapshot (``acting_lanes`` lanes share a forward's weights)."""
    snapshot = param_count(shapes) * shapes["acting_param_bytes"]
    return dict(flops=2 * forward_macs(shapes),
                bytes=2 * shapes["context"]
                + snapshot // shapes["acting_lanes"])


#: forward-sized runs of an expert layer's two grouped products that an
#: update enqueues, by the pass whose routing counter says how many pairs
#: they served: the differentiated pass makes its forward, the forward
#: again when the layer is rematerialised, and a backward of twice a
#: forward; the next-state and target passes one forward each
#: (``tests/test_nemotron_h.py`` counts the kernels in the toy's update
#: compiled for a described chip)
EXPERT_UNITS = {"moe_local_pairs": 4, "moe_local_pairs_next": 1,
                "moe_local_pairs_target": 1}
#: the same of a Mamba-2 layer's scan, which every pass runs on every
#: token: forward-sized passes an update makes of it, a layer
SSD_UNITS = sum(EXPERT_UNITS.values())


def mamba_layers(shapes: dict) -> int:
    return _kinds(shapes)["M"]


def expert_macs(shapes: dict, pairs: float) -> float:
    """The routed experts' products for ``pairs`` (token, expert) pairs."""
    return pairs * _expert_params(shapes["model"])


def expert_bytes(shapes: dict, pairs: float, layer_passes: float) -> float:
    """What those products move: the held experts' float32 weights once a
    layer and pass, and each pair's row in and out in the compute dtype."""
    m = shapes["model"]
    weights = m["n_routed_experts"] * _expert_params(m) * 4
    return layer_passes * weights + pairs * 2 * m["hidden_size"] * 2


def self_check() -> None:
    """Hand-worked (ISSUE 33): a Mamba-2 layer 2688 x 5152 + 2048 x 2688 =
    19,353,600 in matrices, + 5 x 3072 + 3 x 32 + 2048 + 2688 = 19,373,792;
    attention 2 x 2688 x 128 x 17 + 2688 = 11,700,864; an expert layer 2688
    x 128 + 128 + 19,955,712 + 8 x 9,977,856 + 2688 = 100,125,440;
    vocabulary 2 x 16,384 x 2688 + 2688 = 88,083,072; 4 + 4 + 1 layers:
    577,780,864.  The scan a token: (512 + 2048) x 64.5 + 2 x 262,144 x 7 /
    8 = 165,120 + 458,752 = 623,872; its bytes a token 2 x 3072 + 128 + 4096
    + 8192 = 18,560."""
    import json
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "configs",
                           "nemotron_twotower_q_ep16.json")) as f:
        shapes = json.load(f)["shapes"]
    m = shapes["model"]
    assert _mamba_matrix_params(m) == 19_353_600
    assert _attention_matrix_params(m) == 11_698_176
    assert _expert_params(m) == 9_977_856 and _shared_params(m) == 19_955_712
    assert param_count(shapes) == 577_780_864, param_count(shapes)
    assert ssd_macs(shapes, 1) == 623_872, ssd_macs(shapes, 1)
    assert ssd_bytes(shapes, 1, 1) == 18_560, ssd_bytes(shapes, 1, 1)
    assert ssd_bytes(shapes, 16_384, 6) == 6 * 16_384 * 18_560
    token = (4 * (19_353_600 + 12_288 + 623_872)
             + 4 * (344_064 + 19_955_712 + 0.375 * 9_977_856) + 11_698_176)
    assert token_macs(shapes) == token, (token_macs(shapes), token)
    assert score_macs(shapes) == 16 * 256 * 1024 * 1025 // 2
    assert forward_macs(shapes) == (token * 1024 + score_macs(shapes)
                                    + 2688 * 16_384)
    assert step_macs(shapes) == 5 * 16 * forward_macs(shapes)
    assert expert_macs(shapes, 768) == 768 * 9_977_856
    assert mamba_layers(shapes) == 4
    from benchmark import costs
    cost = costs.step_cost(dict(family="nemotron_h_q", shapes=shapes))
    assert cost["flops"] == 2 * step_macs(shapes)
    assert cost["bytes"] == 2 * 16 * 2048 + param_count(shapes) * 4 * 8


if __name__ == "__main__":
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    self_check()
    print("costs_nemotron_h_q self-check passed")
