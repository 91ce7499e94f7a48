"""Operations of the ``qwen3_next_q`` family (Qwen3-Next-80B-A3B's layer
stack as the Q-network of Ape-X DQN, one chip's share of each layer), from
shapes: every count is over the HELD widths (``shapes["model"]``: key,
value and query heads, key/value heads and experts as this chip holds
them).

A multiply-add is one MAC; ``costs.py`` doubles them.  Routed experts are
counted at their EXPECTED local share, ``k x held / published`` experts a
token (0.625 here); ``expert_macs(pairs)`` counts the pairs a run really
had.  Attention and the delta rule's products inside a chunk are counted
causal (``T (T + 1) / 2`` score pairs a head; ``C (C + 1) / 2`` a chunk of
``C``, ``C (C - 1) / 2`` where the diagonal is out): what the algorithm
needs, whatever an implementation computes and masks.  The chunk's
triangular system is counted SOLVED for its two right-hand sides (forward
substitution), not as an inverse formed by repeated squaring.
Rematerialised forward passes do not count in ``step_macs`` (what the step
is for); they do in ``EXPERT_UNITS`` / ``DELTA_UNITS`` (what a kernel was
asked to do, for its own roofline).

``python benchmark/costs_qwen3_next_q.py`` runs the self-check against the
hand-worked numbers of ISSUE 35's table.
"""

from __future__ import annotations


def _gdn_widths(m: dict) -> tuple[int, int]:
    """(key width, value width) of the heads held, all heads wide."""
    return (m["linear_num_key_heads"] * m["linear_key_head_dim"],
            m["linear_num_value_heads"] * m["linear_value_head_dim"])


def _gdn_matrix_params(m: dict) -> int:
    """``W_qkvz``, ``W_ba`` and the out-projection."""
    key, value = _gdn_widths(m)
    d = m["hidden_size"]
    return (d * (2 * key + 2 * value) + d * 2 * m["linear_num_value_heads"]
            + value * d)


def _attention_matrix_params(m: dict) -> int:
    """``W_q`` (query and gate), ``W_k``, ``W_v``, ``W_o``."""
    d, hd = m["hidden_size"], m["head_dim"]
    return d * hd * (3 * m["num_attention_heads"]
                     + 2 * m["num_key_value_heads"])


def _expert_params(m: dict) -> int:
    """One routed SwiGLU expert: gate, up, down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def _shared_params(m: dict) -> int:
    """The shared expert and its gate ``w_g``."""
    return (3 * m["hidden_size"] * m["shared_expert_intermediate_size"]
            + m["hidden_size"])


def _kinds(shapes: dict) -> dict[str, int]:
    """Layers by mixer: layer ``i`` is attention where ``(i + 1) %
    full_attention_interval == 0``."""
    m = shapes["model"]
    attn = m["num_hidden_layers"] // m["full_attention_interval"]
    return {"gdn": m["num_hidden_layers"] - attn, "attention": attn}


def delta_macs(shapes: dict, tokens: float) -> float:
    """The chunked gated delta rule's products for ``tokens`` positions of
    whole contexts, one layer, one forward pass, at the config's
    ``chunk_size`` ``C``.  A key head: ``k k^T`` under the diagonal and ``q
    k^T`` with it, over the key width (``C`` pairs a position together).  A
    value head: the unit lower-triangular system solved for the values and
    the decayed keys (``(C - 1) / 2`` rows a position over ``dv + dk``), the
    chunk's own output ``(q k^T o decay) v_new`` (``(C + 1) / 2`` over
    ``dv``), and between chunks the correction by the entering state, the
    output it gives and the state a chunk adds, ``dk dv`` a position each,
    for the chunks that have a neighbour (the first is entered by nought,
    the last adds to no one)."""
    m = shapes["model"]
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv, c = (m["linear_key_head_dim"], m["linear_value_head_dim"],
                 m["chunk_size"])
    chunks = shapes["context"] // c
    a_key_head = dk * c
    a_value_head = ((dv + dk) * (c - 1) / 2 + dv * (c + 1) / 2
                    + 3 * dk * dv * (chunks - 1) / chunks)
    return tokens * (hk * a_key_head + hv * a_value_head)


def delta_bytes(shapes: dict, tokens: float, passes: float) -> float:
    """The least any implementation of the rule moves for ``tokens``
    positions a pass, over ``passes`` forward-sized passes of one layer:
    read ``q``, ``k``, ``v`` (compute dtype, 2 bytes), ``g`` and ``beta``
    (float32, a value head), write ``o`` (compute dtype) and the float32
    state carried out of every chunk.  The projections, the convolution,
    ``z`` and the gated norm lie outside the scope ``delta``."""
    m = shapes["model"]
    key, value = _gdn_widths(m)
    hv = m["linear_num_value_heads"]
    a_token = 2 * (2 * key + value) + 2 * 4 * hv + 2 * value
    carried = (4 * hv * m["linear_key_head_dim"] * m["linear_value_head_dim"]
               / m["chunk_size"])
    return passes * tokens * (a_token + carried)


def token_macs(shapes: dict) -> float:
    """Products a token, all layers: the matrices, the convolution, the
    delta rule, routed experts at their expected local share."""
    m = shapes["model"]
    d, kinds = m["hidden_size"], _kinds(shapes)
    key, value = _gdn_widths(m)
    gdn = (_gdn_matrix_params(m)
           + m["linear_conv_kernel_dim"] * (2 * key + value)
           + delta_macs(shapes, 1))
    routed = (m["num_experts_per_tok"] * m["num_experts"]
              * _expert_params(m)) / shapes["n_routed_published"]
    expert = d * shapes["n_routed_published"] + _shared_params(m) + routed
    return (kinds["gdn"] * gdn
            + kinds["attention"] * _attention_matrix_params(m)
            + m["num_hidden_layers"] * expert)


def score_macs(shapes: dict) -> int:
    """Causal attention of one context: q.k and p.v over the head width a
    (query, key) pair, ``T (T + 1) / 2`` pairs a query head, every
    attention layer."""
    m, t = shapes["model"], shapes["context"]
    return (_kinds(shapes)["attention"] * m["num_attention_heads"] * 2
            * m["head_dim"] * t * (t + 1) // 2)


def forward_macs(shapes: dict) -> float:
    """One context through the torso; the head once (last position)."""
    m = shapes["model"]
    return (token_macs(shapes) * shapes["context"] + score_macs(shapes)
            + m["hidden_size"] * m["vocab_size"])


def param_count(shapes: dict) -> int:
    """Every parameter the learner holds (ISSUE 35's table): the matrices;
    a DeltaNet layer's convolution, ``A_log`` / ``dt_bias`` and the gated
    norm's gain; an attention layer's two head norms; two pre-norms a layer
    and the final norm."""
    m = shapes["model"]
    d, kinds = m["hidden_size"], _kinds(shapes)
    key, value = _gdn_widths(m)
    gdn = (_gdn_matrix_params(m)
           + m["linear_conv_kernel_dim"] * (2 * key + value)
           + 2 * m["linear_num_value_heads"] + m["linear_value_head_dim"])
    attention = _attention_matrix_params(m) + 2 * m["head_dim"]
    expert = (d * shapes["n_routed_published"] + _shared_params(m)
              + m["num_experts"] * _expert_params(m))
    return (kinds["gdn"] * gdn + kinds["attention"] * attention
            + m["num_hidden_layers"] * (expert + 2 * d)
            + 2 * m["vocab_size"] * d + d)


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


#: forward-sized runs of an expert block's three grouped products that an
#: update enqueues, by the pass whose routing counter says how many pairs
#: they served (the other token families' count, for their reason)
EXPERT_UNITS = {"moe_local_pairs": 4, "moe_local_pairs_next": 1,
                "moe_local_pairs_target": 1}
#: the same of a Gated DeltaNet layer's delta rule, which every pass runs
#: on every token: forward-sized passes an update makes of it, a layer
#: (``tests/test_qwen3_next.py`` counts the carried state's loops in the
#: toy's update compiled for a described chip)
DELTA_UNITS = sum(EXPERT_UNITS.values())


def gdn_layers(shapes: dict) -> int:
    return _kinds(shapes)["gdn"]


def expert_layers(shapes: dict) -> int:
    """Every layer has an expert block."""
    return shapes["model"]["num_hidden_layers"]


def expert_macs(shapes: dict, pairs: float) -> float:
    """The routed experts' products for ``pairs`` (token, expert) pairs."""
    return pairs * _expert_params(shapes["model"])


def expert_bytes(shapes: dict, pairs: float, layer_passes: float) -> float:
    """What those products move: the held experts' float32 weights once a
    layer and pass, and each pair's row in and out in the compute dtype."""
    m = shapes["model"]
    weights = m["num_experts"] * _expert_params(m) * 4
    return layer_passes * weights + pairs * 2 * m["hidden_size"] * 2


def self_check() -> None:
    """Hand-worked (ISSUE 35): a DeltaNet layer 2048 x 6144 + 2048 x 32 +
    2048 x 2048 = 16,842,752 in matrices, + 4 x 4096 + 32 + 128 =
    16,859,296; attention 2048 x 256 x (24 + 2) = 13,631,488 + 512 =
    13,632,000; an expert block 2048 x 512 + 3,145,728 + 2,048 + 32 x
    3,145,728 = 104,859,648; two pre-norms 4,096; vocabulary 2 x 18,992 x
    2048 + 2048 = 77,793,280; 3 + 1 layers: 561,458,144.  The delta rule a
    token: 8 x 128 x 64 = 65,536 a key head's products + 16 x (256 x 31.5 +
    128 x 32.5 + 3 x 16,384 x 15 / 16 = 58,304) = 998,400; its bytes a
    token 2 x 4096 + 128 + 4096 + 16,384 = 28,800."""
    import json
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "configs", "qwen3_next_q_ep16.json")) as f:
        shapes = json.load(f)["shapes"]
    m = shapes["model"]
    assert _gdn_matrix_params(m) == 16_842_752
    assert _attention_matrix_params(m) == 13_631_488
    assert _expert_params(m) == 3_145_728 and _shared_params(m) == 3_147_776
    assert param_count(shapes) == 561_458_144, param_count(shapes)
    assert delta_macs(shapes, 1) == 998_400, delta_macs(shapes, 1)
    assert delta_bytes(shapes, 1, 1) == 28_800, delta_bytes(shapes, 1, 1)
    assert delta_bytes(shapes, 16_384, 6) == 6 * 16_384 * 28_800
    expert = 1_048_576 + 3_147_776 + 0.625 * 3_145_728
    token = (3 * (16_842_752 + 16_384 + 998_400) + 13_631_488 + 4 * expert)
    assert token_macs(shapes) == token, (token_macs(shapes), token)
    assert score_macs(shapes) == 8 * 512 * 1024 * 1025 // 2
    assert forward_macs(shapes) == (token * 1024 + score_macs(shapes)
                                    + 2048 * 18_992)
    assert step_macs(shapes) == 5 * 16 * forward_macs(shapes)
    assert expert_macs(shapes, 320) == 320 * 3_145_728
    assert gdn_layers(shapes) == 3 and expert_layers(shapes) == 4
    from benchmark import costs
    cost = costs.step_cost(dict(family="qwen3_next_q", shapes=shapes))
    assert cost["flops"] == 2 * step_macs(shapes)
    assert cost["bytes"] == 2 * 16 * 2048 + param_count(shapes) * 4 * 8


if __name__ == "__main__":
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    self_check()
    print("costs_qwen3_next_q self-check passed")
