"""Operations of the ``lfm2_moe_q`` family (LFM2-8B-A1B's layer stack as
the Q-network of Ape-X DQN, one chip's share of each layer), from shapes:
every count is over the HELD widths (``shapes["model"]``: experts and
vocabulary as this chip holds them; mixers and dense feed-forward whole).

A multiply-add is one MAC; ``costs.py`` doubles them.  Routed experts are
counted at their EXPECTED local share, ``k x held / published`` experts a
token (1 here); ``expert_macs(pairs)`` counts the pairs a run really had.
Attention is counted causal (``T (T + 1) / 2`` score pairs a head).  The
short convolution is counted on every token: its two projections, its two
gates (a product each, half a MAC) and its ``K`` taps.  Rematerialised
forward passes do not count in ``step_macs`` (what the step is for); they
do in ``EXPERT_UNITS`` / ``SHORTCONV_UNITS`` (what a block was asked to
do, for its own roofline).

``python benchmark/costs_lfm2_moe_q.py`` runs the self-check against the
hand-worked numbers of the table in PERF.md section 4.
"""

from __future__ import annotations


def _kinds(shapes: dict) -> dict[str, int]:
    """Held layers by mixer and by feed-forward."""
    m = shapes["model"]
    kinds = m["layer_types"]
    dense = m["num_dense_layers"]
    return {"conv": kinds.count("conv"),
            "attention": kinds.count("full_attention"),
            "dense": dense, "experts": len(kinds) - dense}


def _shortconv_params(m: dict) -> int:
    """``W_in`` (``[B | C | x]``), the taps and ``W_out``."""
    d = m["hidden_size"]
    return 3 * d * d + m["conv_L_cache"] * d + d * d


def _attention_matrix_params(m: dict) -> int:
    """``W_q``, ``W_k``, ``W_v``, ``W_o``."""
    d, hd = m["hidden_size"], m["head_dim"]
    return d * hd * (2 * m["num_attention_heads"]
                     + 2 * m["num_key_value_heads"])


def _dense_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def _expert_params(m: dict) -> int:
    """One routed SwiGLU expert: gate, up, down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def shortconv_macs(shapes: dict, tokens: float) -> float:
    """The short convolution's work for ``tokens`` positions, one layer,
    one forward pass: ``W_in`` and ``W_out`` (``4 d^2``), the ``K`` taps a
    channel, and the gates ``B * x`` and ``C * z`` (one product each a
    channel, half a MAC)."""
    m = shapes["model"]
    d = m["hidden_size"]
    return tokens * (4 * d * d + m["conv_L_cache"] * d + d)


def shortconv_bytes(shapes: dict, tokens: float, passes: float) -> float:
    """The least any implementation of the block moves for ``tokens``
    positions a pass, over ``passes`` forward-sized passes of one layer:
    its float32 weights once a pass, ``h`` in (the compute dtype, 2 bytes)
    and ``out`` out (float32: it joins the float32 residual stream)."""
    m = shapes["model"]
    d = m["hidden_size"]
    return passes * (4 * _shortconv_params(m) + tokens * (2 * d + 4 * d))


def token_macs(shapes: dict) -> float:
    """Products a token, all held layers: the matrices, the convolution,
    the router, routed experts at their expected local share."""
    m = shapes["model"]
    d, kinds = m["hidden_size"], _kinds(shapes)
    routed = (m["num_experts_per_tok"] * m["num_experts"]
              * _expert_params(m)) / shapes["n_routed_published"]
    expert = d * shapes["n_routed_published"] + routed
    return (kinds["conv"] * shortconv_macs(shapes, 1)
            + kinds["attention"] * _attention_matrix_params(m)
            + kinds["dense"] * _dense_params(m)
            + kinds["experts"] * expert)


def score_macs(shapes: dict) -> int:
    """Causal attention of one context: q.k and p.v over the head width a
    (query, key) pair, ``T (T + 1) / 2`` pairs a query head, every
    attention layer."""
    m, t = shapes["model"], shapes["context"]
    return (_kinds(shapes)["attention"] * m["num_attention_heads"] * 2
            * m["head_dim"] * t * (t + 1) // 2)


def forward_macs(shapes: dict) -> float:
    """One context through the torso; the tied head once (last
    position)."""
    m = shapes["model"]
    return (token_macs(shapes) * shapes["context"] + score_macs(shapes)
            + m["hidden_size"] * m["vocab_size"])


def param_count(shapes: dict) -> int:
    """Every parameter the learner holds (PERF.md section 4's table): the
    matrices; a conv layer's taps; an attention layer's two head norms; the
    router's bias; two norms a layer; the tied embedding once and the final
    norm."""
    m = shapes["model"]
    d, kinds = m["hidden_size"], _kinds(shapes)
    expert = (d * shapes["n_routed_published"]
              + shapes["n_routed_published"]
              + m["num_experts"] * _expert_params(m))
    return (kinds["conv"] * _shortconv_params(m)
            + kinds["attention"] * (_attention_matrix_params(m)
                                    + 2 * m["head_dim"])
            + kinds["dense"] * _dense_params(m)
            + kinds["experts"] * expert
            + len(m["layer_types"]) * 2 * d + m["vocab_size"] * d + d)


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
#: the same of a short-convolution layer, which every pass runs on every
#: token: the differentiated pass's forward, the same made again where the
#: layer is rematerialised and a backward of twice a forward, and one
#: forward each for the next-state and target passes
SHORTCONV_UNITS = sum(EXPERT_UNITS.values())


def shortconv_layers(shapes: dict) -> int:
    return _kinds(shapes)["conv"]


def expert_layers(shapes: dict) -> int:
    """The layers after the leading dense ones."""
    return _kinds(shapes)["experts"]


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
    """Hand-worked (PERF.md section 4): a conv layer 2048 x 6144 + 3 x 2048
    + 2048 x 2048 = 16,783,360; attention 2048 x 64 x (64 + 16) =
    10,485,760 + 128 = 10,485,888; dense 3 x 2048 x 7168 = 44,040,192; an
    expert block 2048 x 32 + 32 + 8 x 11,010,048 = 88,145,952; two norms
    4,096; the tied vocabulary 16,384 x 2048 + 2048 = 33,556,480; 1 dense +
    4 expert layers: 507,820,288.  A token's work 168.1 M (167.8 M without
    the routers' 0.26 M): four short convs of 16,785,408 (67.14 M), dense
    44.04 M, four expert blocks of 65,536 + 11,010,048 (44.30 M),
    attention's matrices 10.49 M and its scores 32 x 64 x 1025 = 2.10 M a
    token at 1,024."""
    import json
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "configs", "lfm2_8b_a1b_q_ep4.json")) as f:
        shapes = json.load(f)["shapes"]
    m = shapes["model"]
    assert _shortconv_params(m) == 16_783_360
    assert _attention_matrix_params(m) == 10_485_760
    assert _dense_params(m) == 44_040_192
    assert _expert_params(m) == 11_010_048
    assert param_count(shapes) == 507_820_288, param_count(shapes)
    assert shortconv_macs(shapes, 1) == 4 * 2048 ** 2 + 4 * 2048
    assert shortconv_bytes(shapes, 1, 1) == 4 * 16_783_360 + 6 * 2048
    token = (4 * 16_785_408 + 10_485_760 + 44_040_192
             + 4 * (65_536 + 11_010_048))
    assert token_macs(shapes) == token, (token_macs(shapes), token)
    assert score_macs(shapes) == 32 * 128 * 1024 * 1025 // 2
    assert forward_macs(shapes) == (token * 1024 + score_macs(shapes)
                                    + 2048 * 16_384)
    assert 168.0e6 < forward_macs(shapes) / 1024 < 168.2e6
    assert step_macs(shapes) == 5 * 16 * forward_macs(shapes)
    assert expert_macs(shapes, 2048) == 2048 * 11_010_048
    assert shortconv_layers(shapes) == 4 and expert_layers(shapes) == 4
    from benchmark import costs
    cost = costs.step_cost(dict(family="lfm2_moe_q", shapes=shapes))
    assert cost["flops"] == 2 * step_macs(shapes)
    assert cost["bytes"] == 2 * 16 * 2048 + param_count(shapes) * 4 * 8


if __name__ == "__main__":
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    self_check()
    print("costs_lfm2_moe_q self-check passed")
