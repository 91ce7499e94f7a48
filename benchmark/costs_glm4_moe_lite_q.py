"""Operations of the ``glm4_moe_lite_q`` family (GLM-4.7-Flash's block as
the Q-network of Ape-X DQN, one chip's share of each layer), from shapes.

A multiply-add is one MAC; ``costs.py`` doubles them.  Routed experts are
counted at their EXPECTED local share, ``k x held / published`` experts a
token (0.5 here), because which pairs land on the held experts is the
router's to decide at run time; ``expert_macs(pairs)`` counts the pairs a
run really had (``experts_roofline``).  Attention is counted causal
(``T (T + 1) / 2`` score pairs a head): what the algorithm needs, whatever
an implementation computes and masks.  Rematerialised forward passes do not
count in ``step_macs`` (what the step is for); they do in
``EXPERT_UNITS`` (what the grouped kernel was asked to do, for its own
roofline).

``python benchmark/costs_glm4_moe_lite_q.py`` runs the self-check against
the hand-worked numbers of ISSUE 29's table.
"""

from __future__ import annotations


def _mla_macs(m: dict) -> int:
    d, nh = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (d * m["q_lora_rank"] + m["q_lora_rank"] * nh * qk
            + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * nh * (m["qk_nope_head_dim"]
                                        + m["v_head_dim"])
            + nh * m["v_head_dim"] * d)


def _expert_params(m: dict) -> int:
    """One SwiGLU expert: gate, up, down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def _layers(shapes: dict) -> tuple[int, int]:
    m = shapes["model"]
    dense = m["first_k_dense_replace"]
    return dense, m["num_hidden_layers"] - dense


def token_macs(shapes: dict) -> int:
    """Matrix products a token, all layers, routed experts at their
    expected local share."""
    m = shapes["model"]
    d = m["hidden_size"]
    dense, expert = _layers(shapes)
    routed = (m["num_experts_per_tok"] * m["n_routed_experts"]
              * _expert_params(m)) // shapes["n_routed_published"]
    return (dense * (_mla_macs(m) + 3 * d * m["intermediate_size"])
            + expert * (_mla_macs(m) + d * shapes["n_routed_published"]
                        + m["n_shared_experts"] * _expert_params(m)
                        + routed))


def score_macs(shapes: dict) -> int:
    """Causal attention of one context: q.k over 256 and p.v over 256 a
    (query, key) pair, ``T (T + 1) / 2`` pairs a head, every layer."""
    m, t = shapes["model"], shapes["context"]
    per_pair = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                + m["v_head_dim"])
    return (m["num_hidden_layers"] * m["num_attention_heads"] * per_pair
            * t * (t + 1) // 2)


def forward_macs(shapes: dict) -> int:
    """One context through the torso; the head once (last position)."""
    m = shapes["model"]
    return (token_macs(shapes) * shapes["context"] + score_macs(shapes)
            + m["hidden_size"] * m["vocab_size"])


def matrix_param_count(shapes: dict) -> int:
    """Parameters in matrices (ISSUE 29's table: gains and the router's
    bias not counted)."""
    m = shapes["model"]
    d = m["hidden_size"]
    dense, expert = _layers(shapes)
    return (dense * (_mla_macs(m) + 3 * d * m["intermediate_size"])
            + expert * (_mla_macs(m) + d * shapes["n_routed_published"]
                        + (m["n_shared_experts"] + m["n_routed_experts"])
                        * _expert_params(m))
            + 2 * m["vocab_size"] * d)


def param_count(shapes: dict) -> int:
    """Every parameter the learner holds: the matrices, the norm gains (two
    a layer, two in each attention block, one before the head) and the
    router's bias."""
    m = shapes["model"]
    _dense, expert = _layers(shapes)
    gains = (m["num_hidden_layers"] * (2 * m["hidden_size"]
                                       + m["q_lora_rank"]
                                       + m["kv_lora_rank"])
             + m["hidden_size"])
    return (matrix_param_count(shapes) + gains
            + expert * shapes["n_routed_published"])


def step_macs(shapes: dict) -> int:
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


#: forward-sized runs of an expert layer's three grouped products that an
#: update enqueues, by the pass whose routing counter says how many pairs
#: they served.  The differentiated pass: its forward, the forward made
#: again when the layer is rematerialised, and a backward of twice a
#: forward (the layer's ``nn.remat`` and the round's own ``jax.checkpoint``
#: would make it three forwards; the second is dead code and goes).  So the
#: compiled update calls the grouped kernel 3 x 6 = 18 times an expert layer
#: (my AOT compile for a described v5e at the cell's shapes, PR 29;
#: ``tests/test_glm4_moe_lite.py`` counts them in the toy's).
EXPERT_UNITS = {"moe_local_pairs": 4, "moe_local_pairs_next": 1,
                "moe_local_pairs_target": 1}


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
    """Hand-worked (ISSUE 29): MLA 2048x768 + 768x5120 + 2048x576 +
    512x8960 + 5120x2048 = 21,757,952; dense layer + 3 x 2048 x 10240 =
    84,672,512; expert layer = MLA + 2048x64 + 9,437,184 + 8 x 9,437,184 =
    106,823,680; vocabulary 2 x 19,360 x 2048 = 79,298,560; in all
    591,265,792.  A token: 84,672,512 + 4 x (21,757,952 + 131,072 +
    9,437,184 + 0.5 x 9,437,184) = 228,851,712; scores 5 x 20 x 512 x
    1024 x 1025 / 2 = 26,869,760,000; head 39,649,280; a context
    261,253,562,368."""
    import json
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "configs", "glm47_flash_q_ep8.json")) as f:
        shapes = json.load(f)["shapes"]
    m = shapes["model"]
    assert _mla_macs(m) == 21_757_952, _mla_macs(m)
    assert _expert_params(m) == 9_437_184
    assert matrix_param_count(shapes) == 591_265_792, \
        matrix_param_count(shapes)
    assert param_count(shapes) == 591_265_792 + 29_184, param_count(shapes)
    assert token_macs(shapes) == 228_851_712, token_macs(shapes)
    assert score_macs(shapes) == 26_869_760_000, score_macs(shapes)
    assert forward_macs(shapes) == 261_253_562_368, forward_macs(shapes)
    assert step_macs(shapes) == 5 * 16 * 261_253_562_368
    assert expert_macs(shapes, 8192) == 8192 * 9_437_184
    from benchmark import costs
    cost = costs.step_cost(dict(family="glm4_moe_lite_q", shapes=shapes))
    assert cost["flops"] == 2 * step_macs(shapes)
    assert cost["bytes"] == 2 * 16 * 2048 + param_count(shapes) * 4 * 8


if __name__ == "__main__":
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    self_check()
    print("costs_glm4_moe_lite_q self-check passed")
