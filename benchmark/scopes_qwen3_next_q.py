"""The ``qwen3_next_q`` family's table for ``family_scopes.py``: the trace
scopes of ``apex_tpu/models/qwen3_next.py`` and of the expert layer it
shares with the other token torsos."""

#: ``gdn`` a Gated DeltaNet mixer (projections, gated norm), inside it
#: ``conv`` (the causal depthwise convolution and its ``silu``) and
#: ``delta`` (the chunked delta rule: unit vectors, decays, the products
#: inside a chunk, the triangular inverse, the carried state, the output);
#: ``gated_attention`` an attention layer's projections, norms, RoPE, kernel
#: and gate; the rest as the other families'
SCOPES = ("embed", "gdn", "conv", "delta", "gated_attention", "router",
          "experts", "shared_expert", "q_head")
#: operation-name prefix -> scope: the grouped products XLA:TPU names
#: itself and leaves without a scope path
KERNELS = {"ragged-dot": "experts"}
#: outer scope -> what lies inside it
INSIDE = {"gdn": ("conv", "delta"), "router": ("experts",)}
