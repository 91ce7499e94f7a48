"""The ``lfm2_moe_q`` family's table for ``family_scopes.py``: the trace
scopes of ``apex_tpu/models/lfm2_moe.py`` and of the expert layer it
shares with the other token torsos."""

#: ``shortconv`` a double-gated short convolution (the in-projection, the
#: gates, the out-projection), inside it ``conv`` (the three-tap sum);
#: ``qk_norm_attention`` an attention layer's projections, head norms,
#: RoPE, kernel and out-projection; ``dense_ffn`` the leading dense
#: layer's SwiGLU; the rest as the other families' (no shared expert)
SCOPES = ("embed", "shortconv", "conv", "qk_norm_attention", "dense_ffn",
          "router", "experts", "q_head")
#: operation-name prefix -> scope: the grouped products XLA:TPU names
#: itself and leaves without a scope path
KERNELS = {"ragged-dot": "experts"}
#: outer scope -> what lies inside it
INSIDE = {"shortconv": ("conv",), "router": ("experts",)}
