"""``apex_tpu.ops.attention``: the fused kernel against the plain path, and
the rule that picks between them.

The kernel runs here in Pallas's TPU interpret mode (the same kernel body,
interpreted on the CPU) at shapes that are eligible but small: one where a
row of blocks is the whole context (the kernel's single-step form) and one
where the online softmax walks several blocks with some above the diagonal
skipped, each at a head width of 128 and of 64.
``tests/test_glm4_moe_lite.py`` holds the compiled program.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from apex_tpu.models import glm4_moe_lite as glm  # noqa: E402
from apex_tpu.ops import attention  # noqa: E402

#: ``[b, H, T, d]``: one block a row; several blocks a row; both again at
#: LFM2-8B-A1B's head width of 64, which the kernel slices its 128-lane
#: row statistics to
SHAPES = {"one_block": (2, 2, 256, 128),
          "online": (1, 2, 2 * attention.BLOCK, 128),
          "one_block_narrow": (2, 2, 256, 64),
          "online_narrow": (1, 2, 2 * attention.BLOCK, 64)}
#: largest distance allowed, as a share of the largest plain value: float32
#: differs by the order of its sums alone; bfloat16 operands round the
#: probabilities at another point (after the row sum here, before it there)
TOLERANCE = {"float32": 1e-5, "bfloat16": 2e-2}


@functools.cache
def both_paths(shape_name: str, dtype: str):
    """``{name: (fused, plain)}`` for the output and the three gradients."""
    shape = SHAPES[shape_name]
    rng = np.random.default_rng(7)
    q, k, v, do = (jnp.asarray(rng.normal(0, 1, shape), dtype)
                   for _ in range(4))
    scale = shape[-1] ** -0.5

    def run(fn):
        def loss(q, k, v):
            out = fn(q, k, v, scale)
            return (out.astype(jnp.float32) * do.astype(jnp.float32)).sum(), \
                out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                             has_aux=True)(q, k, v)
        return dict(zip(("out", "dq", "dk", "dv"), (out, *grads)))

    with pltpu.force_tpu_interpret_mode():
        fused = run(attention.fused)
    plain = run(attention.plain)
    return {n: (np.asarray(fused[n], np.float32),
                np.asarray(plain[n], np.float32)) for n in plain}


@pytest.mark.parametrize("name", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
@pytest.mark.parametrize("shape_name", sorted(SHAPES))
def test_kernel_equals_the_plain_path(shape_name, dtype, name):
    assert attention.kernel_eligible(*SHAPES[shape_name][2:],
                                     SHAPES[shape_name][3])
    fused, plain = both_paths(shape_name, dtype)[name]
    assert fused.shape == plain.shape and np.isfinite(fused).all()
    assert np.abs(fused - plain).max() <= \
        TOLERANCE[dtype] * np.abs(plain).max()


EP8, TINY = glm.PRESETS["glm47_flash_ep8"], glm.PRESETS["glm47_flash_tiny"]


def widths(preset: dict) -> tuple[int, int, int]:
    return (preset["context"],
            preset["qk_nope_head_dim"] + preset["qk_rope_head_dim"],
            preset["v_head_dim"])


@pytest.mark.parametrize("case,context,qk,v,platform,fused", [
    ("published widths on a TPU", *widths(EP8), "tpu", 1),
    ("published widths on a CPU", *widths(EP8), "cpu", 0),
    ("the toy on a TPU", *widths(TINY), "tpu", 0),
    ("the toy on a CPU", *widths(TINY), "cpu", 0),
    ("a context of 1,000", 1000, 256, 256, "tpu", 0),
    ("a context the blocks do not divide", 1024 + 128, 256, 256, "tpu", 0),
    ("q.k narrower than v", 1024, 128, 256, "tpu", 0),
    ("a head width of 192", 1024, 192, 192, "tpu", 0),
    ("LFM2's head width of 64", 1024, 64, 64, "tpu", 1),
    ("LFM2's head width on a CPU", 1024, 64, 64, "cpu", 0),
    ("a head width of 32", 1024, 32, 32, "tpu", 0),
    ("q.k of 64 and v of 128", 1024, 64, 128, "tpu", 0),
    ("a short eligible context", 256, 128, 128, "tpu", 1),
    ("a short eligible context on a GPU", 256, 128, 128, "gpu", 0),
])
def test_the_rule_that_picks_the_kernel(case, context, qk, v, platform,
                                        fused):
    path = attention.attention_path(context, qk, v, platform)
    assert path["fused"] == fused, case
    assert (context, qk, v, platform) == (
        path["context"], path["qk_head_dim"], path["v_head_dim"],
        path["platform"])
    # block sizes are named where the kernel runs, and only there
    assert path.get("block") == (min(attention.BLOCK, context) if fused
                                 else None)
    if platform == "tpu":
        assert attention.kernel_eligible(context, qk, v) == bool(fused)


def test_an_eligible_shape_takes_the_plain_path_on_this_cpu(monkeypatch):
    """``causal_attention`` asks the platform the program is lowered for:
    compiled here, for a CPU, an eligible shape never reaches the kernel
    (it could not run: no interpret mode is on)."""
    def never(*_a, **_k):
        raise AssertionError("the kernel was lowered for a CPU")
    shape = SHAPES["one_block"]
    q = jnp.ones(shape, jnp.bfloat16)
    want = attention.plain(q, q, q, 0.125)
    got = jax.jit(lambda q: attention.causal_attention(q, q, q, 0.125))(q)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    # and an ineligible one does not even offer it
    monkeypatch.setattr(attention, "fused", never)
    small = jnp.ones((1, 2, 16, 16), jnp.float32)
    jax.grad(lambda x: attention.causal_attention(x, x, x, 0.25).sum())(small)
