"""The Nemotron-H torso (apex_tpu/models/nemotron_h.py) against its plain
reference (benchmark/reference/nemotron_h_q.py), at the toy preset on the
CPU, with seeded weights drawn as the benchmark draws them.

The program computes the Mamba-2 scan chunked; the reference steps the
recurrence one position at a time.  (a) Q rows over contexts of 1, 2 and 4
chunks, (b) bfloat16 against float32's bound, (c) one learner update, (d)
rows of a batch are independent, (e) the shares of all ranks (mixer heads,
query heads, experts) add up to the uncut layer, (f) no pair is dropped
with two-matrix experts, (g) the presets hold what the issue counts, (h)
grouped queries reach the fused attention kernel, (i) the factory and the
CLI find the family by the preset's name.
"""

from __future__ import annotations

import functools
import math
import os
import re
import sys

import numpy as np
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from apex_tpu import models  # noqa: E402
from apex_tpu.models import (acting_params, learner_apply_fn,  # noqa: E402
                             make_q_network)
from apex_tpu.models import glm4_moe_lite as glm  # noqa: E402
from apex_tpu.models import nemotron_h as nh  # noqa: E402
from apex_tpu.ops import attention, grouped  # noqa: E402
from apex_tpu.ops.losses import double_dqn_loss, make_optimizer  # noqa: E402
from apex_tpu.training.learner import td_update  # noqa: E402
from apex_tpu.training.state import create_train_state  # noqa: E402
from benchmark import feed  # noqa: E402
from benchmark.reference import nemotron_h_q as ref  # noqa: E402

PRESET, BIG = "nemotron_h_tiny", "nemotron_twotower_ep16"
C = nh.PRESETS[PRESET]
B, T, V, D = 4, C["context"], C["vocab_held"], C["hidden_size"]
HP = dict(lr=6.25e-5, lr_decay_steps=1000, lr_decay_rate=0.99,
          rmsprop_decay=0.95, rmsprop_eps=1.5e-7, max_grad_norm=40.0,
          target_update_interval=2500)
M = dict(ref.model_of({"params": {"embedding": jnp.zeros((V, D))}}))


def model(dtype=jnp.float32, preset: str = PRESET, **kw):
    return make_q_network(dict(
        torso=preset, num_actions=nh.PRESETS[preset]["vocab_held"],
        compute_dtype=dtype, **kw))


def seeded(m, seed: int, t: int = T):
    shapes = jax.eval_shape(m.init, jax.random.key(0),
                            jnp.zeros((1, 2 * t), jnp.uint8))
    return feed.make_weights(shapes, seed, ref.init_rule)


def batch_of(seed: int, b: int = B, t: int = T):
    rng = np.random.default_rng(seed)
    return dict(
        obs=jnp.asarray(rng.integers(0, 256, (b, 2 * t), dtype=np.uint8)),
        next_obs=jnp.asarray(rng.integers(0, 256, (b, 2 * t),
                                          dtype=np.uint8)),
        action=jnp.asarray(rng.integers(0, V, b).astype(np.int32)),
        reward=jnp.asarray(rng.normal(0, 0.5, b).astype(np.float32)),
        discount=jnp.asarray(np.where(rng.random(b) < 0.25, 0.0,
                                      0.99 ** 3).astype(np.float32)))


@pytest.fixture(scope="module")
def params():
    return seeded(model(), 11)


def reference_q(params, obs, mode="f32"):
    with jax.default_matmul_precision("highest"):
        return ref.forward(params, obs, mode)


# -- (a) Q rows: the chunked scan against the recurrence ----------------------

@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_q_rows_equal_the_reference_in_float32(params, chunks):
    """Contexts of 1, 2 and 4 chunks of 8: the quadratic form alone, one
    carried state, several.  Float32 on both sides, so they differ by the
    order of their sums alone (a chunk's 8 products summed at once against
    8 steps of a recurrence): 1e-5 of a Q of order 1."""
    obs = batch_of(chunks, t=chunks * C["chunk_size"])["obs"]
    q = jax.jit(model().apply)(params, obs)
    want = reference_q(params, obs)
    assert q.shape == (B, V) and q.dtype == jnp.float32
    np.testing.assert_allclose(q, want, rtol=1e-5, atol=1e-5)


def test_the_recurrence_does_work_the_comparison_can_see():
    """At the seeded constants (``A`` 1..16, steps 0.001..0.1) what the
    state adds to ``y`` is no rounding error beside ``D x``: were the scan
    left out, or its state not carried, the comparisons above would see
    it."""
    rng = np.random.default_rng(3)
    h, p, g, n = 2, 16, 1, 16
    x = jnp.asarray(rng.normal(0, 1, (2, T, h, p)), jnp.float32)
    b_in, c_in = (jnp.asarray(rng.normal(0, 1, (2, T, g, n)), jnp.float32)
                  for _ in range(2))
    dt = jnp.asarray(np.geomspace(1e-3, 1e-1, h), jnp.float32) \
        * jnp.ones((2, T, h))
    a = -jnp.linspace(1.0, 16.0, h)
    args = (x, dt, a, b_in, c_in, jnp.ones(h))
    y = nh.ssd(*args, C["chunk_size"], jnp.float32)
    skip = x                                         # ``D x`` alone
    assert float(jnp.abs(y - skip).mean()) > 0.05 * float(jnp.abs(y).mean())
    # and the state crosses chunk boundaries: one chunk a context differs
    whole = nh.ssd(*args, T, jnp.float32)
    np.testing.assert_allclose(y, whole, rtol=1e-4, atol=1e-5)
    cut = jnp.concatenate([nh.ssd(*(v[:, i:i + 8] if v.ndim > 1 else v
                                    for v in args), 8, jnp.float32)
                           for i in range(0, T, 8)], axis=1)
    assert float(jnp.abs(cut - y).max()) > 1e-3


# -- (b) bfloat16 --------------------------------------------------------------

def test_q_rows_in_bfloat16_stay_near_the_reference(params):
    """bfloat16 operands: 8 bits of mantissa through five layers, a pick
    that flips where two router scores are close; Q is of order 1.  The
    stated tolerance: the mean distance under 3% of mean |Q| and no entry
    further than 25% of it; the reference with bfloat16 operands reads the
    same.  And float32's bound does NOT hold for it: a program that
    silently computed in the lower precision would be seen by (a)."""
    obs = batch_of(2)["obs"]
    q = jax.jit(model(jnp.bfloat16).apply)(params, obs)
    want, stated = reference_q(params, obs), reference_q(params, obs, "bf16")
    scale = float(jnp.abs(want).mean())
    for got in (q, stated):
        assert float(jnp.abs(got - want).mean()) < 0.03 * scale
        assert float(jnp.abs(got - want).max()) < 0.25 * scale
        assert float(jnp.abs(got - want).max()) > 1e-3 * scale
        assert not np.allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_acting_snapshot_keeps_the_scan_in_float32(params):
    """The acting snapshot multiplies the same bits from half the bytes,
    and what the scan and the convolution read in float32 stays so."""
    obs = batch_of(2)["obs"]
    m16 = model(jnp.bfloat16)
    snap = acting_params(m16, params)["params"]
    mamba = snap["layers_0"]["mamba"]
    assert mamba["in_proj"]["kernel"].dtype == jnp.bfloat16
    assert snap["layers_1"]["moe"]["experts_up"].dtype == jnp.bfloat16
    for name in ("A_log", "dt_bias", "D", "norm_scale", "conv_kernel",
                 "conv_bias"):
        assert mamba[name].dtype == jnp.float32, name
    assert snap["layers_1"]["moe"]["router_kernel"].dtype == jnp.float32
    np.testing.assert_array_equal(
        jax.jit(m16.apply)({"params": snap}, obs),
        jax.jit(m16.apply)(params, obs))


# -- (c) one update -----------------------------------------------------------

@functools.cache
def _one_update():
    m = model()
    p = seeded(m, 11)
    batch, weights = batch_of(3), jnp.linspace(0.5, 1.0, B)
    opt = make_optimizer()
    ts = create_train_state(m, opt, jax.random.key(0),
                            jnp.zeros((1, 2 * T), jnp.uint8))
    target = seeded(m, 12)
    ts = ts.replace(params=p, target_params=target, opt_state=opt.init(p))

    def loss_fn(q):
        return double_dqn_loss(learner_apply_fn(m), q, target, batch,
                               weights)

    new, prios, metrics = jax.jit(
        lambda ts: td_update(opt, 2500, ts, loss_fn, None))(ts)
    # the clipped gradient, as the harness reads it: RMSprop's first
    # moment after one step is (1 - decay) times it
    clipped = jax.tree.map(lambda mu: 20.0 * mu, new.opt_state[1][0].mu)
    state = dict(params=jax.tree.map(jnp.copy, p), target_params=target,
                 opt=ref.init_opt(p, HP), step=0)
    with jax.default_matmul_precision("highest"):
        want, out = ref.step(state, batch, weights, None, HP, "f32")
    return new, prios, metrics, clipped, want, out


def test_one_update_equals_the_reference_step():
    new, prios, metrics, _clipped, want, out = _one_update()
    assert float(metrics["loss"]) == pytest.approx(float(out["loss"]),
                                                   rel=1e-5)
    np.testing.assert_allclose(prios, out["priorities"], rtol=1e-4,
                               atol=1e-6)
    for (path, p), w in zip(jax.tree_util.tree_leaves_with_path(new.params),
                            jax.tree.leaves(want["params"])):
        np.testing.assert_allclose(p, w, rtol=1e-4, atol=1e-6,
                                   err_msg=str(path))
    # the routing counters of the three passes leave among the metrics
    pairs = B * T * C["pattern"].count("E") * C["num_experts_per_tok"]
    for suffix in ("", "_next", "_target"):
        assert 0 < float(metrics["moe_local_pairs" + suffix]) < pairs
        assert float(metrics["moe_load_max_over_mean" + suffix]) >= 1.0
        overflow = float(metrics["moe_overflow_rounds" + suffix])
        assert overflow == int(overflow)
        assert 0 <= overflow <= 3 * C["pattern"].count("E")


@pytest.mark.parametrize("part", ["mamba", "attention", "moe", "embedding"])
def test_gradients_of_the_td_loss_equal_the_references(part):
    """The backward pass of the chunked scan (autodiff through the
    quadratic form and the carried state) against that of the recurrence,
    and the other parts' beside it: float32 both, sums in another order
    through five layers and a clip: 2e-3 of a leaf's entries."""
    _new, _prios, _metrics, clipped, _want, out = _one_update()
    seen = 0
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(clipped),
                            jax.tree.leaves(out["grads"])):
        if part not in jax.tree_util.keystr(path):
            continue
        seen += 1
        if "router_bias" not in jax.tree_util.keystr(path):  # only selects
            assert float(jnp.abs(w).max()) > 0, path   # a gradient reached it
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-6,
                                   err_msg=str(path))
    assert seen


# -- (d) rows of a batch ------------------------------------------------------

def test_rows_of_a_batch_are_independent(params):
    """The state is nought at every context's start: a row reads the same
    alone (to the order of a product's sums, which a batch of another size
    changes: 1e-5) and, bit for bit, whatever stands in the row before
    it."""
    obs = batch_of(5)["obs"]
    apply = jax.jit(model().apply)
    q = apply(params, obs)
    for i in range(B):
        np.testing.assert_allclose(apply(params, obs[i:i + 1])[0], q[i],
                                   rtol=1e-5, atol=1e-5)
    other = obs.at[0].set(batch_of(6)["obs"][0])
    np.testing.assert_array_equal(apply(params, other)[1:], q[1:])
    assert float(jnp.abs(apply(params, other)[0] - q[0]).max()) > 1e-3


# -- (e), (f) the shares ---------------------------------------------------------

#: the toy with every head held: the uncut layer
UNCUT = dict(C, mamba_heads_held=C["mamba_num_heads"],
             attention_heads_held=C["num_attention_heads"])


def uncut_layer(kind: str, seed: int):
    """An uncut ``M`` or ``*`` layer, its seeded parameters, and the
    module of one rank's share."""
    frozen = lambda c: tuple(sorted(c.items()))         # noqa: E731
    whole = nh.Layer(jnp.float32, frozen(UNCUT), kind)
    x = jax.random.normal(jax.random.key(seed), (B, T, D))
    shapes = jax.eval_shape(whole.init, jax.random.key(0), x)
    p = feed.make_weights(shapes, seed, ref.init_rule)["params"]
    return whole, nh.Layer(jnp.float32, frozen(C), kind), p, x


@pytest.mark.parametrize("kind,part", [("M", "mamba"), ("*", "attention")])
def test_the_head_shares_of_both_ranks_add_up_to_the_uncut_mixer(kind, part):
    """2 ranks x half the heads (whole groups, whole key/value heads): the
    parts the two ranks add to the residual stream are what the uncut
    layer adds, and the uncut reference's; one rank alone is the reference
    given that rank's share."""
    whole, share, p, x = uncut_layer(kind, 31)
    out, _ = whole.apply({"params": p}, x)
    u = jax.vmap(lambda r: ref.rms_norm(r, p["norm"]["scale"],
                                        M["norm_eps"]))(x)
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda r: ref.PARTS[part](r, p[part], M, "f32"))(u)
    np.testing.assert_allclose(out - x, want, rtol=1e-4, atol=1e-5)
    total = jnp.zeros_like(x)
    for rank in range(2):
        cut = nh.share_of_layer(kind, p, C, rank)
        got, _ = share.apply({"params": cut}, x)
        total = total + (got - x)
        with jax.default_matmul_precision("highest"):
            alone = jax.vmap(lambda r: ref.PARTS[part](r, cut[part], M,
                                                       "f32"))(u)
        np.testing.assert_allclose(got - x, alone, rtol=1e-4, atol=1e-5)
        assert float(jnp.abs(got - x).mean()) > 0.1 * float(
            jnp.abs(want).mean())                   # no rank adds nothing
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def moe_layer(held: int, rank: int):
    return glm.MoE(jnp.float32, C["moe_intermediate_size"],
                   C["n_routed_experts"], held, rank,
                   C["num_experts_per_tok"], C["routed_scaling_factor"],
                   "relu2", C["moe_shared_expert_intermediate_size"])


def uncut_moe_params(seed: int):
    shapes = jax.eval_shape(moe_layer(C["n_routed_experts"], 0).init,
                            jax.random.key(0), jnp.zeros((1, T, D)))
    return feed.make_weights(shapes, seed, ref.init_rule)["params"]


def rank_slice(p: dict, rank: int, held: int) -> dict:
    return {"params": {
        k: (v[rank * held:(rank + 1) * held] if k.startswith("experts_")
            else v) for k, v in p.items()}}


def reference_moe(p, h):
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda x: ref.moe(x, p, M, "f32"))(h)


def test_the_expert_shares_of_all_ranks_add_up_to_the_uncut_layer(
        grouped_rule):
    """4 ranks x 2 of 8 two-matrix experts: the routed parts of every
    rank, with the shared expert (of its own width) counted once, are the
    uncut reference's layer output."""
    p = uncut_moe_params(21)
    assert set(p) == {"shared", "router_kernel", "router_bias",
                      "experts_up", "experts_down"}
    assert p["shared"]["up"].shape == (
        D, C["moe_shared_expert_intermediate_size"])
    h = jax.random.normal(jax.random.key(5), (B, T, D))
    held = 2
    shared = glm.FeedForward(
        jnp.float32, C["moe_shared_expert_intermediate_size"], 0,
        "relu2").apply({"params": p["shared"]}, h)
    total, pairs = shared, 0
    for rank in range(C["n_routed_experts"] // held):
        out, (counts, _) = moe_layer(held, rank).apply(
            rank_slice(p, rank, held), h)
        total = total + (out - shared)
        pairs += int(counts.sum())
    assert pairs == B * T * C["num_experts_per_tok"]    # every pair, once
    np.testing.assert_allclose(total, reference_moe(p, h), rtol=1e-4,
                               atol=1e-5)


def test_no_pair_is_dropped_with_two_matrix_experts(grouped_rule):
    """A router bias that sends every token to experts 0 and 1, both held
    here: all ``N k`` pairs land on this rank, every round runs, the layer
    still equals the reference and the gradient reaches both matrices of
    every held expert."""
    p = uncut_moe_params(22)
    p = dict(p, router_bias=p["router_bias"].at[:2].add(10.0))
    h = jax.random.normal(jax.random.key(6), (B, T, D))
    held = 2
    cut = rank_slice(p, 0, held)
    out, (counts, overflow) = moe_layer(held, 0).apply(cut, h)
    np.testing.assert_array_equal(counts, [B * T, B * T])
    assert int(overflow) == 3       # a quarter of the pairs a round
    np.testing.assert_allclose(out, reference_moe(cut["params"], h),
                               rtol=1e-4, atol=1e-5)
    g = jax.grad(lambda q: moe_layer(held, 0).apply(
        {"params": q}, h)[0].sum())(cut["params"])
    assert all(float(jnp.abs(g[k][e]).max()) > 0
               for k in ("experts_up", "experts_down") for e in range(held))


def _round(act: str, case: str):
    """A round's operands at widths no tile of 16 divides (40 x 24): rows
    sorted by expert, ``case`` says what is special about the groups."""
    d, f, e, rows = 40, 24, 4, 32
    sizes = {"rows_past_the_last_group": [5, 9, 3, 7],
             "an_empty_group": [11, 0, 9, 12]}[case]
    keys = jax.random.split(jax.random.key(7), 5)
    live = (jnp.arange(rows) < sum(sizes))[:, None]
    n = 3 if act == "swiglu" else 2
    kernels = [0.3 * jax.random.normal(k, (e, f, d) if i == n - 1
                                       else (e, d, f))
               for i, k in enumerate(keys[:n])]
    xs = jax.random.normal(keys[3], (rows, d))
    cot = jax.random.normal(keys[4], (rows, d))
    return (glm.MoE(jnp.float32, f, act=act), xs,
            jnp.array(sizes, jnp.int32), live, kernels, cot)


@pytest.mark.parametrize("case", ["rows_past_the_last_group",
                                  "an_empty_group"])
@pytest.mark.parametrize("act", ["relu2", "swiglu"])
def test_the_tiled_products_equal_the_plain_ones(act, case,
                                                 interpreted_kernel):
    """The tiled kernel handed zero-padded operands (40 x 24 in tiles of
    16: 48 x 32) gives ``ragged_dot``'s output and its gradients, of the
    rows and of every stacked kernel, at the shapes as they were: the
    zeros add nothing and the cuts take nothing."""
    layer, xs, sizes, live, kernels, cot = _round(act, case)

    def run(tiles):
        def loss(xs, *kernels):
            y = layer.experts(xs, sizes, live, *kernels, tiles=tiles)
            return jnp.sum(y * cot), y
        (_, y), grads = jax.value_and_grad(
            loss, argnums=tuple(range(1 + len(kernels))), has_aux=True)(
                xs, *kernels)
        return y, grads

    want, want_grads = run(None)
    got, got_grads = run(((16, 48), (16, 32)))
    assert got.shape == want.shape and float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(got[int(sizes.sum()):], 0.0)
    for g, w in zip(got_grads, want_grads):
        assert g.shape == w.shape and float(jnp.abs(w).max()) > 0
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("hidden,width,platform,impl,handed,tiles", [
    # the published widths: 3 x 896 as it stands, 1,856 -> 3 x 640
    (2688, 1856, "tpu", "megablox_gmm", (2688, 1920), (896, 640)),
    (2048, 1536, "tpu", "ragged_dot", (2048, 1536), (512, 512)),  # GLM's
    (2688, 384, "tpu", "ragged_dot", (2688, 384), (128, 128)),    # < 512
    (64, 32, "tpu", "ragged_dot", (64, 32), (128, 128)),          # the toy
    (2688, 1856, "cpu", "ragged_dot", (2688, 1856), (0, 0))])     # no TPU
def test_the_grouped_rule_leaves_alone_what_a_tile_divides(
        hidden, width, platform, impl, handed, tiles):
    said = grouped.grouped_path(hidden, width, platform)
    assert said == {"hidden": hidden, "width": width, "platform": platform,
                    "hidden_handed": handed[0], "width_handed": handed[1],
                    "tile_k": tiles[0], "tile_n": tiles[1], "impl": impl}
    assert (grouped.plan(hidden, width, 24576, platform) is None) == \
        (impl == "ragged_dot")
    # rows no tile of 128 divides: the kernel cannot take them
    assert grouped.plan(hidden, width, 24576 + 64, platform) is None


# -- (g) the presets ------------------------------------------------------------

def test_presets_hold_what_the_issue_counts():
    big = model(jnp.bfloat16, BIG)
    c = nh.PRESETS[BIG]
    shapes = jax.eval_shape(big.init, jax.random.key(0),
                            jnp.zeros((1, 2 * c["context"]), jnp.uint8))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 577_780_864
    assert nh.param_count(nh.PRESETS[BIG]) == 577_780_864
    by_kind = {kind: sum(x.size for x in jax.tree.leaves(
        shapes["params"][f"layers_{c['pattern'].index(kind)}"]))
        for kind in "M*E"}
    assert by_kind == {"M": 19_373_792, "*": 11_700_864, "E": 100_125_440}
    assert nh.held_widths(c) == dict(mamba_heads=32, groups=4,
                                     attn_heads=16, kv_heads=1)
    tiny = jax.eval_shape(model().init, jax.random.key(0),
                          jnp.zeros((1, 2 * T), jnp.uint8))
    assert sum(x.size for x in jax.tree.leaves(tiny)) == \
        nh.param_count(C)


def common_direction(params, obs):
    """How long the mean of the unit token vectors is where each expert
    layer's router reads them (the reference's own layers): 0 where
    tokens share no direction, 1 where they are one vector."""
    p, out = params["params"], []
    b = np.asarray(obs).reshape(obs.shape[0], -1, 2).astype(np.int32)
    x = p["embedding"][(b[..., 0] + 256 * b[..., 1]) % V]
    for i in range(len(C["pattern"])):
        p_i = p[f"layers_{i}"]
        if "moe" in p_i:
            u = ref.rms_norm(x, p_i["norm"]["scale"], M["norm_eps"])
            u = u / jnp.linalg.norm(u, axis=-1, keepdims=True)
            out.append(float(jnp.linalg.norm(u.mean((0, 1)))))
        x = jax.vmap(lambda r: ref.layer(r, p_i, M, "f32"))(x)
    return out


def test_seeded_weights_leave_the_routers_a_token_not_a_common_vector():
    """``init_rule`` draws the embedding as a unit table times
    ``sqrt(hidden)``.  By the general rule (``N(0, 2 / rows)``: the
    vocabulary taken for a fan-in) a token's own vector is lost beside
    the first part's output, and random ``relu^2`` experts add one and the
    same vector to every token (``E[relu(z)^2] > 0``): the routers then
    score that vector, which at published widths gave the busiest of 128
    experts 3-7.6 times the mean load (PR 33, ``PERF.md`` section 6).
    At the toy's width the rule's rows are 8, not 52, so the effect is a
    part of the chip's; it is there, and larger at the deeper layer."""
    assert ref.init_rule(("params", "embedding"), (V, D)) == (
        "normal", math.sqrt(D))
    m = model()
    shapes = jax.eval_shape(m.init, jax.random.key(0),
                            jnp.zeros((1, 2 * T), jnp.uint8))
    obs = batch_of(5, b=64)["obs"]
    by_rule = common_direction(seeded(m, 7), obs)
    general = common_direction(feed.make_weights(
        shapes, 7, lambda path, shape: None if path[-1] == "embedding"
        else ref.init_rule(path, shape)), obs)
    assert len(by_rule) == C["pattern"].count("E") == 2
    assert all(r < 0.75 * g for r, g in zip(by_rule, general)), (
        by_rule, general)
    assert general[-1] > 0.35 and by_rule[-1] < 0.3


def test_torso_layout_says_what_the_chip_holds():
    assert model(jnp.bfloat16, BIG).torso_layout() == {
        "pattern": "MEMEM*EME", "mamba_heads": "32/64", "groups": "4/8",
        "attn_heads": "16/32", "kv_heads": "1/2", "experts": "8/128",
        "expert_rank": 0, "chunk": 128, "ssd_impl": "xla",
        "params": 577_780_864}
    assert model(jnp.bfloat16, BIG).attention_path("tpu")["fused"] == 1
    assert model().attention_path("tpu")["fused"] == 0


#: the toy's gradient program as ``jax.jit(...).lower(...).as_text()`` gives
#: it, hashed at commit a38378e, before PR 35 gave the shared expert layer
#: its ``scoring`` and ``shared_gate`` fields, with jax 0.9.0 (the GLM
#: torso's case is ``tests/test_glm4_moe_lite.py``'s)
UPDATE_SHA256 = {"float32": "7b169c1e87301b2a", "bfloat16": "14f648587e1e36c3"}
UPDATE_JAX = "0.9.0"


@pytest.mark.skipif(jax.__version__ != UPDATE_JAX,
                    reason=f"hashed as jax {UPDATE_JAX} lowers it")
@pytest.mark.parametrize("dtype", sorted(UPDATE_SHA256))
def test_lowered_update_is_the_program_it_was(dtype):
    """A third family on the shared expert layer left this torso's
    lowered update text-identical.  A PR that changes this program on
    purpose records the new hash here and says so."""
    import hashlib
    m = model(jnp.dtype(dtype))
    p, target = seeded(m, 11), seeded(m, 12)
    rng = np.random.default_rng(3)
    batch = dict(
        obs=jnp.asarray(rng.integers(0, 256, (4, 2 * T), dtype=np.uint8)),
        next_obs=jnp.asarray(rng.integers(0, 256, (4, 2 * T),
                                          dtype=np.uint8)),
        action=jnp.asarray(rng.integers(0, V, 4).astype(np.int32)),
        reward=jnp.asarray(rng.normal(0, .5, 4).astype(np.float32)),
        discount=jnp.full((4,), 0.97, jnp.float32))

    def grads(params, target, batch, weights):
        (loss, _out), g = jax.value_and_grad(lambda q: double_dqn_loss(
            learner_apply_fn(m), q, target, batch, weights),
            has_aux=True)(params)
        return loss, g

    text = jax.jit(grads).lower(p, target, batch,
                                jnp.linspace(.5, 1., 4)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        UPDATE_SHA256[dtype]


# -- (h) grouped queries in the fused kernel -------------------------------------

@functools.cache
def gqa_both_paths():
    """16 query heads on 1 key/value head through
    ``attention.causal_attention``: the kernel (interpreted) and the plain
    path, output and the three gradients."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.default_rng(9)
    q, do = (jnp.asarray(rng.normal(0, 1, (1, 16, 256, 128)), jnp.float32)
             for _ in range(2))
    k, v = (jnp.asarray(rng.normal(0, 1, (1, 1, 256, 128)), jnp.float32)
            for _ in range(2))

    def run():
        def loss(q, k, v):
            out = attention.causal_attention(q, k, v, 128 ** -0.5)
            return (out * do).sum(), out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                             has_aux=True)(q, k, v)
        return dict(zip(("out", "dq", "dk", "dv"), (out, *grads)))

    plain = run()                       # a CPU program: the plain path
    with pytest.MonkeyPatch.context() as mp, \
            pltpu.force_tpu_interpret_mode():
        mp.setattr(jax.lax, "platform_dependent",
                   lambda *a, tpu, default: tpu(*a))
        fused = run()
    return {n: (np.asarray(fused[n]), np.asarray(plain[n])) for n in plain}


@pytest.mark.parametrize("name", ["out", "dq", "dk", "dv"])
def test_grouped_queries_agree_between_the_kernel_and_the_plain_path(name):
    fused, plain = gqa_both_paths()[name]
    assert fused.shape == plain.shape == (
        (1, 16, 256, 128) if name in ("out", "dq") else (1, 1, 256, 128))
    assert np.abs(fused - plain).max() <= 1e-5 * np.abs(plain).max()


# -- (i) found by the preset's name ---------------------------------------------

@pytest.mark.parametrize("torso,cls", [
    (PRESET, nh.NemotronHQ), (BIG, nh.NemotronHQ),
    ("glm47_flash_tiny", glm.Glm4MoeLiteQ)])
def test_the_factory_and_the_cli_find_a_family_by_its_preset(torso, cls):
    from apex_tpu.runtime.cli import build_parser, config_from_args
    assert torso in models.torso_names()
    preset = models.token_preset(torso)
    m = make_q_network(models.q_model_spec(
        torso, num_actions=preset["vocab_held"], obs_is_image=False,
        compute_dtype=jnp.bfloat16, scale_uint8=True))
    assert type(m) is cls and m.preset == torso
    cfg = config_from_args(build_parser().parse_args(
        ["--role", "apex", "--torso", torso, "--env-id", "ApexTokens-v0"]))
    assert cfg.learner.torso == torso
    assert (cfg.env.token_context, cfg.env.token_vocab) == (
        preset["context"], preset["vocab_held"])
    with pytest.raises(SystemExit, match="known are"):
        config_from_args(build_parser().parse_args(["--torso", "nope"]))


# -- (j) what the update compiled for the chip is made of -------------------------

@functools.cache
def _tpu_update_hlo(preset: str = PRESET, rows: int = B) -> str:
    """A preset's update compiled for a described v5e chip (libtpu
    compiles without a chip; nothing runs)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no libtpu on this machine
        pytest.skip(f"no TPU compiler here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    m = model(jnp.bfloat16, preset)
    t = nh.PRESETS[preset]["context"]

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    p = described(jax.eval_shape(m.init, jax.random.key(0),
                                 jnp.zeros((1, 2 * t), jnp.uint8)))
    batch = described(jax.eval_shape(lambda: batch_of(3, rows, t)))
    weights = described(jax.ShapeDtypeStruct((rows,), jnp.float32))

    def grads(params, target, batch, weights):
        return jax.grad(lambda q: double_dqn_loss(
            learner_apply_fn(m), q, target, batch, weights)[0])(params)

    return jax.jit(grads).lower(p, p, batch, weights).compile().as_text()


def _instructions(hlo: str, module: str = "models/nemotron_h.py"
                  ) -> list[tuple[str, str, set[str]]]:
    """``(name, op_name, functions)`` of every instruction that carries
    metadata: ``functions`` are the functions of this repo's ``module``
    (``nemotron_h.py``) on the instruction's stack of frames (the module's
    ``FileNames`` / ``FunctionNames`` / ``FileLocations`` / ``StackFrames``
    tables, by ``stack_frame_id``)."""
    import re

    def table(title: str) -> dict[int, str]:
        body = hlo[hlo.index("\n" + title + "\n") + len(title) + 2:]
        out = {}
        for line in body.split("\n"):
            got = re.match(r"(\d+) (.*)$", line)
            if not got:
                break
            out[int(got.group(1))] = got.group(2)
        return out

    files, functions = table("FileNames"), table("FunctionNames")
    locations = {k: tuple(int(x) for x in re.findall(
        r"file_name_id=(\d+) function_name_id=(\d+)", v)[0])
        for k, v in table("FileLocations").items()}
    frames = {k: tuple(int(x) for x in re.findall(
        r"file_location_id=(\d+) parent_frame_id=(\d+)", v)[0])
        for k, v in table("StackFrames").items()}

    def on_stack(frame: int) -> set[str]:
        seen, out = set(), set()
        while frame in frames and frame not in seen:
            seen.add(frame)
            location, parent = frames[frame]
            file_id, function_id = locations[location]
            if files[file_id].strip('"').endswith(module):
                out.add(functions[function_id].strip('"'))
            frame = parent
        return out

    return [(n, op, on_stack(int(frame))) for n, op, frame in re.findall(
        r"\n\s*(?:ROOT )?(%[\w.\-]+) = [^\n]*metadata=\{op_name=\"([^\"]*)\""
        r"[^}\n]*?stack_frame_id=(\d+)", hlo)]


def test_compiled_update_keeps_the_scan_under_its_scope():
    """``ssd_ms`` and ``ssd_roofline`` read the device time under the
    scope ``ssd``.  In the update compiled for the chip, every instruction
    that a line of :func:`nemotron_h.ssd` made carries ``ssd`` as the
    innermost torso name of its path, forward, rematerialised and
    transposed alike, or is placed there by its own name where the
    compiler named it and left no path (the running sums); the
    convolution's lie under ``conv``; and the loop that carries the state
    between chunks, which each pass runs once, appears in the three
    forward passes of every Mamba-2 layer, once more where the layer is
    rematerialised and once transposed in the backward pass: the six units
    ``SSD_UNITS`` weighs a layer by (a backward is twice a forward)."""
    from benchmark import costs_nemotron_h_q as costs_nh
    from benchmark import nemotron_h_scopes

    hlo = _tpu_update_hlo()
    rows = _instructions(hlo)
    scan = [r for r in rows if "ssd" in r[2]]
    assert len(scan) > 50
    by_name = set()
    for name, op_name, _fns in scan:
        assert nemotron_h_scopes.op_scope(name, op_name + ":") == "ssd", (
            name, op_name)
        if nemotron_h_scopes.scope_of(op_name + ":") != "ssd":
            by_name.add(op_name)
    # ``jnp.cumsum`` lowers to an operation the compiler names itself, with
    # no path: the reader places it by that name (PR 29's ragged-dot lesson)
    assert by_name == {"reduce_window_sum"}
    conv = [r for r in rows if r[2] & {"causal_conv", "_causal_conv_bwd"}]
    assert conv and all(nemotron_h_scopes.scope_of(op + ":") == "conv"
                        for _n, op, _fns in conv)
    # every custom call the compiler names itself is placed by name
    import re
    for name, op_name in re.findall(
            r"\n\s*(%[\w.\-]+) = [^\n]*custom-call\([^\n]*tpu_custom_call"
            r"[^\n]*op_name=\"([^\"]*)\"", hlo):
        assert nemotron_h_scopes.op_scope(name, op_name) is not None, name
    # the passes: the carried state's loop runs once a pass and layer
    loops = [op for _n, op in re.findall(
        r"\n\s*(%[\w.\-]+) = [^\n]*? while\([^\n]*op_name=\"([^\"]*)\"",
        hlo) if nemotron_h_scopes.scope_of(op + ":") == "ssd"]
    n_mamba = C["pattern"].count("M")
    backward = [op for op in loops if "transpose(jvp" in op
                and "rematted_computation" not in op]
    again = [op for op in loops if "rematted_computation" in op]
    assert len(again) == len(backward) == n_mamba
    assert len(loops) - len(again) - len(backward) == 3 * n_mamba, loops
    # differentiated pass: forward + forward again + backward (2) = 4;
    # next-state and target passes share a path and count one each
    assert costs_nh.SSD_UNITS == 6
    assert costs_nh.mamba_layers(dict(model=dict(pattern=C["pattern"]))) \
        == n_mamba


def test_compiled_update_calls_the_grouped_kernel_as_the_roofline_counts():
    """Two-matrix experts: the compiled update calls the grouped kernel
    ``2 x sum(EXPERT_UNITS) = 12`` times an expert layer outside every
    ``lax.cond`` branch (the first round of each layer), each named by the
    compiler with no scope path and placed under ``experts`` by name."""
    import re

    from benchmark import costs_nemotron_h_q as costs_nh
    from benchmark import nemotron_h_scopes

    hlo = _tpu_update_hlo()
    entry = hlo[hlo.index("\nENTRY "):]
    calls = re.findall(r"\n\s*(%[\w.\-]+) = [^\n]*custom-call\([^\n]*"
                       r"tpu_custom_call[^\n]*op_name=\"([^\"]*)\"", entry)
    kernels = [(n, op) for n, op in calls
               if "ragged" in n and "metadata" not in n]
    want = 2 * sum(costs_nh.EXPERT_UNITS.values()) * C["pattern"].count("E")
    assert len(kernels) == want, (len(kernels), want)
    for name, op_name in kernels:
        assert nemotron_h_scopes.scope_of(op_name + ":") is None
        assert nemotron_h_scopes.op_scope(name, op_name) == "experts"


def _pallas_blocks(jaxpr, out=None) -> list[list[tuple]]:
    """The block shapes of every ``pallas_call`` in a jaxpr, the branches
    of every ``cond`` and what ``remat`` / ``custom_vjp`` wrap included:
    one list a call, a tuple an operand or result (``None`` for a squeezed
    axis)."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append([tuple(getattr(b, "block_size", None)
                              for b in m.block_shape)
                        for m in eqn.params["grid_mapping"].block_mappings])
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_blocks(sub, out)
    return out


def test_compiled_update_hands_the_grouped_products_whole_tiles(monkeypatch):
    """At widths like the published ones (hidden 1,344 and experts 928,
    their halves: 512 divides neither) every grouped product of the update
    compiled for the chip, the 12 of ``EXPERT_UNITS`` in each of the
    layer's rounds, is the tiled kernel (``gmm``, and ``tgmm`` for the two
    weight gradients) and none XLA's own, whose weight window there would
    be 128 x 128; each carries its scope path and lies under ``experts``;
    its weight block is the rule's (768 x 512 for 1,536 x 1,024 handed);
    and a round hands back gradients of the shapes as published, never of
    the padded ones."""
    import re

    from benchmark import costs_nemotron_h_q as costs_nh
    from benchmark import nemotron_h_scopes

    like = dict(C, hidden_size=1344, moe_intermediate_size=928,
                moe_shared_expert_intermediate_size=256, pattern="ME",
                context=64)
    monkeypatch.setitem(nh.PRESETS, "published_like", like)
    m = model(jnp.bfloat16, "published_like")
    said = m.grouped_path("tpu")
    assert said["impl"] == "megablox_gmm"
    assert (said["hidden_handed"], said["width_handed"]) == (1536, 1024)
    assert (said["tile_k"], said["tile_n"]) == (768, 512)
    rounds, rows = 4, 4 * 64 * C["num_experts_per_tok"] // 4
    want = 2 * sum(costs_nh.EXPERT_UNITS.values()) * rounds
    hlo = _tpu_update_hlo("published_like", 4)
    calls = re.findall(r"\n\s*(?:ROOT )?(%[\w.\-]+) = [^\n]*custom-call\("
                       r"[^\n]*tpu_custom_call[^\n]*op_name=\"([^\"]*)\"", hlo)
    assert not any("ragged" in n for n, _op in calls)
    kernels = [(n, op) for n, op in calls if "moe.grouped" in op]
    assert len(kernels) == want, (len(kernels), want)
    assert sum("tgmm" in n for n, _op in kernels) == 2 * rounds
    for name, op_name in kernels:
        assert op_name.endswith("gmm)/pallas_call"), op_name
        assert nemotron_h_scopes.scope_of(op_name + ":") == "experts"
        assert nemotron_h_scopes.op_scope(name, op_name) == "experts"
    # what leaves a round: ``lax.cond``'s results
    results = re.findall(r"\n\s*(?:ROOT )?%[\w.\-]+ = (\([^\n]*?\)|\S+) "
                         r"conditional\(", hlo)
    assert any("[2,1344,928]" in r for r in results)
    assert any("[2,928,1344]" in r for r in results)
    assert not any(re.search(r"\b(1536|1024)\b", r) for r in results), \
        results
    # the blocks, from the program as traced (both sides of the platform's
    # choice are in it; only the kernel's side has a ``pallas_call``)
    t = like["context"]
    p = jax.eval_shape(m.init, jax.random.key(0),
                       jnp.zeros((1, 2 * t), jnp.uint8))
    batch = jax.eval_shape(lambda: batch_of(3, 4, t))
    traced = jax.make_jaxpr(lambda q, b: jax.grad(lambda q: double_dqn_loss(
        learner_apply_fn(m), q, q, b, jnp.ones(4))[0])(q))(p, batch)
    blocks = _pallas_blocks(traced.jaxpr)
    assert len(blocks) == want
    for call in blocks:
        weight = [b for b in call if len(b) == 3]
        assert len(weight) == 1 and weight[0][0] is None, call
        assert sorted(weight[0][1:]) == [512, 768], call
        assert all(b[0] == rows for b in call if len(b) == 2), call


@pytest.mark.parametrize("product", ["up", "down"])
def test_the_tiled_kernels_compile_at_the_published_widths(product):
    """The three kernels of a grouped product (forward, the rows' and the
    weights' gradient) at the published widths and a round's 24,576 rows,
    compiled for the described chip: the tiles the rule gives them fit
    its VMEM, and no ``ragged_dot`` is left."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no libtpu on this machine
        pytest.skip(f"no TPU compiler here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    c = nh.PRESETS["nemotron_twotower_ep16"]
    rows, e = 16 * c["context"] * c["num_experts_per_tok"] // 4, 8
    tiles = grouped.plan(c["hidden_size"], c["moe_intermediate_size"], rows,
                         "tpu")
    assert tiles == ((896, 2688), (640, 1920))
    k, n = (c["hidden_size"], c["moe_intermediate_size"])
    if product == "down":
        tiles, (k, n) = tiles[::-1], (n, k)

    def both_gradients(xs, w, sizes, dy):
        y, back = jax.vjp(lambda a, b: grouped.product(a, b, sizes, tiles),
                          xs, w)
        return y, back(dy)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    hlo = jax.jit(both_gradients).lower(
        shaped((rows, k), jnp.bfloat16), shaped((e, k, n), jnp.bfloat16),
        shaped((e,), jnp.int32), shaped((rows, tiles[1][1]), jnp.float32)
    ).compile().as_text()
    assert "ragged-dot" not in hlo
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*"
                          r"gmm[^\n\"]*/pallas_call", hlo)) == 3


def test_the_chips_compiler_knows_the_step_programs_option(monkeypatch):
    """``learner.jit_step_program`` hands XLA:TPU an option by name; a
    compiler that has dropped it refuses the program here, not on the
    chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from apex_tpu.training import learner
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no libtpu on this machine
        pytest.skip(f"no TPU compiler here: {e}")
    with monkeypatch.context() as as_on_a_tpu:
        as_on_a_tpu.setattr(jax, "default_backend", lambda: "tpu")
        step = learner.jit_step_program(lambda x: x @ x)
    x = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    assert "256,256" in step.lower(x).compile().as_text()
    with pytest.raises(Exception, match="no_such_option"):
        jax.jit(lambda x: x @ x, compiler_options={
            "xla_tpu_no_such_option": "true"}).lower(x).compile()


def test_compiled_update_takes_grouped_queries_to_the_fused_kernel(
        monkeypatch):
    """At widths the kernel takes (head width 128, a context of 256; the
    toy's other widths) the ``*`` layer of the update compiled for the
    chip is the flash kernel over ALL the query heads held, the shared
    key/value head repeated for them: four forward kernels (online(s), the
    same made again under the layer's ``nn.remat``, online(s') and the
    target pass) and the two of the backward pass, each under the scope
    ``attention``; the plain path's score product is nowhere."""
    import re

    from benchmark import nemotron_h_scopes

    wide = dict(C, context=256, head_dim=128, chunk_size=128)
    monkeypatch.setitem(nh.PRESETS, "eligible_toy", wide)
    assert model(jnp.bfloat16, "eligible_toy").attention_path(
        "tpu")["fused"] == 1
    hlo = _tpu_update_hlo("eligible_toy", 2)
    calls = re.findall(r"\n\s*(%[\w.\-]+) = ([^\n]*)custom-call\([^\n]*"
                       r"tpu_custom_call[^\n]*op_name=\"([^\"]*)\"", hlo)
    kernels = [(n, shape, op) for n, shape, op in calls if "flash" in op]
    backward = [n for n, _s, _op in kernels if "bwd" in n]
    assert len(kernels) - len(backward) == 4 and len(backward) == 2, kernels
    heads = nh.held_widths(wide)["attn_heads"]
    for name, shape, op_name in kernels:
        assert nemotron_h_scopes.scope_of(op_name + ":") == "attention"
        assert f"[2,{heads},256," in shape, (name, shape)
    assert "bhqd,bhkd->bhqk" not in hlo         # the plain path's scores
