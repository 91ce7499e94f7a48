"""The LFM2-8B-A1B torso (apex_tpu/models/lfm2_moe.py) against its plain
reference (benchmark/reference/lfm2_moe_q.py), at the toy preset on the
CPU, with seeded weights drawn as the benchmark draws them.

(a) Q rows in float32, (b) bfloat16 against float32's bound, (c) the
double-gated short convolution alone against a loop over positions,
values and gradients, (d) one learner update and its gradients by part,
(e) the 4 expert ranks' parts add up to the uncut expert block and no pair
is dropped under skewed routing, (f) the presets hold the published
share and say what the chip holds, (g) the factory and the CLI find the
family by the preset's name, (h) the update compiled for the chip keeps
the short convolution and the attention under their scopes.
"""

from __future__ import annotations

import functools
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
from apex_tpu.models import lfm2_moe as lf  # noqa: E402
from apex_tpu.ops.losses import double_dqn_loss, make_optimizer  # noqa: E402
from apex_tpu.training.learner import td_update  # noqa: E402
from apex_tpu.training.state import create_train_state  # noqa: E402
from benchmark import feed  # noqa: E402
from benchmark.reference import lfm2_moe_q as ref  # noqa: E402
from tests.test_nemotron_h import _instructions  # noqa: E402

PRESET, BIG = "lfm2_tiny", "lfm2_8b_a1b_ep4"
C = lf.PRESETS[PRESET]
B, T, V, D = 4, C["context"], C["vocab_held"], C["hidden_size"]
HP = dict(lr=6.25e-5, lr_decay_steps=1000, lr_decay_rate=0.99,
          rmsprop_decay=0.95, rmsprop_eps=1.5e-7, max_grad_norm=40.0,
          target_update_interval=2500)
M = dict(ref.model_of({"params": {"embedding": jnp.zeros((V, D))}}))


def model(dtype=jnp.float32, preset: str = PRESET, **kw):
    return make_q_network(dict(
        torso=preset, num_actions=lf.PRESETS[preset]["vocab_held"],
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


# -- (a), (b) Q rows ----------------------------------------------------------

def test_q_rows_equal_the_reference_in_float32(params):
    """Float32 on both sides, the same sums in another order (the tied
    head's Q is of the order of the hidden width, 64 here): 1e-5 of the
    mean |Q|."""
    obs = batch_of(1)["obs"]
    q = jax.jit(model().apply)(params, obs)
    want = reference_q(params, obs)
    assert q.shape == (B, V) and q.dtype == jnp.float32
    scale = float(jnp.abs(want).mean())
    np.testing.assert_allclose(q, want, rtol=1e-5, atol=1e-5 * scale)


def test_q_rows_in_bfloat16_stay_near_the_reference(params):
    """bfloat16 operands through five layers: the mean distance under 2%
    of the mean |Q| and no entry further than 15% of it; the reference
    with bfloat16 operands reads the same, and the one with fp8 operands
    does not.  Float32's bound does NOT hold for either: a program that
    silently computed in the lower precision would be seen by (a)."""
    obs = batch_of(2)["obs"]
    q = jax.jit(model(jnp.bfloat16).apply)(params, obs)
    want, stated = reference_q(params, obs), reference_q(params, obs, "bf16")
    scale = float(jnp.abs(want).mean())
    for got in (q, stated):
        assert float(jnp.abs(got - want).mean()) < 0.02 * scale
        assert float(jnp.abs(got - want).max()) < 0.15 * scale
        assert float(jnp.abs(got - want).max()) > 1e-3 * scale
        assert not np.allclose(got, want, rtol=1e-5, atol=1e-5 * scale)
    fp8 = reference_q(params, obs, "fp8")
    assert float(jnp.abs(fp8 - want).mean()) > 0.05 * scale


def test_the_acting_snapshot_computes_the_same_bits(params):
    """The acting snapshot multiplies the same bits from half the bytes;
    the tied embedding, the taps, the norms and the router stay float32."""
    obs = batch_of(2)["obs"]
    m = model(jnp.bfloat16)
    snap = acting_params(m, params)
    kept = {jax.tree_util.keystr(p).rsplit("'", 2)[-2]
            for p, x in jax.tree_util.tree_leaves_with_path(snap)
            if x.dtype == jnp.float32}
    assert kept == {"scale", "router_kernel", "router_bias", "embedding",
                    "conv_kernel"}
    np.testing.assert_array_equal(jax.jit(m.apply)(snap, obs),
                                  jax.jit(m.apply)(params, obs))


# -- (c) the short convolution ------------------------------------------------

def _shortconv_by_position(h, p, k: int):
    """``out[t] = (C[t] * sum_i w[i] (B * x)[t + i - (k - 1)]) W_out``,
    position by position in a Python loop, float32."""
    bcx = np.asarray(h, np.float64) @ np.asarray(p["in_proj"]["kernel"],
                                                 np.float64)
    b_g, c_g, xs = np.split(bcx, 3, axis=-1)
    u = b_g * xs
    w = np.asarray(p["conv_kernel"], np.float64)
    out = np.zeros_like(u)
    for t in range(u.shape[1]):
        for i in range(k):
            if t + i - (k - 1) >= 0:
                out[:, t] += w[i] * u[:, t + i - (k - 1)]
    return (c_g * out) @ np.asarray(p["out_proj"]["kernel"], np.float64)


def test_the_short_conv_equals_a_loop_over_positions():
    """The block alone (float32), its value and its gradients against the
    reference's three-tap sum, and its value against a loop over
    positions; zero history before position 0 (a change at the last
    position moves nothing before it)."""
    k = C["conv_L_cache"]
    block = lf.ShortConv(jnp.float32, k)
    h = jax.random.normal(jax.random.key(3), (2, 16, D))
    p = feed.make_weights(jax.eval_shape(block.init, jax.random.key(0), h),
                          3, ref.init_rule)["params"]
    out = block.apply({"params": p}, h)
    np.testing.assert_allclose(out, _shortconv_by_position(h, p, k),
                               rtol=1e-4, atol=1e-4)
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda x: ref.short_conv(x, p, M, "f32"))(h)
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
        cot = jax.random.normal(jax.random.key(4), out.shape)
        g = jax.grad(lambda q, x: (block.apply({"params": q}, x) * cot).sum(),
                     argnums=(0, 1))(p, h)
        gw = jax.grad(lambda q, x: (jax.vmap(
            lambda r: ref.short_conv(r, q, M, "f32"))(x) * cot).sum(),
            argnums=(0, 1))(p, h)
    for got, w in zip(jax.tree.leaves(g), jax.tree.leaves(gw)):
        assert float(jnp.abs(w).max()) > 0
        np.testing.assert_allclose(got, w, rtol=1e-4, atol=1e-5)
    later = block.apply({"params": p}, h.at[:, -1].add(1.0))
    np.testing.assert_array_equal(later[:, :-1], out[:, :-1])


# -- (d) one update -----------------------------------------------------------

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


@functools.cache
def one_update():
    return _one_update()


def test_one_update_equals_the_reference_step():
    new, prios, metrics, _clipped, want, out = one_update()
    assert float(metrics["loss"]) == pytest.approx(float(out["loss"]),
                                                   rel=1e-5)
    np.testing.assert_allclose(prios, out["priorities"], rtol=1e-4,
                               atol=1e-6)
    for (path, p), w in zip(jax.tree_util.tree_leaves_with_path(new.params),
                            jax.tree.leaves(want["params"])):
        np.testing.assert_allclose(p, w, rtol=1e-4, atol=1e-6,
                                   err_msg=str(path))
    # the routing counters of the three passes, over the 4 expert layers
    pairs = B * T * 4 * C["num_experts_per_tok"]
    for suffix in ("", "_next", "_target"):
        assert 0 < float(metrics["moe_local_pairs" + suffix]) < pairs
        assert float(metrics["moe_load_max_over_mean" + suffix]) >= 1.0


@pytest.mark.parametrize("part", ["short_conv", "self_attn", "feed_forward",
                                  "embedding"])
def test_gradients_of_the_td_loss_equal_the_references(part):
    """The backward pass by part inside the whole update: float32 both,
    sums in another order through five layers and a clip: 2e-3 of a
    leaf's entries; the tied embedding takes the head's gradient and the
    rows' alike."""
    _new, _prios, _metrics, clipped, _want, out = one_update()
    seen = 0
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(clipped),
                            jax.tree.leaves(out["grads"])):
        key = jax.tree_util.keystr(path)
        if part not in key:
            continue
        seen += 1
        # after the last mixer only the 4 last tokens carry a gradient, and
        # none of them need pick a held expert
        last_routed = "layers_4" in key and (
            "experts_" in key or "router_kernel" in key)
        assert last_routed or "router_bias" in key or \
            float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-6 * max(
            1.0, float(jnp.abs(w).max())), err_msg=key)
    assert seen


# -- (e) the expert shares ----------------------------------------------------

def moe_layer(held: int, rank: int, routed: int = C["num_experts"],
              k: int = C["num_experts_per_tok"]):
    return glm.MoE(jnp.float32, C["moe_intermediate_size"], routed, held,
                   rank, k, 1.0, "swiglu", 0, "sigmoid_bias", False,
                   n_shared_experts=0)


def uncut_moe_params(seed: int, routed: int = C["num_experts"]):
    shapes = jax.eval_shape(moe_layer(routed, 0, routed).init,
                            jax.random.key(0), jnp.zeros((1, T, D)))
    return feed.make_weights(shapes, seed, ref.init_rule)["params"]


def rank_slice(p: dict, rank: int, held: int) -> dict:
    return {"params": {
        k: (v[rank * held:(rank + 1) * held] if k.startswith("experts_")
            else v) for k, v in p.items()}}


def reference_moe(p, h, k: int = C["num_experts_per_tok"]):
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda x: ref.moe(
            x, p, dict(M, num_experts_per_tok=k), "f32"))(h)


def test_the_expert_shares_of_all_4_ranks_add_up_to_the_uncut_block(
        grouped_rule):
    """4 ranks x 2 of 8 experts under a sigmoid router with a
    selection-only bias that picks 4 and renormalises: the parts of every
    rank add up to the uncut reference's block output, every pair counted
    once; the layer has no shared expert and no gate."""
    p = uncut_moe_params(21)
    assert set(p) == {"router_kernel", "router_bias", "experts_gate",
                      "experts_up", "experts_down"}
    h = jax.random.normal(jax.random.key(5), (B, T, D))
    held = C["n_held_experts"]
    total, pairs = jnp.zeros_like(h), 0
    for rank in range(C["num_experts"] // held):
        out, (counts, _) = moe_layer(held, rank).apply(
            rank_slice(p, rank, held), h)
        total = total + out
        pairs += int(counts.sum())
        assert float(jnp.abs(out).mean()) > 0     # no rank adds nothing
    assert C["num_experts"] // held == 4
    assert pairs == B * T * C["num_experts_per_tok"]    # every pair, once
    np.testing.assert_allclose(total, reference_moe(p, h), rtol=1e-4,
                               atol=1e-5)


def test_no_pair_is_dropped_under_skewed_routing(grouped_rule):
    """A router whose outputs for experts 0 and 1 (both held by rank 0)
    stand far over the rest: every token picks both, all ``N k / 2``
    held pairs land in 2 of the rank's groups, every round they fill runs,
    the layer still equals the reference, and the gradient reaches all
    three matrices of both picked experts."""
    held, k = C["n_held_experts"], C["num_experts_per_tok"]
    p = uncut_moe_params(22)
    h = jax.random.normal(jax.random.key(6), (B, T, D)).at[..., 0].set(3.0)
    p["router_kernel"] = p["router_kernel"].at[0, :2].set(10.0).at[
        0, 2:].set(0.0)
    cut = rank_slice(p, 0, held)
    layer = moe_layer(held, 0)
    out, (counts, overflow) = layer.apply(cut, h)
    np.testing.assert_array_equal(counts, [B * T] * 2)
    assert int(overflow) > 0            # the later rounds ran
    np.testing.assert_allclose(out, reference_moe(cut["params"], h, k),
                               rtol=1e-4, atol=1e-5)
    g = jax.grad(lambda q: layer.apply({"params": q}, h)[0].sum())(
        cut["params"])
    for name in ("experts_gate", "experts_up", "experts_down"):
        assert all(float(jnp.abs(g[name][e]).max()) > 0 for e in range(2))


def test_the_shared_expert_is_a_field_of_the_one_expert_layer():
    """``n_shared_experts=0`` leaves no shared leaves and no gate; the
    default keeps GLM's layer as it was; another count is refused."""
    x = jnp.zeros((1, 4, D))
    none = jax.eval_shape(moe_layer(2, 0).init, jax.random.key(0),
                          x)["params"]
    assert not {"shared", "shared_gate"} & set(none)
    one = jax.eval_shape(glm.MoE(jnp.float32, 32, 8, 2).init,
                         jax.random.key(0), x)["params"]
    assert "shared" in one
    with pytest.raises(ValueError, match="shared experts"):
        glm.MoE(jnp.float32, 32, 8, 2, n_shared_experts=2).init(
            jax.random.key(0), x)


# -- (f) the presets ----------------------------------------------------------

def test_presets_hold_the_published_share():
    from benchmark import costs_lfm2_moe_q as costs_lf
    c = lf.PRESETS[BIG]
    shapes = jax.eval_shape(model(jnp.bfloat16, BIG).init, jax.random.key(0),
                            jnp.zeros((1, 2 * c["context"]), jnp.uint8))
    size = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa: E731
    assert size(shapes) == 507_820_288 == lf.param_count(c)
    p = shapes["params"]
    assert size(p["layers_0"]["short_conv"]) == 16_783_360
    assert size(p["layers_1"]["self_attn"]) == 10_485_888
    assert size(p["layers_0"]["feed_forward"]) == 44_040_192
    assert size(p["layers_2"]["feed_forward"]) == 88_145_952
    assert [size(p[f"layers_{i}"]) for i in range(5)] == [
        60_827_648, 98_635_936] + [104_933_408] * 3
    assert size(p["embedding"]) + size(p["embedding_norm"]) == 33_556_480
    assert "head" not in p                  # tied
    assert lf.pattern(c) == "CACCC"
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_8b_a1b_q_ep4.json")) as f:
        config = json.load(f)
    assert costs_lf.param_count(config["shapes"]) == 507_820_288
    assert tuple(config["layer_types_held"]) == c["layer_types"]
    tiny = jax.eval_shape(model().init, jax.random.key(0),
                          jnp.zeros((1, 2 * T), jnp.uint8))
    assert size(tiny) == lf.param_count(C)


def test_torso_layout_says_what_the_chip_holds():
    big = model(jnp.bfloat16, BIG)
    assert big.torso_layout() == {
        "pattern": "CACCC", "ffn": "DEEEE", "experts": "8/32",
        "expert_rank": 0, "shared_experts": 0, "vocab": "16384/65536",
        "tied_head": 1, "params": 507_820_288}
    path = big.attention_path("tpu")
    assert path["fused"] == 1 and path["qk_head_dim"] == 64
    assert big.attention_path("cpu")["fused"] == 0
    assert model().attention_path("tpu")["fused"] == 0
    # width 1,792 = 2 x 896: the tiled kernel, nothing padded
    assert big.grouped_path("tpu") == dict(
        hidden=2048, width=1792, platform="tpu", hidden_handed=2048,
        width_handed=1792, tile_k=512, tile_n=896, impl="megablox_gmm")


# -- (g) found by the preset's name -------------------------------------------

@pytest.mark.parametrize("torso", [PRESET, BIG])
def test_the_factory_and_the_cli_find_the_family_by_its_preset(torso):
    from apex_tpu.runtime.cli import build_parser, config_from_args
    assert torso in models.torso_names()
    preset = models.token_preset(torso)
    m = make_q_network(models.q_model_spec(
        torso, num_actions=preset["vocab_held"], obs_is_image=False,
        compute_dtype=jnp.bfloat16, scale_uint8=True))
    assert type(m) is lf.Lfm2MoeQ and m.preset == torso
    cfg = config_from_args(build_parser().parse_args(
        ["--role", "apex", "--torso", torso, "--env-id", "ApexTokens-v0"]))
    assert cfg.learner.torso == torso
    assert (cfg.env.token_context, cfg.env.token_vocab) == (
        preset["context"], preset["vocab_held"])
    assert torso in build_parser().format_help()


# -- (h) what the update compiled for the chip is made of ---------------------

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
    t = lf.PRESETS[preset]["context"]

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


def test_compiled_update_keeps_the_mixers_under_their_scopes():
    """``shortconv_ms`` / ``shortconv_roofline`` read the device time under
    ``shortconv`` (with ``conv`` inside it), ``qk_norm_attention_ms`` under
    ``qk_norm_attention``.  In the update compiled for the chip, every
    instruction a line of :class:`lfm2_moe.ShortConv` made carries
    ``shortconv`` or ``conv`` as its innermost torso name, the three-tap
    sum's (``nemotron_h.causal_conv``, forward and its own backward)
    ``conv``; every instruction of :class:`lfm2_moe.QKNormAttention`
    ``qk_norm_attention``; every custom call the compiler names itself is
    placed; and every conv layer's ``in_proj`` product appears where the
    layer is rematerialised and in the backward pass, besides the forward
    passes: ``SHORTCONV_UNITS`` weighs a layer by six forward-sized
    passes (three forwards, the rematerialised one, a backward of two)."""
    from benchmark import costs_lfm2_moe_q as costs_lf
    from benchmark import family_scopes

    table = family_scopes.table_for("lfm2_moe_q")
    hlo = _tpu_update_hlo()
    rows = _instructions(hlo, "models/lfm2_moe.py")
    conv_rows = [op for _n, op, fns in rows if "ShortConv.__call__" in fns]
    assert conv_rows and all(
        family_scopes.scope_of(table, op + ":") in ("shortconv", "conv")
        for op in conv_rows)
    taps = [r for r in _instructions(hlo) if r[2] & {
        "causal_conv", "_causal_conv_fwd", "_causal_conv_bwd"}]
    assert taps and all(family_scopes.scope_of(table, op + ":") == "conv"
                        for _n, op, _fns in taps)
    attn = [op for _n, op, fns in rows
            if "QKNormAttention.__call__" in fns]
    assert attn and all(
        family_scopes.scope_of(table, op + ":") == "qk_norm_attention"
        for op in attn)
    for name, op_name in re.findall(
            r"\n\s*(%[\w.\-]+) = [^\n]*custom-call\([^\n]*tpu_custom_call"
            r"[^\n]*op_name=\"([^\"]*)\"", hlo):
        assert family_scopes.op_scope(table, name, op_name) is not None, name
    # the block's products in a forward pass, again where the layer is
    # rematerialised, and transposed in the backward pass
    in_proj = set(re.findall(
        r'op_name="([^"]*/shortconv/short_conv/in_proj/[^"]*)"', hlo))
    n_conv = lf.pattern(C).count("C")
    for layer in (0, 2, 3, 4):
        mine = [op for op in in_proj if f"/layers_{layer}/" in op]
        assert any("rematted_computation" in op for op in mine), layer
        assert any("transpose(" in op.split("/layers_")[1]
                   or op.startswith("jit(grads)/transpose(") for op in mine)
    assert costs_lf.SHORTCONV_UNITS == 6
    assert costs_lf.shortconv_layers(dict(model=dict(
        layer_types=list(C["layer_types"]),
        num_dense_layers=C["num_dense_layers"]))) == n_conv
