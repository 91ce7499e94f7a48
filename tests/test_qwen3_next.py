"""The Qwen3-Next torso (apex_tpu/models/qwen3_next.py) against its plain
reference (benchmark/reference/qwen3_next_q.py), at the toy preset on the
CPU, with seeded weights drawn as the benchmark draws them.

The program computes the gated delta rule chunked, with a triangular
inverse a chunk; the reference steps the recurrence one position at a
time.  (a) Q rows over contexts of 1, 2 and 4 chunks, (b) bfloat16 against
float32's bound, (c) the chunked rule alone against the recurrence, values
and gradients, and the inverse alone, (d) one learner update and its
gradients by part, (e) rows of a batch are independent, (f) the shares of
all ranks (DeltaNet heads, query heads, experts under softmax top-k) add
up to the uncut layer, (g) no pair is dropped at 32 held groups under
skewed routing, (h) the presets hold what the issue counts, (i) the
factory and the CLI find the family by the preset's name, (j) the update
compiled for the chip keeps the rule under its scope.
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
from apex_tpu.models import qwen3_next as qn  # noqa: E402
from apex_tpu.ops.losses import double_dqn_loss, make_optimizer  # noqa: E402
from apex_tpu.training.learner import td_update  # noqa: E402
from apex_tpu.training.state import create_train_state  # noqa: E402
from benchmark import feed  # noqa: E402
from benchmark.reference import qwen3_next_q as ref  # noqa: E402
from tests.test_nemotron_h import _instructions  # noqa: E402

PRESET, BIG = "qwen3_next_tiny", "qwen3_next_80b_ep16"
C = qn.PRESETS[PRESET]
B, T, V, D = 4, C["context"], C["vocab_held"], C["hidden_size"]
HP = dict(lr=6.25e-5, lr_decay_steps=1000, lr_decay_rate=0.99,
          rmsprop_decay=0.95, rmsprop_eps=1.5e-7, max_grad_norm=40.0,
          target_update_interval=2500)
M = dict(ref.model_of({"params": {"embedding": jnp.zeros((V, D))}}))


def model(dtype=jnp.float32, preset: str = PRESET, **kw):
    return make_q_network(dict(
        torso=preset, num_actions=qn.PRESETS[preset]["vocab_held"],
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


# -- (a) Q rows: the chunked rule against the recurrence ----------------------

@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_q_rows_equal_the_reference_in_float32(params, chunks):
    """Contexts of 1, 2 and 4 chunks of 8: the triangular system alone,
    one carried state, several.  Float32 on both sides, so they differ by
    the order of their sums alone (a chunk solved at once against 8 steps
    of a recurrence): 1e-5 of a Q of order 1."""
    obs = batch_of(chunks, t=chunks * C["chunk_size"])["obs"]
    q = jax.jit(model().apply)(params, obs)
    want = reference_q(params, obs)
    assert q.shape == (B, V) and q.dtype == jnp.float32
    np.testing.assert_allclose(q, want, rtol=1e-5, atol=1e-5)


# -- (b) bfloat16 --------------------------------------------------------------

def test_q_rows_in_bfloat16_stay_near_the_reference(params):
    """bfloat16 operands: 8 bits of mantissa through four layers, a pick
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


def test_the_acting_snapshot_keeps_the_rule_in_float32(params):
    """The acting snapshot multiplies the same bits from half the bytes,
    and what the delta rule, the convolution, the norms and the router
    read in float32 stays so."""
    obs = batch_of(2)["obs"]
    m16 = model(jnp.bfloat16)
    snap = acting_params(m16, params)["params"]
    gdn = snap["layers_0"]["mixer"]["gdn"]
    assert gdn["in_proj_qkvz"]["kernel"].dtype == jnp.bfloat16
    assert gdn["in_proj_ba"]["kernel"].dtype == jnp.bfloat16
    assert snap["layers_1"]["experts"]["moe"]["experts_up"].dtype == jnp.bfloat16
    assert snap["layers_1"]["experts"]["moe"]["shared_gate"]["kernel"].dtype == \
        jnp.bfloat16
    for name in ("A_log", "dt_bias", "norm_scale", "conv_kernel"):
        assert gdn[name].dtype == jnp.float32, name
    assert snap["layers_3"]["mixer"]["attention"]["q_norm"]["scale"].dtype == \
        jnp.float32
    assert snap["layers_1"]["experts"]["moe"]["router_kernel"].dtype == jnp.float32
    np.testing.assert_array_equal(
        jax.jit(m16.apply)({"params": snap}, obs),
        jax.jit(m16.apply)(params, obs))


# -- (c) the rule alone -----------------------------------------------------------

def _rule_operands(seed: int = 3, b: int = 2, t: int = T, hk: int = 2,
                   r: int = 2, d: int = 16):
    """Unit ``q`` (scaled) and ``k``, ``v``, log-decays as the seeded
    constants give them (``A`` 1..16, steps 0.001..0.1 times a token's
    own factor) and ``beta`` in (0, 1)."""
    rng = np.random.default_rng(seed)
    hv = hk * r
    q, k = (rng.normal(0, 1, (b, t, hk, d)) for _ in range(2))
    q, k = (x / np.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    v = rng.normal(0, 1, (b, t, hv, d))
    steps = np.geomspace(1e-3, 1e-1, hv) * np.exp(rng.normal(0, 1,
                                                             (b, t, hv)))
    g = -np.linspace(1.0, 16.0, hv) * steps
    beta = 1 / (1 + np.exp(-rng.normal(0, 1.4, (b, t, hv))))
    return tuple(jnp.asarray(x, jnp.float32)
                 for x in (q / math.sqrt(d), k, v, g, beta))


@functools.cache
def _rule_both_ways():
    """The chunked rule and the recurrence over the same operands: the
    output and the gradient of a weighted sum of it by every operand."""
    args = _rule_operands()
    cot = jnp.asarray(np.random.default_rng(4).normal(
        0, 1, args[2].shape), jnp.float32)

    def chunked(*a):
        return qn.delta_rule(*a, C["chunk_size"], jnp.float32)

    def stepped(*a):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(lambda *row: ref.recurrence(*row, "f32"))(*a)

    out = {}
    for name, f in (("chunked", chunked), ("stepped", stepped)):
        o, grads = jax.value_and_grad(
            lambda *a: (f(*a) * cot).sum(), argnums=tuple(range(5)))(*args)
        out[name] = dict(zip(("dq", "dk", "dv", "dg", "dbeta"), grads),
                         o=f(*args), loss=o)
    return out


@pytest.mark.parametrize("name", ["o", "dq", "dk", "dv", "dg", "dbeta"])
def test_the_chunked_rule_equals_the_recurrence(name):
    """Four chunks of 8 against 32 steps, float32 both: the output, and
    the backward pass of the chunked form (autodiff through the triangular
    inverse, the products inside a chunk and the carried state) against
    that of the recurrence."""
    both = _rule_both_ways()
    got, want = both["chunked"][name], both["stepped"][name]
    assert got.shape == want.shape and float(jnp.abs(want).max()) > 1e-3
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_the_rule_does_work_the_comparison_can_see():
    """At the seeded constants the state reaches across chunks and the
    correction term is no rounding error: one chunk a context equals four,
    cutting the context into separate chunks does not, and neither does
    leaving the delta term out (``beta (v - S^T k)`` -> ``beta v``: plain
    gated linear attention)."""
    args = _rule_operands()
    y = qn.delta_rule(*args, C["chunk_size"], jnp.float32)
    whole = qn.delta_rule(*args, T, jnp.float32)
    np.testing.assert_allclose(y, whole, rtol=1e-4, atol=1e-5)
    scale = float(jnp.abs(y).mean())
    cut = jnp.concatenate([qn.delta_rule(*(v[:, i:i + 8] for v in args), 8,
                                         jnp.float32)
                           for i in range(0, T, 8)], axis=1)
    assert float(jnp.abs(cut - y).mean()) > 0.05 * scale
    q, k, v, g, beta = args
    # without the correction: S_t = exp(g_t) S_{t-1} + beta_t k_t v_t^T
    kh = jnp.repeat(k, 2, axis=2)
    qh = jnp.repeat(q, 2, axis=2)
    cum = jnp.cumsum(g, axis=1)
    decay = jnp.exp(cum[:, :, None] - cum[:, None, :])        # [b, t, s, h]
    decay = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, :, :, None],
                      decay, 0.0)
    scores = jnp.einsum("bthk,bshk->btsh", qh, kh) * decay
    plain = jnp.einsum("btsh,bshd->bthd", scores, v * beta[..., None])
    assert float(jnp.abs(plain - y).mean()) > 0.05 * scale


@pytest.mark.parametrize("c", [1, 8, 64])
def test_the_unit_lower_inverse_is_the_inverse(c):
    """``(I - A)(I + A^2)(I + A^4) ...`` against ``numpy``'s inverse of
    ``I + A`` in float64, at the toy's chunk, the published one and a
    single position."""
    rng = np.random.default_rng(c)
    a = np.tril(rng.normal(0, 0.3, (3, c, c)), -1)
    got = qn.unit_lower_inverse(jnp.asarray(a, jnp.float32))
    want = np.linalg.inv(np.eye(c) + a)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-5 * np.abs(want).max())


# -- (d) one update -----------------------------------------------------------

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
    pairs = B * T * C["num_hidden_layers"] * C["num_experts_per_tok"]
    for suffix in ("", "_next", "_target"):
        assert 0 < float(metrics["moe_local_pairs" + suffix]) < pairs
        assert float(metrics["moe_load_max_over_mean" + suffix]) >= 1.0
        # 2 of 32 held: round 0, a quarter of the pairs here, holds them
        assert float(metrics["moe_overflow_rounds" + suffix]) == 0.0


@pytest.mark.parametrize("part", ["gdn", "attention", "moe", "embedding"])
def test_gradients_of_the_td_loss_equal_the_references(part):
    """The backward pass of the chunked rule against that of the
    recurrence inside the whole update, and the other parts' beside it:
    float32 both, sums in another order through four layers and a clip:
    2e-3 of a leaf's entries."""
    _new, _prios, _metrics, clipped, _want, out = _one_update()
    seen = 0
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(clipped),
                            jax.tree.leaves(out["grads"])):
        if part not in jax.tree_util.keystr(path):
            continue
        seen += 1
        # a gradient reached it; after the last mixer only the 4 last
        # tokens carry one, and none of them need pick a held expert: the
        # routed experts and the router of the last block may stand still
        key = jax.tree_util.keystr(path)
        last_routed = "layers_3" in key and (
            "experts_" in key or "router_kernel" in key)
        assert last_routed or float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-6,
                                   err_msg=str(path))
    assert seen


# -- (e) rows of a batch ------------------------------------------------------

def test_rows_of_a_batch_are_independent(params):
    """The state is nought at every context's start: a row reads the same
    alone (to the order of a product's sums: 1e-5) and, bit for bit,
    whatever stands in the row before it."""
    obs = batch_of(5)["obs"]
    apply = jax.jit(model().apply)
    q = apply(params, obs)
    for i in range(B):
        np.testing.assert_allclose(apply(params, obs[i:i + 1])[0], q[i],
                                   rtol=1e-5, atol=1e-5)
    other = obs.at[0].set(batch_of(6)["obs"][0])
    np.testing.assert_array_equal(apply(params, other)[1:], q[1:])
    assert float(jnp.abs(apply(params, other)[0] - q[0]).max()) > 1e-3


# -- (f), (g) the shares ---------------------------------------------------------

#: the toy with every head held: the uncut layer
UNCUT = dict(C, linear_key_heads_held=C["linear_num_key_heads"],
             attention_heads_held=C["num_attention_heads"])


@pytest.mark.parametrize("kind,part", [("D", "gdn"), ("A", "attention")])
def test_the_head_shares_of_both_ranks_add_up_to_the_uncut_mixer(kind, part):
    """2 ranks x half the heads (key heads with their value heads; query
    heads with their key/value head): the parts the two ranks add to the
    residual stream are what the uncut mixer adds, and the uncut
    reference's; one rank alone is the reference given that rank's
    share."""
    frozen = lambda c: tuple(sorted(c.items()))         # noqa: E731
    whole = qn.Mixer(jnp.float32, frozen(UNCUT), kind)
    share = qn.Mixer(jnp.float32, frozen(C), kind)
    x = jax.random.normal(jax.random.key(31), (B, T, D))
    shapes = jax.eval_shape(whole.init, jax.random.key(0), x)
    p = feed.make_weights(shapes, 31, ref.init_rule)["params"]
    out = whole.apply({"params": p}, x) - x
    u = jax.vmap(lambda r: ref.norm(r, p["norm"], M))(x)
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda r: ref.MIXERS[part](r, p[part], M, "f32"))(u)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    total = jnp.zeros_like(x)
    for rank in range(2):
        cut = qn.share_of_layer(kind, p, C, rank)
        got = share.apply({"params": cut}, x) - x
        total = total + got
        with jax.default_matmul_precision("highest"):
            alone = jax.vmap(lambda r: ref.MIXERS[part](r, cut[part], M,
                                                        "f32"))(u)
        np.testing.assert_allclose(got, alone, rtol=1e-4, atol=1e-5)
        assert float(jnp.abs(got).mean()) > 0.1 * float(
            jnp.abs(want).mean())                   # no rank adds nothing
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def moe_layer(held: int, rank: int, routed: int = C["num_experts"],
              k: int = C["num_experts_per_tok"]):
    return glm.MoE(jnp.float32, C["moe_intermediate_size"], routed, held,
                   rank, k, 1.0, "swiglu",
                   C["shared_expert_intermediate_size"], "softmax", True)


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


def test_the_expert_shares_of_all_16_ranks_add_up_to_the_uncut_block(
        grouped_rule):
    """16 ranks x 2 of 32 experts under a softmax router that picks 4 and
    renormalises: the routed parts of every rank, with the gated shared
    expert counted once, are the uncut reference's block output; the layer
    has no router bias and has the shared expert's gate."""
    p = uncut_moe_params(21)
    assert set(p) == {"shared", "shared_gate", "router_kernel",
                      "experts_gate", "experts_up", "experts_down"}
    assert p["shared_gate"]["kernel"].shape == (D, 1)
    h = jax.random.normal(jax.random.key(5), (B, T, D))
    held = 2
    zero = jax.tree.map(jnp.zeros_like, rank_slice(p, 0, held))
    zero["params"].update(shared=p["shared"], shared_gate=p["shared_gate"],
                          router_kernel=p["router_kernel"])
    shared, _ = moe_layer(held, 0).apply(zero, h)     # routed experts nought
    with jax.default_matmul_precision("highest"):
        want_shared = jax.vmap(lambda x: ref.swiglu(x, p["shared"], "f32")
                               * jax.nn.sigmoid(x @ p["shared_gate"]["kernel"])
                               )(h)
    np.testing.assert_allclose(shared, want_shared, rtol=1e-4, atol=1e-5)
    total, pairs = shared, 0
    for rank in range(C["num_experts"] // held):
        out, (counts, _) = moe_layer(held, rank).apply(
            rank_slice(p, rank, held), h)
        total = total + (out - shared)
        pairs += int(counts.sum())
    assert C["num_experts"] // held == 16
    assert pairs == B * T * C["num_experts_per_tok"]    # every pair, once
    np.testing.assert_allclose(total, reference_moe(p, h), rtol=1e-4,
                               atol=1e-5)


def test_no_pair_is_dropped_at_32_held_groups_under_skewed_routing(
        grouped_rule):
    """32 of 64 experts held, a router whose outputs for experts 0-3 stand
    far over the rest: every token picks those four, all ``N k`` pairs land
    on this rank in 4 of its 32 groups (28 empty), every round runs, the
    layer still equals the reference and the gradient reaches all three
    matrices of each picked expert."""
    routed, held, k = 64, 32, 4
    p = uncut_moe_params(22, routed)
    p = dict(p, router_kernel=p["router_kernel"].at[:, :4].set(0.0))
    h = jax.random.normal(jax.random.key(6), (B, T, D))
    # a constant feature the first four columns alone read
    h = h.at[..., 0].set(3.0)
    p["router_kernel"] = p["router_kernel"].at[0, :4].set(10.0).at[
        0, 4:].set(0.0)
    cut = rank_slice(p, 0, held)
    layer = moe_layer(held, 0, routed, k)
    out, (counts, _) = layer.apply(cut, h)
    np.testing.assert_array_equal(counts, [B * T] * 4 + [0] * 28)
    np.testing.assert_allclose(out, reference_moe(cut["params"], h, k),
                               rtol=1e-4, atol=1e-5)
    g = jax.grad(lambda q: layer.apply({"params": q}, h)[0].sum())(
        cut["params"])
    for name in ("experts_gate", "experts_up", "experts_down"):
        assert all(float(jnp.abs(g[name][e]).max()) > 0 for e in range(4))
        assert float(jnp.abs(g[name][4:]).max()) == 0.0


@pytest.mark.parametrize("routing,ran", [("onto_held", [512, 1024, 1024]),
                                         ("even", [512])])
def test_nothing_is_dropped_once_round_0_overflows(monkeypatch, routing,
                                                   ran):
    """The toy's 2 of 32 held experts over 1,024 tokens: 4,096 pairs,
    round 0 of 512 rows (twice the even 256), later rounds of 1,024.  A
    router that gives every token both held experts fills 2,048 rows:
    rounds 1 and 2 run, 3 and 4 do not, ``overflow`` says 2, the layer
    equals the float32 reference and the gradient reaches every held
    expert.  Under the router as drawn round 0 holds every held pair."""
    held = C["n_held_experts"]
    p = uncut_moe_params(23)
    h = jax.random.normal(jax.random.key(7), (4, 256, D))
    if routing == "onto_held":
        h = h.at[..., 0].set(3.0)
        p["router_kernel"] = p["router_kernel"].at[0, :held].set(10.0).at[
            0, held:].set(0.0)
    cut = rank_slice(p, 0, held)
    layer = moe_layer(held, 0)
    rows, real = [], glm.MoE.experts

    def counted(self, xs, *args, **kw):
        jax.debug.callback(lambda: rows.append(xs.shape[0]))
        return real(self, xs, *args, **kw)

    monkeypatch.setattr(glm.MoE, "experts", counted)
    out, (counts, overflow) = layer.apply(cut, h)
    assert rows == ran                      # the rounds that ran, in order
    assert int(overflow) == len(ran) - 1
    local = int(counts.sum())
    assert sum(ran[:-1]) < local <= sum(ran)    # each round that ran was due
    assert routing == "even" or local == 2 * 1024
    np.testing.assert_allclose(out, reference_moe(cut["params"], h),
                               rtol=1e-4, atol=1e-5)
    g = jax.grad(lambda q: layer.apply({"params": q}, h)[0].sum())(
        cut["params"])
    for name in ("experts_gate", "experts_up", "experts_down"):
        assert all(float(jnp.abs(g[name][e]).max()) > 0 for e in range(held))


def test_the_scoring_rule_is_a_field_of_the_one_expert_layer():
    """``sigmoid_bias`` (the other two families') keeps its bias leaf and
    has no gate; ``softmax`` has the gate and no bias; another name is
    refused."""
    x = jnp.zeros((1, 4, D))
    sig = jax.eval_shape(glm.MoE(jnp.float32, 32, 8, 2).init,
                         jax.random.key(0), x)["params"]
    assert "router_bias" in sig and "shared_gate" not in sig
    soft = jax.eval_shape(moe_layer(2, 0).init, jax.random.key(0),
                          x)["params"]
    assert "router_bias" not in soft and "shared_gate" in soft
    with pytest.raises(ValueError, match="scoring rule"):
        glm.MoE(jnp.float32, 32, 8, 2, scoring="argmax").init(
            jax.random.key(0), x)


# -- (h) the presets ------------------------------------------------------------

def test_presets_hold_what_the_issue_counts():
    from benchmark import costs_qwen3_next_q as costs_qn
    big = model(jnp.bfloat16, BIG)
    c = qn.PRESETS[BIG]
    shapes = jax.eval_shape(big.init, jax.random.key(0),
                            jnp.zeros((1, 2 * c["context"]), jnp.uint8))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 561_458_144
    assert qn.param_count(c) == 561_458_144
    size = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa: E731
    first, last = shapes["params"]["layers_0"], shapes["params"]["layers_3"]
    assert size(first["mixer"]["gdn"]) == 16_859_296
    assert size(last["mixer"]["attention"]) == 13_632_000
    assert size(first["experts"]["moe"]) == 104_859_648
    assert size(last["experts"]["moe"]) == 104_859_648
    assert size(first) == 121_723_040 and size(last) == 118_495_744
    assert qn.pattern(c) == "DDDA"
    assert qn.held_widths(c) == dict(key_heads=8, value_heads=16,
                                     attn_heads=8, kv_heads=1)
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3_next_q_ep16.json")) as f:
        config = json.load(f)
    assert costs_qn.param_count(config["shapes"]) == 561_458_144
    tiny = jax.eval_shape(model().init, jax.random.key(0),
                          jnp.zeros((1, 2 * T), jnp.uint8))
    assert size(tiny) == qn.param_count(C)


def test_torso_layout_says_what_the_chip_holds():
    assert model(jnp.bfloat16, BIG).torso_layout() == {
        "pattern": "DDDA", "key_heads": "8/16", "value_heads": "16/32",
        "attn_heads": "8/16", "kv_heads": "1/2", "experts": "32/512",
        "expert_rank": 0, "chunk": 64,
        "inverse": "nilpotent_doubling_f32", "params": 561_458_144}
    big = model(jnp.bfloat16, BIG)
    assert big.attention_path("tpu")["fused"] == 1
    assert big.attention_path("tpu")["qk_head_dim"] == 256
    assert model().attention_path("tpu")["fused"] == 0
    # hidden 2,048 / width 512: 512 divides both, ``ragged_dot`` as it is
    assert big.grouped_path("tpu") == dict(
        hidden=2048, width=512, platform="tpu", hidden_handed=2048,
        width_handed=512, tile_k=512, tile_n=512, impl="ragged_dot")


# -- (i) found by the preset's name ---------------------------------------------

@pytest.mark.parametrize("torso", [PRESET, BIG])
def test_the_factory_and_the_cli_find_the_family_by_its_preset(torso):
    from apex_tpu.runtime.cli import build_parser, config_from_args
    assert torso in models.torso_names()
    preset = models.token_preset(torso)
    m = make_q_network(models.q_model_spec(
        torso, num_actions=preset["vocab_held"], obs_is_image=False,
        compute_dtype=jnp.bfloat16, scale_uint8=True))
    assert type(m) is qn.Qwen3NextQ and m.preset == torso
    cfg = config_from_args(build_parser().parse_args(
        ["--role", "apex", "--torso", torso, "--env-id", "ApexTokens-v0"]))
    assert cfg.learner.torso == torso
    assert (cfg.env.token_context, cfg.env.token_vocab) == (
        preset["context"], preset["vocab_held"])
    assert torso in build_parser().format_help()


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
    t = qn.PRESETS[preset]["context"]

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


def test_compiled_update_keeps_the_rule_under_its_scope():
    """``delta_ms`` and ``delta_roofline`` read the device time under the
    scope ``delta``.  In the update compiled for the chip, every
    instruction that a line of :func:`qwen3_next.delta_rule` or of the
    inverse made carries ``delta`` as the innermost torso name of its
    path, forward, rematerialised and transposed alike (the running sums
    are a product with a triangle of ones, so none is left to the
    compiler's own naming); the convolution's lie under ``conv``; the
    attention layer's under ``gated_attention``; and the loop that carries
    the state between chunks, which each pass runs once, appears in the
    three forward passes of every DeltaNet layer, once more where the
    layer is rematerialised and once transposed in the backward pass: the
    six units ``DELTA_UNITS`` weighs a layer by (a backward is twice a
    forward)."""
    from benchmark import costs_qwen3_next_q as costs_qn
    from benchmark import family_scopes

    table = family_scopes.table_for("qwen3_next_q")
    hlo = _tpu_update_hlo()
    rows = _instructions(hlo, "models/qwen3_next.py")
    rule = [r for r in rows if r[2] & {"delta_rule", "unit_lower_inverse"}]
    assert len(rule) > 50
    for name, op_name, _fns in rule:
        assert family_scopes.scope_of(table, op_name + ":") == "delta", (
            name, op_name)
    attn = [r for r in rows if any("GatedAttention" in f for f in r[2])]
    assert attn and all(
        family_scopes.scope_of(table, op + ":") == "gated_attention"
        for _n, op, _fns in attn)
    conv = [op for _n, op, _fns in rows if "/conv/" in op]
    assert conv and all(family_scopes.scope_of(table, op + ":") == "conv"
                        for op in conv)
    # every custom call the compiler names itself is placed by name
    for name, op_name in re.findall(
            r"\n\s*(%[\w.\-]+) = [^\n]*custom-call\([^\n]*tpu_custom_call"
            r"[^\n]*op_name=\"([^\"]*)\"", hlo):
        assert family_scopes.op_scope(table, name, op_name) is not None, name
    # the passes: the carried state's loop runs once a pass and layer
    loops = [op for _n, op in re.findall(
        r"\n\s*(%[\w.\-]+) = [^\n]*? while\([^\n]*op_name=\"([^\"]*)\"",
        hlo) if family_scopes.scope_of(table, op + ":") == "delta"]
    n_gdn = qn.pattern(C).count("D")
    backward = [op for op in loops if "transpose(jvp" in op
                and "rematted_computation" not in op]
    again = [op for op in loops if "rematted_computation" in op]
    assert len(again) == len(backward) == n_gdn
    assert len(loops) - len(again) - len(backward) == 3 * n_gdn, loops
    assert costs_qn.DELTA_UNITS == 6
    assert costs_qn.gdn_layers(dict(model=dict(
        num_hidden_layers=C["num_hidden_layers"],
        full_attention_interval=C["full_attention_interval"]))) == n_gdn
