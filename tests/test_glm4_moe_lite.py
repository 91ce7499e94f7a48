"""The GLM-4.7-Flash torso (apex_tpu/models/glm4_moe_lite.py) against its
plain reference (benchmark/reference/glm4_moe_lite_q.py), at the toy preset
on the CPU, with seeded weights drawn as the benchmark draws them.

(a) Q rows, (b) one learner update, (c) the expert shares of all ranks add
up to the uncut layer, (d) no pair is dropped under the most uneven
routing, (e) the next-state pass outside the differentiated function
changes no gradient, (f) without ``--torso`` nothing moved.
"""

from __future__ import annotations

import hashlib
import os
import sys

import numpy as np
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from apex_tpu.models import (DEFAULT_TORSO, acting_params,  # noqa: E402
                             learner_apply_fn, make_q_network)
from apex_tpu.models import glm4_moe_lite as glm  # noqa: E402
from apex_tpu.ops.losses import (double_dqn_loss, huber,  # noqa: E402
                                 make_optimizer, mixed_max_priorities)
from apex_tpu.training.learner import td_update  # noqa: E402
from apex_tpu.training.state import create_train_state  # noqa: E402
from benchmark import feed  # noqa: E402
from benchmark.reference import glm4_moe_lite_q as ref  # noqa: E402

PRESET = "glm47_flash_tiny"
C = glm.PRESETS[PRESET]
B, T, V = 4, C["context"], C["vocab_held"]
HP = dict(lr=6.25e-5, lr_decay_steps=1000, lr_decay_rate=0.99,
          rmsprop_decay=0.95, rmsprop_eps=1.5e-7, max_grad_norm=40.0,
          target_update_interval=2500)


def model(dtype=jnp.float32, preset: str = PRESET, **kw):
    return make_q_network(dict(
        torso=preset, num_actions=glm.PRESETS[preset]["vocab_held"],
        compute_dtype=dtype, **kw))


def seeded(m, seed: int):
    shapes = jax.eval_shape(m.init, jax.random.key(0),
                            jnp.zeros((1, 2 * T), jnp.uint8))
    return feed.make_weights(shapes, seed, ref.init_rule)


def batch_of(seed: int, B: int = B, T: int = T):
    rng = np.random.default_rng(seed)
    return dict(
        obs=jnp.asarray(rng.integers(0, 256, (B, 2 * T), dtype=np.uint8)),
        next_obs=jnp.asarray(rng.integers(0, 256, (B, 2 * T),
                                          dtype=np.uint8)),
        action=jnp.asarray(rng.integers(0, V, B).astype(np.int32)),
        reward=jnp.asarray(rng.normal(0, 0.5, B).astype(np.float32)),
        discount=jnp.asarray(np.where(rng.random(B) < 0.25, 0.0,
                                      0.99 ** 3).astype(np.float32)))


@pytest.fixture(scope="module")
def params():
    return seeded(model(), 11)


# -- (a) Q rows ---------------------------------------------------------------

def test_q_rows_equal_the_reference_in_float32(params):
    obs = batch_of(1)["obs"]
    q = jax.jit(model().apply)(params, obs)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, obs)
    assert q.shape == (B, V) and q.dtype == jnp.float32
    np.testing.assert_allclose(q, want, rtol=1e-5, atol=1e-5)


def test_q_rows_in_bfloat16_stay_near_the_reference(params):
    """bfloat16 operands: 8 bits of mantissa through three layers, and a
    pick that flips where two router scores are close; Q is of order 1.
    The stated tolerance: the mean distance under 3% of mean |Q| and no
    entry further than 20% of it (read: 2% and 10%; the reference with
    bfloat16 operands reads the same)."""
    obs = batch_of(2)["obs"]
    q = jax.jit(model(jnp.bfloat16).apply)(params, obs)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, obs)
        stated = ref.forward(params, obs, "bf16")
    scale = float(jnp.abs(want).mean())
    for got in (q, stated):
        assert float(jnp.abs(got - want).mean()) < 0.03 * scale
        assert float(jnp.abs(got - want).max()) < 0.2 * scale
    # and the acting snapshot multiplies the same bits from half the bytes
    m16 = model(jnp.bfloat16)
    snap = acting_params(m16, params)
    kernel = snap["params"]["layers_1"]["moe"]["experts_up"]
    assert kernel.dtype == jnp.bfloat16
    assert snap["params"]["layers_1"]["moe"]["router_kernel"].dtype == \
        jnp.float32
    np.testing.assert_array_equal(jax.jit(m16.apply)(snap, obs), q)


# -- (b) one update -----------------------------------------------------------

def test_one_update_equals_the_reference_step(params):
    m = model()
    batch, weights = batch_of(3), jnp.linspace(0.5, 1.0, B)
    opt = make_optimizer()
    ts = create_train_state(m, opt, jax.random.key(0),
                            jnp.zeros((1, 2 * T), jnp.uint8))
    target = seeded(m, 12)
    ts = ts.replace(params=params, target_params=target,
                    opt_state=opt.init(params))

    def loss_fn(p):
        return double_dqn_loss(learner_apply_fn(m), p, target, batch,
                               weights)

    new, prios, metrics = jax.jit(
        lambda ts: td_update(opt, 2500, ts, loss_fn, None))(ts)
    # the clipped gradient, as the harness reads it: RMSprop's first
    # moment after one step is (1 - decay) times it
    clipped = jax.tree.map(lambda mu: 20.0 * mu, new.opt_state[1][0].mu)
    state = dict(params=jax.tree.map(jnp.copy, params),
                 target_params=target, opt=ref.init_opt(params, HP), step=0)
    with jax.default_matmul_precision("highest"):
        want, out = ref.step(state, batch, weights, None, HP, "f32")
    assert float(metrics["loss"]) == pytest.approx(float(out["loss"]),
                                                   rel=1e-5)
    np.testing.assert_allclose(prios, out["priorities"], rtol=1e-4,
                               atol=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(clipped),
                            jax.tree.leaves(out["grads"])):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-6,
                                   err_msg=str(path))
    for (path, p), w in zip(jax.tree_util.tree_leaves_with_path(new.params),
                            jax.tree.leaves(want["params"])):
        np.testing.assert_allclose(p, w, rtol=1e-4, atol=1e-6,
                                   err_msg=str(path))
    # the routing counters of the three passes leave among the metrics
    pairs = B * T * C["n_expert_layers"] * C["num_experts_per_tok"]
    for suffix in ("", "_next", "_target"):
        assert 0 < float(metrics["moe_local_pairs" + suffix]) < pairs
        assert float(metrics["moe_load_max_over_mean" + suffix]) >= 1.0
        # the rounds past the first that ran, of three a layer
        overflow = float(metrics["moe_overflow_rounds" + suffix])
        assert overflow == int(overflow)
        assert 0 <= overflow <= 3 * C["n_expert_layers"]


# -- (c), (d) the expert layer's shares ---------------------------------------

def moe_layer(held: int, rank: int):
    return glm.MoE(jnp.float32, C["moe_intermediate_size"],
                   C["n_routed_experts"], held, rank,
                   C["num_experts_per_tok"], C["routed_scaling_factor"])


def uncut_moe_params(seed: int):
    e = C["n_routed_experts"]
    shapes = jax.eval_shape(
        moe_layer(e, 0).init, jax.random.key(0),
        jnp.zeros((1, T, C["hidden_size"])))
    return feed.make_weights(shapes, seed, ref.init_rule)["params"]


def rank_slice(p: dict, rank: int, held: int) -> dict:
    cut = {k: (v[rank * held:(rank + 1) * held] if k.startswith("experts_")
               else v) for k, v in p.items()}
    return {"params": cut}


def reference_moe(p, h):
    m = dict(ref.model_of({"params": {"embedding": jnp.zeros(
        (V, C["hidden_size"]))}}))
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda x: ref.moe(x, p, m, "f32"))(h)


def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer(grouped_rule):
    """4 ranks x 2 of 8 experts: the routed parts of every rank, with the
    shared expert counted once, are the uncut reference's layer output."""
    p = uncut_moe_params(21)
    h = jax.random.normal(jax.random.key(5), (B, T, C["hidden_size"]))
    held = 2
    shared = glm.SwiGLU(jnp.float32, C["moe_intermediate_size"]).apply(
        {"params": p["shared"]}, h)
    total, pairs = shared, 0
    for rank in range(C["n_routed_experts"] // held):
        out, (counts, _) = moe_layer(held, rank).apply(
            rank_slice(p, rank, held), h)
        total = total + (out - shared)
        pairs += int(counts.sum())
    assert pairs == B * T * C["num_experts_per_tok"]    # every pair, once
    np.testing.assert_allclose(total, reference_moe(p, h), rtol=1e-4,
                               atol=1e-5)
    # one rank alone is the reference given that rank's share (rank 0)
    out, _ = moe_layer(held, 0).apply(rank_slice(p, 0, held), h)
    np.testing.assert_allclose(
        out, reference_moe(rank_slice(p, 0, held)["params"], h), rtol=1e-4,
        atol=1e-5)


def test_no_pair_is_dropped_when_every_token_picks_the_same_experts(
        grouped_rule):
    """A router bias that sends every token to experts 0 and 1, both held
    here: all ``N k`` pairs land on this rank, every round runs, and the
    layer still equals the reference."""
    p = uncut_moe_params(22)
    p = dict(p, router_bias=p["router_bias"].at[:2].add(10.0))
    h = jax.random.normal(jax.random.key(6), (B, T, C["hidden_size"]))
    held = 2
    out, (counts, overflow) = moe_layer(held, 0).apply(
        rank_slice(p, 0, held), h)
    assert int(counts.sum()) == B * T * C["num_experts_per_tok"]
    np.testing.assert_array_equal(counts, [B * T, B * T])
    assert int(overflow) == 3       # a quarter of the pairs a round
    np.testing.assert_allclose(
        out, reference_moe(rank_slice(p, 0, held)["params"], h), rtol=1e-4,
        atol=1e-5)
    # and the gradient reaches every held expert's weights
    g = jax.grad(lambda q: moe_layer(held, 0).apply(
        {"params": q}, h)[0].sum())(rank_slice(p, 0, held)["params"])
    assert float(jnp.abs(g["experts_down"]).min(axis=(1, 2)).max()) >= 0.0
    assert all(float(jnp.abs(g[k][e]).max()) > 0
               for k in ("experts_gate", "experts_up", "experts_down")
               for e in range(held))


#: the rounds of each family's expert layer at its published widths, by
#: the ``lax.cond`` that runs them, a call of 16 contexts (the update's
#: passes and the 16-lane acting call; Qwen3-Next's blocks of 8) and of
#: one (the call that initialises the parameters): round 0 twice the held
#: share (at most a quarter of the pairs, half in LFM2, whose held share is
#: a quarter), later rounds a quarter, the fifth in the fourth's cond
ROUNDS = {
    ("glm47_flash_ep8", 16): ((16384,),) * 4,
    ("glm47_flash_ep8", 1): ((1024,),) * 4,
    ("nemotron_twotower_ep16", 16): ((12288,), (24576,), (24576,),
                                     (24576, 12288)),
    ("nemotron_twotower_ep16", 1): ((1024,), (1536,), (1536,), (1536, 512)),
    ("qwen3_next_80b_ep16", 16): ((10240,), (20480,), (20480,),
                                  (20480, 10240)),
    ("qwen3_next_80b_ep16", 1): ((1536,), (2560,), (2560,), (2560, 1024)),
    ("lfm2_8b_a1b_ep4", 16): ((32768,), (16384,), (16384,)),
    ("lfm2_8b_a1b_ep4", 1): ((2048,), (1024,), (1024,)),
}


@pytest.mark.parametrize("preset,contexts", sorted(ROUNDS))
def test_the_first_round_holds_twice_the_held_share(monkeypatch, preset,
                                                    contexts):
    """Each expert layer of a published preset, traced at a call's shape:
    the rounds cover every pair, round 0 is the rule's value (twice the
    held experts' even share up to a multiple of 512 rows, a quarter of
    the pairs at most or the quarters the family gives), no later round
    is larger than a quarter and no more conds hand back gradients than
    four rounds of a quarter did.  At
    Nemotron's widths every round is one the tiled kernel takes."""
    from apex_tpu.models import token_preset
    from apex_tpu.ops import grouped
    real, calls = glm.expert_rounds, []

    def spied(pairs, held, routed, quarters=1):
        calls.append((pairs, held, routed, quarters,
                      real(pairs, held, routed, quarters)))
        return calls[-1][-1]

    monkeypatch.setattr(glm, "expert_rounds", spied)
    c = token_preset(preset)
    m = make_q_network(dict(torso=preset, num_actions=c["vocab_held"],
                            compute_dtype=jnp.bfloat16))
    jax.eval_shape(m.init, jax.random.key(0),
                   jnp.zeros((contexts, 2 * c["context"]), jnp.uint8))
    # each of the four expert layers asks, for its rounds and its counter
    # (Qwen3-Next's block loop traces a body twice)
    assert len(calls) >= 8
    for pairs, held, routed, quarters, groups in calls:
        assert groups == ROUNDS[preset, contexts]
        assert quarters == (2 if preset.startswith("lfm2") else 1)
        rounds = [rows for group in groups for rows in group]
        assert sum(rounds) == pairs
        assert rounds[0] == min(-(-2 * pairs * held // (routed * 512)) * 512,
                                pairs * quarters // 4)
        assert max(rounds[1:]) <= pairs // 4 and len(groups) <= 4
        if preset.startswith("nemotron"):
            assert all(grouped.plan(2688, 1856, rows, "tpu") is not None
                       for rows in rounds)


# -- (e) the next-state pass ---------------------------------------------------

def concatenated_loss(apply_fn, params, target_params, batch, weights):
    """``double_dqn_loss`` as it stood before PR 29: online(s) and
    online(s') in one pass inside the differentiated function."""
    both = jnp.concatenate([batch["obs"], batch["next_obs"]], axis=0)
    q, next_q = jnp.split(apply_fn(params, both), 2, axis=0)
    tgt = apply_fn(target_params, batch["next_obs"])
    a = batch["action"].astype(jnp.int32)[:, None]
    q_taken = jnp.take_along_axis(q, a, axis=1)[:, 0]
    boot = jnp.take_along_axis(tgt, next_q.argmax(axis=1)[:, None],
                               axis=1)[:, 0]
    td = jax.lax.stop_gradient(
        batch["reward"] + batch["discount"] * boot) - q_taken
    return (huber(td) * weights).mean(), mixed_max_priorities(jnp.abs(td))


@pytest.mark.parametrize("torso", [DEFAULT_TORSO, PRESET])
def test_next_state_pass_outside_the_gradient_changes_nothing(torso, params):
    if torso == DEFAULT_TORSO:
        m = make_q_network(dict(num_actions=5, obs_is_image=False,
                                compute_dtype=jnp.float32,
                                scale_uint8=False))
        rng = np.random.default_rng(0)
        batch = dict(obs=jnp.asarray(rng.normal(size=(8, 6)), jnp.float32),
                     next_obs=jnp.asarray(rng.normal(size=(8, 6)),
                                          jnp.float32),
                     action=jnp.asarray(rng.integers(0, 5, 8)),
                     reward=jnp.asarray(rng.normal(size=8), jnp.float32),
                     discount=jnp.full(8, 0.97, jnp.float32))
        p = m.init(jax.random.key(1), batch["obs"])
        target = m.init(jax.random.key(2), batch["obs"])
    else:
        m, p, batch = model(), params, batch_of(4)
        target = seeded(m, 13)
    w = jnp.linspace(0.5, 1.0, batch["reward"].shape[0])
    (loss, out), g = jax.jit(jax.value_and_grad(
        lambda q: double_dqn_loss(m.apply, q, target, batch, w),
        has_aux=True))(p)
    (want_loss, want_prios), want = jax.jit(jax.value_and_grad(
        lambda q: concatenated_loss(m.apply, q, target, batch, w),
        has_aux=True))(p)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    np.testing.assert_allclose(out.priorities, want_prios, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


# -- (f) the default torso ------------------------------------------------------

#: ``benchmark/tests/test_harness.py``: the dqn tree's weights hashed leaf
#: by leaf at commit ca80edd (PR 27)
DQN_SHA256 = {
    11: "e4eaa5022a08d5567daffc259dc342d89f7bec13a3ad33decad9f2fbf9ef1695",
    2_147_483_659:
        "69cd1a6e8a5da40ddd8c63c592673514ab625e8a746815047b61ac018166a123",
    4_000_000_007:
        "98a1142b988d3b47ed2a9748720c9e0a44b3c46dae385deaf00961e302422970",
}


@pytest.mark.parametrize("seed", sorted(DQN_SHA256))
def test_without_torso_the_tree_and_the_program_are_as_before(seed):
    from apex_tpu.models.dueling import DuelingDQN
    from apex_tpu.runtime.cli import build_parser, config_from_args
    from apex_tpu.training.apex import dqn_env_specs
    from benchmark.reference import dqn

    args = build_parser().parse_args(
        ["--role", "apex", "--env-id", "ApexCatch-v0", "--frame-stack", "4"])
    cfg = config_from_args(args)
    assert cfg.learner.torso == DEFAULT_TORSO
    assert cfg.actor.send_interval == 50
    spec, frame_shape, _dtype, stack = dqn_env_specs(cfg)
    assert spec == dict(num_actions=3, obs_is_image=True,
                        compute_dtype=jnp.dtype("bfloat16"),
                        scale_uint8=True)
    m = make_q_network(spec)
    assert m == DuelingDQN(**spec)
    shapes = jax.eval_shape(
        m.init, jax.random.key(0),
        jnp.zeros((1,) + frame_shape[:-1] + (stack,), jnp.uint8))
    weights = feed.make_weights(shapes, seed,
                                getattr(dqn, "init_rule", None))
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(weights):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    assert h.hexdigest() == DQN_SHA256[seed]
    # the learner's apply function is the module's own: no wrapper, no
    # second output, one program a step as before
    assert learner_apply_fn(m) == m.apply


#: the toy's gradient program as ``jax.jit(...).lower(...).as_text()`` gives
#: it (no locations in it), hashed at commit afb7785, before PR 33 made the
#: expert layer every token torso's (``FeedForward``'s and ``MoE``'s ``act``
#: and ``shared_width`` fields, ``routed(*kernels)``), with jax 0.9.0
UPDATE_SHA256 = {"float32": "a2be7f7cb2542c0d", "bfloat16": "64ab5c7560315548"}
#: the version whose lowering was hashed (``pyproject.toml`` asks for
#: ``jax>=0.9`` and pins none): under another the text differs for reasons
#: that are not this program's, so the case is skipped and not failed, and
#: the PR that moves jax hashes the program anew
UPDATE_JAX = "0.9.0"


@pytest.mark.skipif(jax.__version__ != UPDATE_JAX,
                    reason=f"hashed as jax {UPDATE_JAX} lowers it")
@pytest.mark.parametrize("dtype", sorted(UPDATE_SHA256))
def test_lowered_update_is_the_program_it_was(dtype):
    """Sharing the expert layer with another torso left this torso's
    lowered update text-identical: same operations in the same order on
    the same parameter tree.  A PR that changes this program on purpose
    records the new hash here and says so."""
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


def test_presets_hold_what_the_issue_counts():
    assert glm.param_count("glm47_flash_ep8") == 591_265_792 + 29_184
    shapes = jax.eval_shape(model().init, jax.random.key(0),
                            jnp.zeros((1, 2 * T), jnp.uint8))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == \
        glm.param_count(PRESET)


# -- what the compiled update asks of the grouped kernel ----------------------

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
    T = glm.PRESETS[preset]["context"]

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    p = described(jax.eval_shape(m.init, jax.random.key(0),
                                 jnp.zeros((1, 2 * T), jnp.uint8)))
    batch = described(jax.eval_shape(lambda: batch_of(3, rows, T)))
    weights = described(jax.ShapeDtypeStruct((rows,), jnp.float32))

    def grads(params, target, batch, weights):
        return jax.grad(lambda q: double_dqn_loss(
            learner_apply_fn(m), q, target, batch, weights)[0])(params)

    return jax.jit(grads).lower(p, p, batch, weights).compile().as_text()


def test_compiled_update_calls_the_grouped_kernel_as_the_roofline_counts():
    """``experts_roofline`` weighs each pass by the forward-sized runs of
    the three grouped products the compiled update makes for it
    (``costs_glm4_moe_lite_q.EXPERT_UNITS``), and ``torso_scopes`` places
    the kernel XLA:TPU makes of ``lax.ragged_dot`` by its name, because
    the compiler leaves no ``jax.named_scope`` path on it.  Both are read
    off the program compiled for the chip: the calls outside every
    ``lax.cond`` branch (the first round of each layer, the one that runs
    whatever the routing), their names and their ``op_name``."""
    import re

    from benchmark import costs_glm4_moe_lite_q as costs_glm
    from benchmark import torso_scopes

    hlo = _tpu_update_hlo()
    entry = hlo[hlo.index("\nENTRY "):]
    calls = re.findall(r"\n\s*(%[\w.\-]+) = [^\n]*custom-call\([^\n]*"
                       r"tpu_custom_call[^\n]*op_name=\"([^\"]*)\"", entry)
    ragged = [(n, op) for n, op in calls if "ragged" in n]
    # the products; ``ragged-dot-metadata`` lays out their groups
    kernels = [(n, op) for n, op in ragged if "metadata" not in n]
    assert kernels, "lax.ragged_dot is no kernel of its own any more"
    want = 3 * sum(costs_glm.EXPERT_UNITS.values()) * C["n_expert_layers"]
    assert len(kernels) == want, (len(kernels), want)
    for name, op_name in ragged:
        assert torso_scopes.scope_of(op_name + ":") is None, op_name
        assert torso_scopes.op_scope(name, op_name) == "experts"
    # and what stands around them does carry the path
    assert re.search(r'op_name="[^"]*/router/[^"]*/experts/', entry)


def test_compiled_update_keeps_attention_in_the_fused_kernel(monkeypatch):
    """At widths the kernel takes (``ops.attention.kernel_eligible``; the
    toy's other widths, so the program stays small) the update compiled
    for the chip holds, for every attention layer, four forward kernels
    (online(s), the same made again under the layer's ``nn.remat``,
    online(s') and the target pass) and the two of the backward pass
    (``dkv``, ``dq``): no second rematerialisation.  Each carries its
    ``jax.named_scope`` path, so ``torso_scopes`` places it under ``mla``
    without an entry in ``KERNELS``; and no float32 ``[.., T, T]`` buffer
    is anywhere in the program: scores and softmax stay on the chip."""
    import re

    from apex_tpu.ops import attention
    from benchmark import torso_scopes

    wide = dict(C, context=1024, qk_nope_head_dim=96, qk_rope_head_dim=32,
                v_head_dim=128)
    monkeypatch.setitem(glm.PRESETS, "eligible_toy", wide)
    t = wide["context"]
    assert attention.attention_path(t, 128, 128, "tpu")["fused"] == 1
    hlo = _tpu_update_hlo("eligible_toy", rows=2)
    calls = re.findall(r"\n\s*(%[\w.\-]+) = [^\n]*custom-call\([^\n]*"
                       r"tpu_custom_call[^\n]*op_name=\"([^\"]*)\"", hlo)
    kernels = [(n, op) for n, op in calls if "flash" in op]
    layers = wide["n_dense_layers"] + wide["n_expert_layers"]
    backward = [n for n, _op in kernels if "bwd" in n]
    assert len(kernels) - len(backward) == 4 * layers, kernels
    assert len(backward) == 2 * layers, backward
    assert sum("dkv" in n for n in backward) == layers
    for name, op_name in kernels:
        assert not any(name.lstrip("%").startswith(k)
                       for k in torso_scopes.KERNELS)
        assert torso_scopes.scope_of(op_name + ":") == "mla", op_name
        assert torso_scopes.op_scope(name, op_name) == "mla"
    assert not re.search(rf"f32\[[\d,]*{t},{t}\]", hlo)
