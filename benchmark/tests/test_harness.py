"""The harness is indifferent to how large the learner state is and to
what kind of leaves a family has.  A stand-in family that exists only in
this file (a small Q-network over contexts carried as bytes in one frame:
embedding, a gain vector, experts stacked ``[E, in, out]``, a dense head;
its reference module carries ``init_rule``) is driven through
``Run.build()``, ``checked_steps()``, ``reference()`` and ``judge()`` on
the CPU; the trainer is the program's own ``LearnerCore`` and
``FramePoolReplay`` around that network.  For ``dqn`` the seed's weights
hash to what they hashed to before any family could say how its leaves
are drawn.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_harness.py -q
"""

from __future__ import annotations

import gc
import hashlib
import math
import os
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TOKENS, VOCAB, DIM, EXPERTS, HIDDEN, ACTIONS = 16, 512, 64, 4, 128, 8
SEEDS = (11, 2_147_483_659, 4_000_000_007)
#: bytes a parameter that one learner state (parameters, target, two
#: moments) and one more parameter tree come to
STATE_AND_A_TREE = 20
#: what is live beside the trees: a step counter, a batch, keys
SLACK_BYTES = 64 * 1024


def live_bytes() -> int:
    """Bytes of the live device buffers, each once: on the CPU a
    ``device_get`` leaves a second array behind that is a view of the
    first one's buffer."""
    import jax
    return sum({x.unsafe_buffer_pointer(): x.nbytes
                for x in jax.live_arrays()}.values())


def tree_bytes(tree) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree)
               if hasattr(x, "nbytes"))


# -- the stand-in family: the program's side -----------------------------------

def token_ids(obs_u8, xp):
    """A context of ``TOKENS`` ids carried as two bytes each in one frame."""
    b = obs_u8.reshape(obs_u8.shape[0], TOKENS, 2).astype(xp.int32)
    return (b[..., 0] + 256 * b[..., 1]) % VOCAB


def make_model(use_experts: bool = True):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    class Standin(nn.Module):
        @nn.compact
        def __call__(self, obs):
            init = nn.initializers.normal(0.02)
            emb = self.param("embedding", init, (VOCAB, DIM))
            gain = self.param("gain", nn.initializers.ones, (DIM,))
            w_in = self.param("w_in", init, (EXPERTS, DIM, HIDDEN))
            w_out = self.param("w_out", init, (EXPERTS, HIDDEN, DIM))
            x = emb[token_ids(obs, jnp)].mean(axis=1)
            h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                  + 1e-6) * gain
            if use_experts:
                y = jax.nn.relu(jnp.einsum("bd,edh->beh", h, w_in))
                x = x + jnp.einsum("beh,ehd->bd", y, w_out) / EXPERTS
            return nn.Dense(ACTIONS, name="out")(x)

    return Standin()


def make_trainer(cfg, spy, use_experts: bool = True):
    """What the harness reads of a trainer, around the stand-in network:
    the program's replay, optimizer, ``LearnerCore`` and jitted steps."""
    import jax
    import jax.numpy as jnp
    import optax

    from apex_tpu.ops.losses import make_optimizer
    from apex_tpu.replay.frame_pool import FramePoolReplay
    from apex_tpu.training.learner import LearnerCore
    from apex_tpu.training.state import create_train_state

    lc = cfg.learner
    replay = FramePoolReplay(
        capacity=cfg.replay.capacity, frame_shape=(2 * TOKENS,),
        frame_stack=1, frame_dtype="uint8", alpha=cfg.replay.alpha,
        eps=cfg.replay.eps)
    opt = make_optimizer(
        lr=lc.lr, decay=lc.rmsprop_decay, eps=lc.rmsprop_eps,
        centered=lc.rmsprop_centered, max_grad_norm=lc.max_grad_norm,
        lr_decay_steps=lc.lr_decay_steps, lr_decay_rate=lc.lr_decay_rate)
    opt = optax.GradientTransformation(spy.around(opt.init), opt.update)
    model = make_model()
    state = create_train_state(model, opt, jax.random.key(0),
                               jnp.zeros((1, 2 * TOKENS), jnp.uint8))
    core = LearnerCore(apply_fn=make_model(use_experts).apply, replay=replay,
                       optimizer=opt, batch_size=lc.batch_size,
                       target_update_interval=lc.target_update_interval)
    return types.SimpleNamespace(
        train_state=state, replay_state=replay.init(), replay=replay,
        core=core, _fused=core.jit_fused_step(),
        _train=core.jit_train_step(), _ingest=core.jit_ingest(),
        log=types.SimpleNamespace(history={}), pool=object())


# -- the stand-in family: its plain reference -------------------------------------

def make_family(donates: bool) -> types.ModuleType:
    """``benchmark/reference/<family>.py`` as a module object: the hooks a
    family brings, with ``init_rule``; ``donates`` makes its step donate
    the parameters and moments it is given (the harness is not told)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import common as c

    mod = types.ModuleType("benchmark.reference.standin")
    mod.live_after_update = []

    def forward(params, obs_u8, mode):
        p = params["params"]
        x = p["embedding"][token_ids(obs_u8, jnp)].mean(axis=1)
        h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + 1e-6) * p["gain"]
        y = jax.nn.relu(jnp.einsum(
            "bd,edh->beh", c.rnd(h, mode), c.rnd(p["w_in"], mode),
            precision=c.HIGHEST))
        x = x + jnp.einsum("beh,ehd->bd", c.rnd(y, mode),
                           c.rnd(p["w_out"], mode),
                           precision=c.HIGHEST) / EXPERTS
        return c.dense(x, p["out"], mode)

    def loss_fn(params, target_params, batch, weights, mode):
        both = jnp.concatenate([batch["obs"], batch["next_obs"]], axis=0)
        q, next_q = jnp.split(forward(params, both, mode), 2, axis=0)
        tgt_next_q = forward(target_params, batch["next_obs"], mode)
        a = batch["action"].astype(jnp.int32)[:, None]
        q_taken = jnp.take_along_axis(q, a, axis=1)[:, 0]
        boot = jnp.take_along_axis(
            tgt_next_q, next_q.argmax(axis=1)[:, None], axis=1)[:, 0]
        td = jax.lax.stop_gradient(
            batch["reward"] + batch["discount"] * boot) - q_taken
        return (c.huber(td) * weights).mean(), (jnp.abs(td), q_taken)

    def update(params, target_params, opt, batch, weights, lr, *, mode,
               clip, decay, eps):
        (loss, (td_abs, q_taken)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, target_params, batch, weights,
                                   mode)
        grads = c.clip_by_global_norm(grads, clip)
        params, opt = c.rmsprop_centered(grads, opt, params, lr, decay, eps)
        return (params, opt, loss, grads, c.mixed_max_priorities(td_abs),
                q_taken.mean(), jnp.abs(q_taken).mean())

    update = jax.jit(update, static_argnames=("mode", "clip", "decay", "eps"),
                     donate_argnums=(0, 2) if donates else ())

    def step(state, batch, weights, key, hp, mode):
        del key
        params, opt, loss, grads, prios, q_mean, q_abs = update(
            state["params"], state["target_params"], state["opt"], batch,
            weights, jnp.float32(hp["lr"]), mode=mode,
            clip=hp["max_grad_norm"], decay=hp["rmsprop_decay"],
            eps=hp["rmsprop_eps"])
        target = state["target_params"]
        del state
        jax.block_until_ready(params)
        mod.live_after_update.append(live_bytes())
        new = dict(params=params, target_params=target, opt=opt, step=0)
        return new, dict(loss=loss, grads=grads, priorities=prios,
                         q_mean=q_mean, q_abs=q_abs)

    def init_rule(path, shape):
        if path[-1] == "gain":
            return ("const", 1.0)
        if path[-1] in ("w_in", "w_out"):       # [E, in, out]: fan-in `in`
            return ("normal", math.sqrt(2.0 / shape[1]))
        return None

    def init_opt(params, hp):
        # buffers of their own for each moment: a donated tree may not
        # share one with another (``common.rmsprop_centered_init`` does)
        del hp
        return {name: jax.tree.map(jnp.zeros_like, params)
                for name in ("mu", "nu")}

    mod.init_opt = init_opt
    mod.step_keys = lambda key: (key, None)
    mod.step, mod.init_rule = step, init_rule
    return mod


# -- the run ----------------------------------------------------------------------

class Spy:
    """Live device bytes on entry to and return from the calls it wraps."""

    def __init__(self):
        self.samples = []

    def around(self, fn):
        def wrapped(*args, **kw):
            self.samples.append(live_bytes())
            out = fn(*args, **kw)
            self.samples.append(live_bytes())
            return out
        return wrapped


def make_run(monkeypatch, *, programs=("fused", "train", "fused"),
             use_experts=True):
    """A ``Run`` of the stand-in cell, up to ``build()``."""
    from apex_tpu.runtime import cli
    from benchmark import feed, harness

    config = harness.load_json(harness.HERE, "configs", "apex_dqn_ref.json")
    config.update(name="standin", family="standin", rehearsal_argv=[
        "--frame-stack", "1", "--batch-size", "32", "--capacity", "2048",
        "--warmup", "64"])
    config["check"].update(chunks=4, action_count=ACTIONS, limits={
        "writeback_miss": 0, "loss_gap": 1e-3, "grad_gap": 1e-2,
        "dparam_gap": 1e-2})
    traffic = {"name": "standin", "argv": ["--n-actors", "1"],
               "checked_programs": list(programs)}
    cell = {"name": "standin", "config": "standin", "traffic": "standin",
            "chips": 1}
    monkeypatch.setattr(harness, "load_cell", lambda workload: dict(
        bench={}, cell=cell, config=config, traffic=traffic))
    monkeypatch.setitem(sys.modules, "benchmark.reference.standin",
                        make_family(donates=False))
    spy = Spy()
    baseline = []

    def build_trainer(args, cfg):
        tr = make_trainer(cfg, spy, use_experts)
        del spy.samples[:]                  # construction's own init
        gc.collect()
        baseline.append(live_bytes() - tree_bytes(tr.train_state))
        return tr, {}

    monkeypatch.setattr(cli, "build_trainer", build_trainer)
    monkeypatch.setattr(feed, "make_weights", spy.around(feed.make_weights))
    run = harness.Run("standin", SEEDS[0], 0.0, False, True,
                      time.monotonic())
    run.build()
    run.spy, run.baseline = spy, baseline[0]
    run.n_params = sum(x.size for x in _leaves(run.weights0))
    return run


@pytest.fixture(scope="module")
def checked():
    """One stand-in run whose three programs are compiled once; a test
    puts the seed it wants in with ``reset_state`` + ``checked_steps``."""
    with pytest.MonkeyPatch.context() as mp:
        yield make_run(mp)


def _leaves(tree):
    import jax
    return jax.tree.leaves(tree)


def verdict(run, side=None):
    from benchmark import harness
    numbers = run.judge(side)
    return harness.verdict({}, numbers)[0], numbers


# -- (a) one learner state at a time ------------------------------------------------

@pytest.mark.parametrize("phase", ["build", "reset_state"])
def test_set_up_holds_one_learner_state_and_one_tree(monkeypatch, phase):
    """Around ``make_weights`` and ``optimizer.init`` (the target copy lies
    between them) no more than 20 bytes a parameter of learner state are
    live; the harness before PR 28 read 32."""
    run = make_run(monkeypatch)
    if phase == "reset_state":
        del run.spy.samples[:]
        run.reset_state(SEEDS[1])
    assert len(run.spy.samples) == 4
    worst = max(run.spy.samples) - run.baseline
    assert worst <= STATE_AND_A_TREE * run.n_params + SLACK_BYTES, (
        f"{worst / run.n_params:.1f} bytes a parameter live in {phase}")
    # and the state that is there at the end is a whole one
    assert tree_bytes(run.trainer.train_state) == 16 * run.n_params + 8


# -- (b) the family says how its leaves are drawn ---------------------------------------

def test_leaves_are_drawn_by_the_family_s_rule(monkeypatch):
    run = make_run(monkeypatch)
    p = run.weights0["params"]
    assert np.all(p["gain"] == 1.0)
    assert p["w_in"].std() == pytest.approx(math.sqrt(2.0 / DIM), rel=0.03)
    assert p["w_out"].std() == pytest.approx(math.sqrt(2.0 / HIDDEN),
                                             rel=0.03)
    # no rule for these: a kernel by every axis but its last, a vector small
    assert p["out"]["kernel"].std() == pytest.approx(math.sqrt(2.0 / DIM),
                                                     rel=0.1)
    assert np.abs(p["out"]["bias"]).max() < 0.05
    # the trainer holds the very same numbers
    for mine, theirs in zip(_leaves(run.weights0),
                            _leaves(run.trainer.train_state.params)):
        np.testing.assert_array_equal(mine, np.asarray(theirs))


def test_a_rule_of_an_unknown_kind_is_refused():
    import jax

    from benchmark import feed
    shapes = {"w": jax.ShapeDtypeStruct((4, 4), np.float32)}
    with pytest.raises(ValueError, match="uniform"):
        feed.make_weights(shapes, 1, lambda path, shape: ("uniform", 1.0))


@pytest.mark.parametrize("seed, sha256", [
    (11, "e4eaa5022a08d5567daffc259dc342d89f7bec13a3ad33decad9f2fbf9ef1695"),
    (2_147_483_659,
     "69cd1a6e8a5da40ddd8c63c592673514ab625e8a746815047b61ac018166a123"),
    (4_000_000_007,
     "98a1142b988d3b47ed2a9748720c9e0a44b3c46dae385deaf00961e302422970"),
], ids=[str(seed) for seed in SEEDS])
def test_dqn_draws_the_bits_it_drew(seed, sha256):
    """``apex_dqn_ref``'s tree, hashed leaf by leaf in flattening order on
    the CPU at commit ca80edd (PR 27), before ``init_rule`` was there."""
    import jax

    from benchmark import feed
    from benchmark.reference import dqn
    kernels = {"Conv_0": (8, 8, 4, 32), "Conv_1": (4, 4, 32, 64),
               "Conv_2": (3, 3, 64, 64), "advantage_hidden": (3136, 128),
               "advantage_out": (128, 3), "value_hidden": (3136, 128),
               "value_out": (128, 1)}
    shapes = {"params": {name: {
        "bias": jax.ShapeDtypeStruct(shape[-1:], np.float32),
        "kernel": jax.ShapeDtypeStruct(shape, np.float32)}
        for name, shape in kernels.items()}}
    weights = feed.make_weights(shapes, seed,
                                getattr(dqn, "init_rule", None))
    h = hashlib.sha256()
    for leaf in _leaves(weights):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    assert h.hexdigest() == sha256


# -- (c) the comparison sees the stacked leaf ---------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_sound_program_is_correct(checked, seed):
    run = checked
    run.reset_state(seed)
    run.checked_steps()
    ok, numbers = verdict(run)
    assert ok, numbers
    assert set(numbers) == {"writeback_miss", "loss_gap", "grad_gap",
                            "dparam_gap"}


def test_experts_left_out_of_the_program_is_not_correct(monkeypatch):
    run = make_run(monkeypatch, use_experts=False)
    run.checked_steps()
    ok, numbers = verdict(run)
    assert not ok, numbers
    assert numbers["loss_gap"][0] > numbers["loss_gap"][1]
    # the experts' gradient is nought on the program's side
    assert numbers["grad_gap"][0] == pytest.approx(1.0)


# -- (d) one_program_each reads the programs the mix names -----------------------------------

def test_a_mix_that_never_trains_alone_is_not_held_to_that_program(
        monkeypatch):
    run = make_run(monkeypatch, programs=("fused", "fused", "fused"))
    run.checked_steps()
    run.open = dict(compiles=0, steps=0, frames=0)
    run.close = dict(compiles=0, steps=3, frames=150)
    checks = run.liveness()
    assert checks["one_program_each"]
    assert run.trainer._train._cache_size() == 0
    assert run.trainer._fused._cache_size() == 1
    assert verdict(run)[0]


def test_a_second_program_for_a_named_step_is_seen(checked, monkeypatch):
    run = checked
    run.open = dict(compiles=0, steps=0, frames=0)
    run.close = dict(compiles=0, steps=3, frames=150)
    assert run.liveness()["one_program_each"]
    monkeypatch.setattr(run.trainer, "_train",
                        types.SimpleNamespace(_cache_size=lambda: 2))
    assert not run.liveness()["one_program_each"]


# -- (e) a reference whose step consumes the state it is given ------------------------------------------

def reference_peak(run, **kw) -> tuple[dict, float]:
    """``reference()``'s readings and the most device bytes a parameter
    that were live after one of its updates, over what was live before."""
    gc.collect()
    before = live_bytes()
    del run.family.live_after_update[:]
    got = run.reference("f32", **kw)
    return got, (max(run.family.live_after_update) - before) / run.n_params


@pytest.fixture
def consuming(checked, monkeypatch):
    """The checked run, its family's step donating what it is given."""
    monkeypatch.setattr(checked, "family", make_family(donates=True))
    checked.reset_state(SEEDS[0])
    checked.checked_steps()
    return checked


def test_a_consuming_reference_holds_twenty_bytes_a_parameter(
        consuming, monkeypatch):
    run = consuming
    got, peak = reference_peak(run)
    assert peak <= STATE_AND_A_TREE + SLACK_BYTES / run.n_params, peak
    ok, numbers = verdict(run)
    assert ok, numbers
    # the same readings as the family that keeps what it is given
    monkeypatch.setattr(run, "family", make_family(donates=False))
    plain, plain_peak = reference_peak(run)
    assert plain_peak >= 27.0, plain_peak       # old and new state, gradient
    assert got["losses"] == plain["losses"]
    assert got["dparam_norm"] == plain["dparam_norm"]


@pytest.mark.parametrize("donates", (True, False))
def test_a_frozen_reference_has_not_moved(checked, monkeypatch, donates):
    # the fault keeps the old state, so the step is handed a copy to eat
    run = checked
    monkeypatch.setattr(run, "family", make_family(donates=donates))
    run.reset_state(SEEDS[0])
    run.checked_steps()
    got = run.reference("f32", fault="frozen")
    assert set(got["dparam_norm"].values()) == {0.0}
    ok, numbers = verdict(run, got)
    assert not ok
    assert numbers["dparam_gap"][0] == pytest.approx(1.0)


def test_the_norm_programs_are_unloaded_after_use(checked):
    # a loaded executable holds device memory through the window
    from benchmark import harness
    checked.reset_state(SEEDS[1])
    checked.checked_steps()
    assert [p._cache_size() for p in harness._norm_programs()] == [0, 0]
    checked.reference("f32")
    assert [p._cache_size() for p in harness._norm_programs()] == [0, 0]
