"""Sharded learner on the 8-device virtual CPU mesh: compiles, runs, keeps
params replicated, and matches single-device grad math."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.dueling import DuelingDQN
from apex_tpu.parallel.learner import ShardedLearner
from apex_tpu.parallel.mesh import make_mesh
from apex_tpu.training.learner import build_learner


def _mk_batch(rng, k, dim=6, n_act=3):
    return dict(
        obs=rng.normal(size=(k, dim)).astype(np.float32),
        action=rng.integers(0, n_act, k).astype(np.int32),
        reward=rng.normal(size=k).astype(np.float32),
        next_obs=rng.normal(size=(k, dim)).astype(np.float32),
        discount=np.full(k, 0.99 ** 3, np.float32))


def test_mesh_construction():
    mesh = make_mesh()
    assert mesh.shape["dp"] == 8 and mesh.shape["tp"] == 1


def test_sharded_fused_step_runs_and_replicates(key):
    mesh = make_mesh()
    model = DuelingDQN(num_actions=3, obs_is_image=False,
                       compute_dtype=jnp.float32, scale_uint8=False)
    example = jnp.zeros((1, 6), jnp.float32)
    core, ts, _ = build_learner(model, 256, example, key, batch_size=64,
                                target_update_interval=4)
    sl = ShardedLearner(core, mesh)

    example_item = dict(obs=jnp.zeros(6), action=jnp.int32(0),
                        reward=jnp.float32(0), next_obs=jnp.zeros(6),
                        discount=jnp.float32(0))
    rs = sl.init_replay(example_item)
    assert rs.sum_tree.shape == (8, 2 * 256)
    ts = sl.replicate_train_state(ts)

    step = sl.make_fused_step()
    rng = np.random.default_rng(0)

    for i in range(5):
        ingest, prios = sl.split_ingest(_mk_batch(rng, 64),
                                        np.ones(64, np.float32))
        keys = sl.device_keys(jax.random.key(i))
        ts, rs, metrics = step(ts, rs, ingest, prios, keys,
                               jnp.float32(0.4))

    assert int(ts.step) == 5
    assert np.isfinite(float(metrics["loss"]))
    # every shard ingested 5 * 8 = 40 transitions
    np.testing.assert_array_equal(np.asarray(rs.size), np.full(8, 40))
    # params replicated: all device shards identical
    p = jax.tree.leaves(ts.params)[0]
    assert p.sharding.is_fully_replicated


def test_sharded_r2d2_fused_step_runs_and_replicates(key):
    """The recurrent family on the dp mesh: sequence replay shards + the
    same pmean plan — ShardedLearner is duck-typed over cores, and
    R2D2Core's update signature matches the single-optimizer shape."""
    from apex_tpu.ops.losses import make_optimizer
    from apex_tpu.models.recurrent import RecurrentDuelingDQN
    from apex_tpu.replay.device import DeviceReplay
    from apex_tpu.training.r2d2 import R2D2Core
    from apex_tpu.training.state import TrainState

    mesh = make_mesh()
    burn, unroll, n, t_total, h = 2, 4, 2, 8, 8
    model = RecurrentDuelingDQN(num_actions=3, obs_is_image=False,
                                compute_dtype=jnp.float32,
                                scale_uint8=False, lstm_features=h)
    optimizer = make_optimizer(lr=1e-3)
    carry0 = model.initial_state(1)
    params = model.init(key, jnp.zeros((1, t_total, 5)), carry0)
    ts = TrainState(params=params,
                    target_params=jax.tree.map(jnp.copy, params),
                    opt_state=optimizer.init(params), step=jnp.int32(0))
    replay = DeviceReplay(capacity=64)
    core = R2D2Core(model=model, replay=replay, optimizer=optimizer,
                    batch_size=16, target_update_interval=4,
                    burn_in=burn, n_steps=n)
    sl = ShardedLearner(core, mesh)
    example_item = dict(
        obs=jnp.zeros((t_total, 5)), action=jnp.zeros(t_total, jnp.int32),
        reward=jnp.zeros(t_total), discount=jnp.zeros(t_total),
        mask=jnp.zeros(t_total),
        state_c=jnp.zeros(h), state_h=jnp.zeros(h))
    rs = sl.init_replay(example_item)
    ts = sl.replicate_train_state(ts)

    step = sl.make_fused_step()
    rng = np.random.default_rng(3)

    def seq_chunk(k):
        return dict(
            obs=rng.normal(size=(k, t_total, 5)).astype(np.float32),
            action=rng.integers(0, 3, (k, t_total)).astype(np.int32),
            reward=rng.normal(size=(k, t_total)).astype(np.float32),
            discount=np.full((k, t_total), 0.97, np.float32),
            mask=np.ones((k, t_total), np.float32),
            state_c=np.zeros((k, h), np.float32),
            state_h=np.zeros((k, h), np.float32))

    for i in range(3):
        ingest, prios = sl.split_ingest(seq_chunk(16),
                                        np.ones(16, np.float32))
        ts, rs, metrics = step(ts, rs, ingest, prios,
                               sl.device_keys(jax.random.key(i)),
                               jnp.float32(0.4))

    assert int(ts.step) == 3
    assert np.isfinite(float(metrics["loss"]))
    np.testing.assert_array_equal(np.asarray(rs.size), np.full(8, 6))
    assert jax.tree.leaves(ts.params)[0].sharding.is_fully_replicated


def test_dp_divisibility_guards_are_value_errors(key):
    """The batch/dp and chunk/dp guards must survive ``python -O`` (a
    bare assert would vanish and fail later as an opaque reshape inside
    the shard_map trace) and must name the config knobs to fix."""
    mesh = make_mesh()
    model = DuelingDQN(num_actions=3, obs_is_image=False,
                       compute_dtype=jnp.float32, scale_uint8=False)
    example = jnp.zeros((1, 6), jnp.float32)
    core, _, _ = build_learner(model, 256, example, key, batch_size=60)
    sl = ShardedLearner(core, mesh)            # 60 % 8 != 0
    with pytest.raises(ValueError, match="batch_size"):
        sl.make_fused_step()
    with pytest.raises(ValueError, match="mesh_shape"):
        sl.make_train_step()
    with pytest.raises(ValueError, match="send_interval"):
        sl.split_ingest({"x": np.arange(12)}, np.arange(12.0))


def test_split_ingest_round_robin():
    mesh = make_mesh()
    core_dummy = None  # split_ingest only uses n_dp

    class SL(ShardedLearner):
        pass

    sl = ShardedLearner.__new__(ShardedLearner)
    object.__setattr__(sl, "core", core_dummy)
    object.__setattr__(sl, "mesh", mesh)

    batch = {"x": np.arange(16)}
    prios = np.arange(16.0)
    split, sp = sl.split_ingest(batch, prios)
    # transition i lands on chip i % 8
    np.testing.assert_array_equal(split["x"][:, 0], np.arange(8))
    np.testing.assert_array_equal(split["x"][:, 1], np.arange(8, 16))
    np.testing.assert_array_equal(sp[3], [3.0, 11.0])


def test_dp8_update_matches_single_device_math(key):
    """The DP numerical contract: pmean of per-shard grads on an evenly
    split batch == full-batch gradient, so the sharded update must produce
    (near-)identical params to the single-device update on the same data."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh()
    model = DuelingDQN(num_actions=3, obs_is_image=False,
                       compute_dtype=jnp.float32, scale_uint8=False)
    example = jnp.zeros((1, 6), jnp.float32)
    core, ts, _ = build_learner(model, 256, example, key, batch_size=64)
    rng = np.random.default_rng(3)
    batch = {k: jnp.asarray(v) for k, v in _mk_batch(rng, 64).items()}
    weights = jnp.asarray(rng.uniform(0.5, 1.0, 64).astype(np.float32))

    ts1, _, m1 = core.update_from_batch(ts, batch, weights)

    def per_chip(ts, b, w):
        b = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), b)
        new_ts, prios, m = core.update_from_batch(ts, b, w.reshape(-1),
                                                  axis_name="dp")
        return new_ts, m

    shard = lambda x: x.reshape((8, 8) + x.shape[1:])  # noqa: E731
    mapped = jax.shard_map(
        per_chip, mesh=mesh, in_specs=(P(), P("dp"), P("dp")),
        out_specs=(P(), P()), check_vma=False)
    ts8, m8 = jax.jit(mapped)(ts, jax.tree.map(shard, batch),
                              shard(weights))

    np.testing.assert_allclose(float(m1["loss"]), float(m8["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(ts1.params),
                    jax.tree.leaves(ts8.params), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-7)


@pytest.mark.slow
def test_apex_trainer_on_virtual_mesh():
    """ApexTrainer(mesh_shape=(8,)): sharded frame-pool replay + aggregated
    chunk ingest + pmean training, end to end with real actor processes."""
    import dataclasses

    from apex_tpu.config import small_test_config
    from apex_tpu.training.apex import ApexTrainer

    cfg = small_test_config(capacity=1024, batch_size=32, n_actors=2)
    cfg = cfg.replace(learner=dataclasses.replace(
        cfg.learner, mesh_shape=(8,), batch_size=32, ingest_chunk=32,
        compute_dtype="float32"))
    t = ApexTrainer(cfg, publish_min_seconds=0.05)
    assert t.n_dp == 8
    t.train(total_steps=25, max_seconds=180)
    assert t.steps_rate.total >= 25
    assert t.ingested >= cfg.replay.warmup
    sizes = np.asarray(t.replay_state.size)
    assert sizes.shape == (8,) and (sizes > 0).all()
    # params stayed replicated across the mesh
    p = jax.tree.leaves(t.train_state.params)[0]
    assert p.sharding.is_fully_replicated
    assert np.isfinite(t.evaluate(episodes=1, max_steps=200))


def test_sharded_is_weights_correct_under_skew(key):
    """The dp-sharded IS weights must be the correct
    bias correction for the sampler actually used — per-shard stratified
    draws — under a heavily skewed, bursty priority distribution, with a
    globally consistent normalizer (PERMethods.is_weights docstring).

    Oracle: true inclusion probability of a drawn transition is
    leaf / (dp * shard_total); weight = (p_eff * N_total)^-beta, normalized
    by the max such weight over ALL shards (the pmax collective).  The
    local-total/local-size formula must reproduce this exactly, and with
    balanced shards it must equal the reference single-buffer formula."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh()
    model = DuelingDQN(num_actions=3, obs_is_image=False,
                       compute_dtype=jnp.float32, scale_uint8=False)
    example = jnp.zeros((1, 6), jnp.float32)
    cap = 512                                     # per shard; 4096 total
    core, ts, _ = build_learner(model, cap, example, key, batch_size=64)
    sl = ShardedLearner(core, mesh)
    example_item = dict(obs=jnp.zeros(6), action=jnp.int32(0),
                        reward=jnp.float32(0), next_obs=jnp.zeros(6),
                        discount=jnp.float32(0))
    rs = sl.init_replay(example_item)
    ingest = sl.make_ingest()

    rng = np.random.default_rng(7)
    n_total = 2048
    prios_all = rng.lognormal(0.0, 2.0, n_total).astype(np.float32)
    prios_all[100:140] *= 1000.0                  # concentrated burst
    for i in range(n_total // 64):
        chunk, prios = sl.split_ingest(_mk_batch(rng, 64),
                                       prios_all[i * 64:(i + 1) * 64])
        rs = ingest(rs, chunk, prios)

    # round-robin ingest spreads the 40-row burst exactly evenly
    burst_shard = np.arange(100, 140) % 8
    np.testing.assert_array_equal(np.bincount(burst_shard, minlength=8),
                                  np.full(8, 5))

    replay = core.replay

    def per_chip(rs_, key_):
        rs_ = jax.tree.map(lambda x: x[0], rs_)
        key_ = jax.random.wrap_key_data(key_[0])
        _, w, idx = replay.sample(rs_, key_, 8, jnp.float32(0.4),
                                  axis_name="dp")
        return w[None], idx[None]

    sample = jax.jit(jax.shard_map(
        per_chip, mesh=mesh, in_specs=(P("dp"), P("dp")),
        out_specs=(P("dp"), P("dp")), check_vma=False))
    w, idx = sample(rs, sl.device_keys(jax.random.key(3)))

    trees = np.asarray(rs.sum_tree)               # (8, 2*cap)
    mins = np.asarray(rs.min_tree)
    shard_total = trees[:, 1]
    shard_min = mins[:, 1]
    n_shard = float(n_total) / 8                  # local size per shard
    # heavy skew is present (shard mass up to ~2x the mean): the exactness
    # below is being tested in the regime that broke the old prose claim
    assert shard_total.max() / shard_total.mean() > 1.5
    # globally consistent normalizer = pmax of per-shard max weights
    max_w = ((shard_min / shard_total * n_shard) ** (-0.4)).max()
    w, idx = np.asarray(w), np.asarray(idx)       # (8, 8) each
    for s in range(8):
        leaves = trees[s, cap + idx[s]]
        expect = (leaves / shard_total[s] * n_shard) ** (-0.4) / max_w
        np.testing.assert_allclose(w[s], expect, rtol=2e-4)
        # the local formula IS the true-sampler correction:
        # leaf/shard_total * n_shard == leaf/(8*shard_total) * n_total
        p_eff = leaves / (8.0 * shard_total[s])
        np.testing.assert_allclose(
            (p_eff * n_total) ** (-0.4) / max_w, expect, rtol=1e-5)

    # balanced-shards reduction: uniform priorities -> identical to the
    # reference single-buffer formula on every shard
    rs_u = sl.init_replay(example_item)
    for i in range(4):
        chunk, prios = sl.split_ingest(_mk_batch(rng, 64),
                                       np.full(64, 2.5, np.float32))
        rs_u = ingest(rs_u, chunk, prios)
    w_u, idx_u = sample(rs_u, sl.device_keys(jax.random.key(4)))
    w_u = np.asarray(w_u)
    # global formula: every leaf equal -> every weight exactly 1
    np.testing.assert_allclose(w_u, 1.0, rtol=1e-5)


@pytest.mark.slow
def test_aql_trainer_on_virtual_mesh():
    """AQLApexTrainer(mesh_shape=(8,)): the AQL family on the SAME sharded
    plan as the DQN flagship — per-chip replay shards with a_mu candidate
    sets, chunk aggregation, NoisyNet update keys split per chip, pmean'd
    two-loss gradients — end to end with real actor processes."""
    import dataclasses

    from apex_tpu.config import small_test_config
    from apex_tpu.training.aql import AQLApexTrainer

    cfg = small_test_config(capacity=1024, batch_size=32, n_actors=2,
                            env_id="ApexContinuousNav-v0")
    cfg = cfg.replace(
        learner=dataclasses.replace(cfg.learner, mesh_shape=(8,),
                                    batch_size=32, ingest_chunk=32,
                                    compute_dtype="float32"),
        aql=dataclasses.replace(cfg.aql, propose_sample=8,
                                uniform_sample=16))
    t = AQLApexTrainer(cfg, publish_min_seconds=0.05, train_ratio=8.0)
    assert t.n_dp == 8
    t.train(total_steps=25, max_seconds=240)
    assert t.steps_rate.total >= 25
    assert t.ingested >= cfg.replay.warmup
    sizes = np.asarray(t.replay_state.size)
    assert sizes.shape == (8,) and (sizes > 0).all()
    p = jax.tree.leaves(t.train_state.params)[0]
    assert p.sharding.is_fully_replicated
    assert np.isfinite(t.evaluate(episodes=1, max_steps=30))


def test_sharded_replay_is_born_sharded(monkeypatch):
    """dp=4 construction never materializes an array whose every shard
    sits on one device: ``replay.init`` runs only INSIDE the sharded jit
    (tracers, no concrete single-shard state first), nothing is
    ``device_put``, and every leaf of the result is laid out over four
    distinct devices right after construction — a full-width shard is
    ~7.5 GB, so a tiled or single-shard copy on chip 0 does not fit."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.config import small_test_config
    from apex_tpu.replay.frame_pool import FramePoolReplay
    from apex_tpu.training.apex import ApexTrainer

    concrete_inits = []
    real_init = FramePoolReplay.init

    def spying_init(self, example_item=None):
        state = real_init(self, example_item)
        if not isinstance(state.frames, jax.core.Tracer):
            concrete_inits.append(state.frames.shape)
        return state

    def no_device_put(*a, **kw):
        raise AssertionError("replay state must not be device_put: it is "
                             "built under its sharding")

    monkeypatch.setattr(FramePoolReplay, "init", spying_init)
    cfg = small_test_config(capacity=512, batch_size=16, n_actors=2)
    cfg = cfg.replace(learner=dataclasses.replace(cfg.learner,
                                                  mesh_shape=(4,)))
    trainer = ApexTrainer(cfg)
    assert concrete_inits == []
    rs = trainer.replay_state

    monkeypatch.setattr(jax, "device_put", no_device_put)
    rs2 = trainer.sharded.init_replay()

    want = NamedSharding(trainer.sharded.mesh, P("dp"))
    devices = set(jax.devices()[:4])
    for state in (rs, rs2):
        for leaf in jax.tree.leaves(state):
            assert leaf.shape[0] == 4
            assert leaf.sharding.is_equivalent_to(want, leaf.ndim)
            shards = leaf.addressable_shards
            assert {s.device for s in shards} == devices
            assert all(s.data.shape[0] == 1 for s in shards)
    np.testing.assert_array_equal(np.asarray(rs.size), np.zeros(4))
