"""AQL: model shapes/semantics, loss oracles, two-optimizer isolation,
transition builder oracle, and end-to-end learning on the continuous env."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.config import small_test_config
from apex_tpu.models.aql import AQLNetwork, make_aql_policy_fn
from apex_tpu.ops.losses import (aql_param_labels, aql_proposal_loss,
                                 aql_q_loss, make_aql_optimizer)
from apex_tpu.training.aql import AQLTrainer, AQLTransitionBuilder

A, T_P, T_U = 2, 8, 16
T = T_P + T_U


def _model(**kw):
    return AQLNetwork(action_dim=A, propose_sample=T_P, uniform_sample=T_U,
                      **kw)


def _params(m, obs_dim=3, batch=4):
    obs = jnp.zeros((batch, obs_dim), jnp.float32)
    a_mu = jnp.zeros((batch, T, A), jnp.float32)
    return m.init({"params": jax.random.key(0), "noise": jax.random.key(1),
                   "sample": jax.random.key(2)}, obs, a_mu,
                  method=AQLNetwork.full_init)


def test_propose_shapes_and_bounds(key):
    m = _model(action_low=-1.5, action_high=0.5)
    params = _params(m)
    obs = jax.random.normal(key, (4, 3))
    a_mu = m.apply(params, obs, method=AQLNetwork.propose,
                   rngs={"sample": jax.random.key(3)})
    assert a_mu.shape == (4, T, A)
    # uniform candidates (first T_U rows) respect the box exactly
    uni = a_mu[:, :T_U]
    assert float(uni.min()) >= -1.5 and float(uni.max()) <= 0.5
    # proposal candidates concentrate around the learned mean
    mu = m.apply(params, obs, method=AQLNetwork.proposal_mean)
    prop = a_mu[:, T_U:]
    spread = np.abs(np.asarray(prop) - np.asarray(mu)[:, None, :]).mean()
    assert spread < 4 * np.sqrt(m.action_var)


def test_policy_epsilon_extremes(key):
    m = _model()
    params = _params(m)
    policy = jax.jit(make_aql_policy_fn(m))
    obs = jax.random.normal(key, (64, 3))
    # eps=0: the returned action IS the argmax candidate
    act, idx, a_mu, q = policy(params, obs, jnp.float32(0.0),
                               jax.random.key(5))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(q.argmax(1)))
    chosen = np.take_along_axis(np.asarray(a_mu),
                                np.asarray(idx)[:, None, None], axis=1)[:, 0]
    np.testing.assert_array_equal(np.asarray(act), chosen)
    # eps=1: indices spread across the whole candidate set
    _, idx1, _, _ = policy(params, obs, jnp.float32(1.0), jax.random.key(6))
    assert len(np.unique(np.asarray(idx1))) > T // 4


def test_q_loss_matches_numpy_oracle(key):
    """Deterministic heads -> the TD math is checkable by hand."""
    m = _model(noisy_deterministic=True)
    params = _params(m)
    rng = np.random.default_rng(0)
    b = 8
    batch = dict(
        obs=rng.normal(size=(b, 3)).astype(np.float32),
        action=rng.integers(0, T, b).astype(np.int32),
        reward=rng.normal(size=b).astype(np.float32),
        next_obs=rng.normal(size=(b, 3)).astype(np.float32),
        discount=np.full(b, 0.99, np.float32),
        a_mu=rng.normal(size=(b, T, A)).astype(np.float32))
    weights = rng.uniform(0.5, 1.0, b).astype(np.float32)

    def score(p, obs, a_mu, noise_key):
        return m.apply(p, obs, a_mu, rngs={"noise": noise_key})

    k = jax.random.key(7)
    # apexlint: disable=J004 -- online==target here; the TD reconstruction below needs IDENTICAL noise draws
    loss, aux = aql_q_loss(score, params, params, batch, weights, k, k)

    # apexlint: disable=J004 -- same-draw reconstruction (see above)
    q = np.asarray(score(params, batch["obs"], batch["a_mu"], k))
    # apexlint: disable=J004 -- same-draw reconstruction (see above)
    qn = np.asarray(score(params, batch["next_obs"], batch["a_mu"], k))
    q_taken = q[np.arange(b), batch["action"]]
    # online==target params here, so double-DQN reduces to max
    target = batch["reward"] + batch["discount"] * qn.max(1)
    td = np.abs(target - q_taken)
    np.testing.assert_allclose(np.asarray(aux.td_abs), td, rtol=1e-5)
    huber = np.where(td < 1, 0.5 * td ** 2, td - 0.5)
    np.testing.assert_allclose(float(loss), (huber * weights).mean(),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(aux.priorities),
                               0.9 * td.max() + 0.1 * td + 1e-6, rtol=1e-5)


def test_proposal_loss_matches_gaussian_nll_oracle():
    m = _model(noisy_deterministic=True)
    params = _params(m)
    rng = np.random.default_rng(1)
    b = 8
    batch = dict(obs=rng.normal(size=(b, 3)).astype(np.float32),
                 a_mu=rng.normal(size=(b, T, A)).astype(np.float32))
    best_idx = jnp.asarray(rng.integers(0, T, b).astype(np.int32))

    def log_prob(p, obs, actions):
        return m.apply(p, obs, actions,
                       method=AQLNetwork.proposal_log_prob)

    ent_coef = 0.01
    loss = aql_proposal_loss(log_prob, params, batch, best_idx, ent_coef)

    mu = np.asarray(m.apply(params, batch["obs"],
                            method=AQLNetwork.proposal_mean))
    best = batch["a_mu"][np.arange(b), np.asarray(best_idx)]
    var = m.action_var
    lp = (-0.5 * ((best - mu) ** 2).sum(-1) / var
          - 0.5 * A * np.log(2 * np.pi * var))
    ent = 0.5 * A * (1 + np.log(2 * np.pi * var))
    np.testing.assert_allclose(float(loss), (-lp - ent_coef * ent).mean(),
                               rtol=1e-5)


@pytest.mark.slow
def test_two_optimizer_isolation():
    """The proposal loss moves ONLY proposal params; the Q loss moves only
    the rest (reference interleaved zero_grad/step, AQL_dis.py:87-101)."""
    cfg = small_test_config(capacity=256, batch_size=16,
                            env_id="ApexContinuousNav-v0")
    cfg = cfg.replace(aql=dataclasses.replace(cfg.aql, propose_sample=T_P,
                                              uniform_sample=T_U))
    t = AQLTrainer(cfg)
    rng = np.random.default_rng(2)
    b = 16
    obs_dim = t.env.observation_space.shape[0]
    batch = dict(
        obs=rng.normal(size=(b, obs_dim)).astype(np.float32),
        action=rng.integers(0, T, b).astype(np.int32),
        reward=rng.normal(size=b).astype(np.float32),
        next_obs=rng.normal(size=(b, obs_dim)).astype(np.float32),
        discount=np.full(b, 0.99, np.float32),
        a_mu=rng.normal(size=(b, T, A)).astype(np.float32))
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    ts2, prios, metrics = t.core.update_from_batch(
        t.train_state, batch, jnp.ones(b), jax.random.key(3))
    labels = aql_param_labels(t.train_state.params)
    changed = jax.tree.map(
        lambda a, b_: bool(np.any(np.asarray(a) != np.asarray(b_))),
        t.train_state.params, ts2.params)
    for lbl, ch in zip(jax.tree.leaves(labels), jax.tree.leaves(changed),
                       strict=True):
        assert ch, f"some {lbl} leaf did not update"
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["loss_proposal"]))
    assert prios.shape == (b,)


def test_transition_builder_oracle():
    gamma = 0.9
    b = AQLTransitionBuilder(gamma)
    q0 = np.array([1.0, 5.0, 3.0])     # taken idx 1 -> q_taken 5
    q1 = np.array([2.0, 0.0, 7.0])     # max 7 bootstraps transition 0
    q2 = np.array([4.0, 1.0, 0.0])
    a_mu = np.zeros((3, 1), np.float32)
    b.add_step([0.0], 1, 1.0, [1.0], a_mu, q0, False, False)
    assert len(b) == 0                 # emission delayed one step
    b.add_step([1.0], 2, -1.0, [2.0], a_mu, q1, False, False)
    assert len(b) == 1
    b.add_step([2.0], 0, 2.0, [3.0], a_mu, q2, True, False)
    assert len(b) == 3                 # pending + terminal both flushed
    batch, prios = b.drain(3)
    np.testing.assert_allclose(batch["reward"], [1.0, -1.0, 2.0])
    np.testing.assert_allclose(batch["discount"], [gamma, gamma, 0.0])
    np.testing.assert_array_equal(batch["action"], [1, 2, 0])
    np.testing.assert_allclose(
        prios,
        [abs(1.0 + gamma * 7.0 - 5.0) + 1e-6,      # boot from q1.max
         abs(-1.0 + gamma * 4.0 - 7.0) + 1e-6,     # boot from q2.max
         abs(2.0 + 0.0 - 4.0) + 1e-6],             # terminal: no bootstrap
        rtol=1e-6)

    # truncation: learner bootstraps (discount=gamma); the priority uses the
    # current state's max-Q as proxy for the never-scored final state
    b.add_step([0.0], 0, 0.5, [1.0], a_mu, q0, False, True)
    batch, prios = b.drain(1)
    np.testing.assert_allclose(batch["discount"], [gamma])
    np.testing.assert_allclose(prios, [abs(0.5 + gamma * 5.0 - 1.0) + 1e-6],
                               rtol=1e-6)


@pytest.mark.slow
def test_aql_apex_pipeline_mechanics():
    """Distributed AQL (C9+C12): worker processes act through the
    proposal+Q policy and ship a_mu-carrying chunks; the learner ingests
    and trains concurrently, publishes versioned params, shuts down clean."""
    from apex_tpu.training.aql import AQLApexTrainer

    cfg = small_test_config(capacity=2048, batch_size=32, n_actors=2,
                            env_id="ApexContinuousNav-v0")
    cfg = cfg.replace(aql=dataclasses.replace(cfg.aql, propose_sample=8,
                                              uniform_sample=16))
    t = AQLApexTrainer(cfg, publish_min_seconds=0.05, train_ratio=8.0)
    t.train(total_steps=30, max_seconds=120)
    assert t.steps_rate.total >= 30
    assert t.ingested >= cfg.replay.warmup
    assert t.param_version >= 2
    assert t.log.history.get("learner/episode_reward")
    assert all(not p.is_alive() for p in t.pool.procs)
    assert np.isfinite(t.evaluate(episodes=1, max_steps=50))


def test_aql_fused_multi_step_matches_sequential(key):
    """scan-of-K parity for the AQL core: the two-loss update with its
    NoisyNet key splits must be bit-identical inside lax.scan."""
    from apex_tpu.envs.registry import make_env
    from apex_tpu.training.aql import aql_model_spec, build_aql

    cfg = small_test_config(capacity=256, batch_size=16,
                            env_id="ApexContinuousNav-v0")
    cfg = cfg.replace(aql=dataclasses.replace(cfg.aql, propose_sample=4,
                                              uniform_sample=4))
    env = make_env(cfg.env.env_id, cfg.env, seed=0)
    obs_shape = env.observation_space.shape
    spec = aql_model_spec(cfg, env)
    env.close()
    model, ts, replay, example_item, core = build_aql(
        cfg, spec, obs_shape, np.float32, key)
    rs = replay.init(example_item)
    t = model.total_sample
    a_dim = spec["action_dim"]
    k_steps = 3
    rng = np.random.default_rng(2)

    def chunk(i):
        r = np.random.default_rng(50 + i)
        n = 16
        return dict(
            obs=r.normal(size=(n,) + obs_shape).astype(np.float32),
            action=r.integers(0, t, n).astype(np.int32),
            reward=r.normal(size=n).astype(np.float32),
            next_obs=r.normal(size=(n,) + obs_shape).astype(np.float32),
            discount=np.full(n, 0.99, np.float32),
            a_mu=r.normal(size=(n, t, a_dim)).astype(np.float32))

    chunks = [chunk(i) for i in range(k_steps)]
    prios = [np.abs(rng.normal(size=16)).astype(np.float32) + 0.1
             for _ in range(k_steps)]
    keys = jax.random.split(jax.random.key(4), k_steps)
    # warm the buffer so sampling has mass before the first scanned step
    rs = core.jit_ingest()(rs, chunks[0], jnp.asarray(prios[0]))
    ts_b = jax.tree.map(jnp.copy, ts)
    rs_b = jax.tree.map(jnp.copy, rs)

    fused = core.jit_fused_step()
    for i in range(k_steps):
        ts, rs, m_a = fused(ts, rs, chunks[i], jnp.asarray(prios[i]),
                            keys[i], jnp.float32(0.4))
    multi = core.jit_fused_multi_step()
    stacked = {kk: jnp.stack([jnp.asarray(c[kk]) for c in chunks])
               for kk in chunks[0]}
    ts_m, rs_m, m_m = multi(ts_b, rs_b, stacked,
                            jnp.stack([jnp.asarray(p) for p in prios]),
                            keys, jnp.float32(0.4))
    assert int(ts_m.step) == k_steps
    assert m_m["loss"].shape == (k_steps,)
    for a, b in zip(jax.tree.leaves(ts.params), jax.tree.leaves(ts_m.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(rs.sum_tree),
                                  np.asarray(rs_m.sum_tree))
    np.testing.assert_allclose(float(m_a["loss"]),
                               float(np.asarray(m_m["loss"])[-1]))


@pytest.mark.slow
def test_aql_apex_scan_dispatch_mechanics():
    """config.scan_steps wires the AQL core's fused_multi_step into the
    concurrent loop exactly like the DQN family (two-loss update +
    NoisyNet keys inside lax.scan)."""
    from apex_tpu.training.aql import AQLApexTrainer

    cfg = small_test_config(capacity=2048, batch_size=32, n_actors=2,
                            env_id="ApexContinuousNav-v0")
    cfg = cfg.replace(
        aql=dataclasses.replace(cfg.aql, propose_sample=8,
                                uniform_sample=16),
        learner=dataclasses.replace(cfg.learner, scan_steps=2))
    t = AQLApexTrainer(cfg, publish_min_seconds=0.05)
    assert t._multi is not None
    t.train(total_steps=30, max_seconds=120)
    assert t.steps_rate.total >= 30
    assert t.scan_dispatches > 0, "scan path never fired"
    assert all(not p.is_alive() for p in t.pool.procs)


@pytest.mark.slow
def test_aql_apex_vector_actors():
    """Vectorized AQL actors: 1 process x 4 env slots act through ONE
    batched propose+score call; slots carry global ladder ids; the
    concurrent learner trains and shuts down clean."""
    from apex_tpu.training.aql import AQLApexTrainer

    cfg = small_test_config(capacity=2048, batch_size=32, n_actors=1,
                            env_id="ApexContinuousNav-v0")
    cfg = cfg.replace(
        aql=dataclasses.replace(cfg.aql, propose_sample=8,
                                uniform_sample=16),
        actor=dataclasses.replace(cfg.actor, n_envs_per_actor=4))
    t = AQLApexTrainer(cfg, publish_min_seconds=0.05, train_ratio=8.0)
    t.train(total_steps=30, max_seconds=180)
    assert t.steps_rate.total >= 30
    assert t.ingested >= cfg.replay.warmup
    slots = {int(v) for _, v in t.log.history.get("learner/actor_id", [])}
    assert slots and max(slots) > 0, f"vector slots missing: {slots}"
    assert all(not p.is_alive() for p in t.pool.procs)


@pytest.mark.slow
def test_aql_learns_continuous_nav():
    """AQL must beat random play on ContinuousNav: random returns ~-40,
    competent proposals reach > -20 within a small CI budget."""
    cfg = small_test_config(capacity=8192, batch_size=64,
                            env_id="ApexContinuousNav-v0")
    cfg = cfg.replace(aql=dataclasses.replace(
        cfg.aql, propose_sample=16, uniform_sample=32,
        q_lr=1e-3, proposal_lr=1e-3))
    t = AQLTrainer(cfg)
    t.epsilon.decay = 1500.0
    before = t.evaluate(episodes=5, max_steps=50)
    t.train(total_frames=6000)
    after = t.evaluate(episodes=5, max_steps=50)
    assert after > -20.0, f"eval {before} -> {after}: AQL not learning"
    assert after > before + 5.0, f"no improvement: {before} -> {after}"


# -- discrete-action AQL (reference model.py:370-376) ----------------------

def _discrete_model(n=5, propose=12, uniform=4):
    return AQLNetwork(action_dim=n, discrete=True, propose_sample=propose,
                      uniform_sample=uniform, compute_dtype=jnp.float32)


def test_discrete_propose_shapes_and_uniform_distinct(key):
    m = _discrete_model()
    t = m.total_sample
    obs = jax.random.normal(key, (6, 3))
    params = m.init({"params": jax.random.key(0),
                     "noise": jax.random.key(1),
                     "sample": jax.random.key(2)},
                    obs, jnp.zeros((6, t, 1)), method=AQLNetwork.full_init)
    a_mu = m.apply(params, obs, method=AQLNetwork.propose,
                   rngs={"sample": jax.random.key(3)})
    assert a_mu.shape == (6, t, 1)
    vals = np.asarray(a_mu)[..., 0]
    # all candidates are valid integer action indices
    np.testing.assert_array_equal(vals, np.round(vals))
    assert vals.min() >= 0 and vals.max() < m.action_dim
    # the uniform half is distinct WITHIN each row (model.py:371-373
    # replace=False semantics), per-row independent
    uni = vals[:, :m.uniform_sample]
    for row in uni:
        assert len(np.unique(row)) == m.uniform_sample


def test_discrete_log_prob_matches_softmax_oracle(key):
    m = _discrete_model()
    t = m.total_sample
    obs = jax.random.normal(key, (8, 3))
    params = m.init({"params": jax.random.key(0),
                     "noise": jax.random.key(1),
                     "sample": jax.random.key(2)},
                    obs, jnp.zeros((8, t, 1)), method=AQLNetwork.full_init)
    logits = np.asarray(m.apply(params, obs,
                                method=AQLNetwork.proposal_mean))
    actions = jnp.asarray(
        np.random.default_rng(0).integers(0, m.action_dim, 8)
    ).astype(jnp.float32)[:, None]
    lp, ent = m.apply(params, obs, actions,
                      method=AQLNetwork.proposal_log_prob)
    # numpy oracle: log softmax at the action index; categorical entropy
    z = logits - logits.max(axis=1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(axis=1, keepdims=True))
    idx = np.asarray(actions[:, 0], np.int32)
    np.testing.assert_allclose(np.asarray(lp),
                               logp[np.arange(8), idx], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ent),
                               -(np.exp(logp) * logp).sum(axis=1), rtol=1e-5)


def test_discrete_policy_returns_int_actions(key):
    m = _discrete_model()
    t = m.total_sample
    obs = jax.random.normal(key, (4, 3))
    params = m.init({"params": jax.random.key(0),
                     "noise": jax.random.key(1),
                     "sample": jax.random.key(2)},
                    obs, jnp.zeros((4, t, 1)), method=AQLNetwork.full_init)
    policy = jax.jit(make_aql_policy_fn(m))
    act, idx, a_mu, q = policy(params, obs, jnp.float32(0.0),
                               jax.random.key(5))
    assert act.dtype == jnp.int32 and act.shape == (4,)
    assert int(act.min()) >= 0 and int(act.max()) < m.action_dim
    # the returned action IS the argmax candidate's index value
    chosen = np.take_along_axis(np.asarray(a_mu),
                                np.asarray(q.argmax(1))[:, None, None],
                                axis=1)[:, 0, 0]
    np.testing.assert_array_equal(np.asarray(act), chosen.astype(np.int32))


@pytest.mark.slow
def test_discrete_aql_trainer_mechanics():
    """The full single-process AQL pipeline on a Discrete env (CartPole):
    spec routing, candidate storage, fused two-loss step, eval — the
    capability the r3 framework refused."""
    cfg = small_test_config(capacity=1024, batch_size=16,
                            env_id="ApexCartPole-v0")
    cfg = cfg.replace(aql=dataclasses.replace(
        cfg.aql, propose_sample=12, uniform_sample=8))
    t = AQLTrainer(cfg)
    assert t.model.discrete and t.model.action_dim == 2
    assert t.model.uniform_sample == 2          # clamped to n (model.py:180)
    t.train(total_frames=400, log_every=25)
    assert t.steps_rate.total > 0
    hist = t.log.history
    losses = [v for k, series in hist.items() if "loss" in k
              for _, v in series]
    assert losses and np.isfinite(losses).all()
    assert np.isfinite(t.evaluate(episodes=2, max_steps=50))


@pytest.mark.slow
def test_aql_pixel_frame_pool_pipeline():
    """Pixel AQL end to end: 84x84x4 uint8 Catch
    through the FRAME-POOL replay with a_mu sidecars — actor workers use
    the chunk-builder family, the learner's fused step gathers stacks on
    device and re-scores the shipped candidate sets.  Also exercises the
    Categorical (discrete) proposal on pixels."""
    import dataclasses as dc

    from apex_tpu.replay.frame_pool import FramePoolReplay
    from apex_tpu.training.aql import AQLApexTrainer

    cfg = small_test_config(capacity=2048, batch_size=16, n_actors=1,
                            env_id="ApexCatch-v0")
    cfg = cfg.replace(
        env=dc.replace(cfg.env, frame_stack=4),
        replay=dc.replace(cfg.replay, warmup=128),
        aql=dc.replace(cfg.aql, propose_sample=8, uniform_sample=16))
    t = AQLApexTrainer(cfg, publish_min_seconds=0.05, train_ratio=8.0)
    # the replay really is the frame-pool layout with the sidecar declared
    assert isinstance(t.replay, FramePoolReplay)
    assert t.replay.frame_shape == (84, 84, 1)
    assert t.replay.frame_stack == 4
    assert dict(t.replay.extra_spec)["a_mu"] == (t.model.total_sample, 1)
    assert t.model.discrete and t.model.uniform_sample == 3  # clamped to n
    t.train(total_steps=10, max_seconds=240)
    assert t.steps_rate.total >= 10
    assert t.ingested >= cfg.replay.warmup
    # candidate sidecars are resident (some row was written)
    assert float(np.abs(np.asarray(t.replay_state.extras["a_mu"])).max()) > 0
    assert all(not p.is_alive() for p in t.pool.procs)
    assert np.isfinite(t.evaluate(episodes=1, max_steps=60))


@pytest.mark.slow
def test_aql_pixel_vector_actors():
    """VectorAQLPixelWorkerFamily: one process x 3 env slots of 84x84
    Catch act through ONE batched propose+score call, per-slot chunk
    builders shipping a_mu sidecars into the frame-pool learner."""
    import dataclasses as dc

    from apex_tpu.replay.frame_pool import FramePoolReplay
    from apex_tpu.training.aql import AQLApexTrainer

    cfg = small_test_config(capacity=2048, batch_size=16, n_actors=1,
                            env_id="ApexCatch-v0")
    cfg = cfg.replace(
        env=dc.replace(cfg.env, frame_stack=2),
        replay=dc.replace(cfg.replay, warmup=128),
        actor=dc.replace(cfg.actor, n_envs_per_actor=3),
        aql=dc.replace(cfg.aql, propose_sample=8, uniform_sample=16))
    t = AQLApexTrainer(cfg, publish_min_seconds=0.05, train_ratio=8.0)
    assert isinstance(t.replay, FramePoolReplay)
    t.train(total_steps=8, max_seconds=240)
    assert t.steps_rate.total >= 8
    # stats carry global slot ids from the vector lanes
    slots = {int(v) for _, v in t.log.history.get("learner/actor_id", [])}
    assert slots and max(slots) >= 1, f"vector slots missing: {slots}"
    assert all(not p.is_alive() for p in t.pool.procs)
