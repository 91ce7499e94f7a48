"""Jittable env ports (envs/jax_envs.py) — exact-trajectory parity.

The pin: stepped under identical seeds and actions, the JAX port of an env
produces the SAME trajectory — rendered uint8 observations, rewards,
terminations — as the numpy env in ``envs/toy.py``.  Catch is all-integer
dynamics, so parity is bitwise by construction; Rally's continuous state
runs in float32 on device, so the numpy reference is constructed with its
``dtype=np.float32`` knob and every op matches the port's correctly-rounded
IEEE-f32 op (the deflection lattice is non-dyadic — f64-vs-f32 trajectories
genuinely diverge at round()-to-pixel boundaries, which is why the knob
exists).

Randomness crosses the seam through :class:`KeyedNpRandom`: the ports draw
``jax.random`` values at fixed fold-in tags, and the shim replays the same
``(key, tag) -> value`` mapping into gymnasium's ``np_random`` surface.
Keyed draws are stateless, so draws one side makes and the other skips
(e.g. Rally's dead serve on the final point) can never desync the streams.
"""

from __future__ import annotations

import numpy as np
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402

from apex_tpu.config import EnvConfig  # noqa: E402
from apex_tpu.envs import jax_envs, toy  # noqa: E402
from apex_tpu.envs.registry import (jittable_env, make_env,  # noqa: E402
                                    make_jax_env)


class KeyedNpRandom:
    """``np_random`` shim replaying the ports' keyed draws.  ``mode``
    selects the reset-scope vs step-scope tag family (the test driver
    flips it around ``env.reset()`` calls, mirroring the ports' in-step
    auto-reset tags)."""

    def __init__(self):
        self.key = None
        self.mode = "step"

    def _tag(self, step_tag: int, reset_tag: int) -> int:
        return reset_tag if self.mode == "reset" else step_tag

    def integers(self, low, high=None, size=None):
        lo, hi = (0, low) if high is None else (low, high)
        t = self._tag(jax_envs._T_INT, jax_envs._T_RESET_INT)
        k = jax.random.fold_in(self.key, t)
        if size is not None:            # one keyed draw of a whole vector
            return np.asarray(jax.random.randint(k, (size,), lo, hi))
        return int(jax.random.randint(k, (), lo, hi))

    def random(self):
        t = self._tag(jax_envs._T_COIN, jax_envs._T_RESET_COIN)
        return float(jax.random.uniform(jax.random.fold_in(self.key, t)))

    def choice(self, arr):
        t = self._tag(jax_envs._T_CHOICE, jax_envs._T_RESET_CHOICE)
        i = int(jax.random.randint(jax.random.fold_in(self.key, t),
                                   (), 0, len(arr)))
        return arr[i]


def assert_trajectory_parity(np_env, jenv, steps: int, seed: int,
                             n_actions: int = 3) -> int:
    """Drive both envs ``steps`` steps under one key chain + action stream;
    assert renders/rewards/dones equal bitwise at every step.  Returns the
    number of episode terminations seen (callers assert coverage)."""
    fake = KeyedNpRandom()
    np_env.reset(seed=0)             # materialize _np_random, then replace
    np_env._np_random = fake
    key = jax.random.key(seed)
    key, kr = jax.random.split(key)
    fake.key, fake.mode = kr, "reset"
    obs_np, _ = np_env.reset()
    st, obs_j = jenv.reset(kr)
    np.testing.assert_array_equal(obs_np, np.asarray(obs_j))
    step = jax.jit(jenv.step)
    rng = np.random.default_rng(seed)
    dones = 0
    for t in range(steps):
        a = int(rng.integers(0, n_actions))
        key, kt = jax.random.split(key)
        fake.key, fake.mode = kt, "step"
        obs_np, r_np, term, trunc, _ = np_env.step(a)
        st, obs_j, r_j, done_j, ff_j = step(st, np.int32(a), kt)
        done_np = bool(term or trunc)
        assert done_np == bool(done_j), f"done mismatch at step {t}"
        assert float(r_np) == float(np.asarray(r_j)), \
            f"reward mismatch at step {t}"
        # final_frame is the terminal render; obs the auto-reset render
        np.testing.assert_array_equal(obs_np, np.asarray(ff_j),
                                      err_msg=f"final frame, step {t}")
        if done_np:
            dones += 1
            fake.mode = "reset"
            obs_np, _ = np_env.reset()
        np.testing.assert_array_equal(obs_np, np.asarray(obs_j),
                                      err_msg=f"obs, step {t}")
    return dones


def test_catch_trajectory_parity_bitwise():
    dones = assert_trajectory_parity(toy.CatchEnv(),
                                     make_jax_env("ApexCatch-v0"),
                                     steps=250, seed=7)
    assert dones >= 1          # the pin covers termination + auto-reset


def test_catch_small_trajectory_parity_bitwise():
    dones = assert_trajectory_parity(
        toy.CatchEnv(grid=7, pixels=42, balls=3),
        make_jax_env("ApexCatchSmall-v0"), steps=200, seed=11)
    assert dones >= 5          # 18-step episodes: many resets covered


def test_rally_trajectory_parity():
    dones = assert_trajectory_parity(
        toy.RallyEnv(dtype=np.float32), make_jax_env("ApexRally-v0"),
        steps=400, seed=3)
    assert dones >= 1


def test_rally_small_trajectory_parity():
    # the Small certificate variant: wide agent paddle, 0.45-speed
    # opponent — exercises the non-integer opp_speed clip path
    assert_trajectory_parity(
        toy.RallyEnv(grid=14, pixels=42, points=2, agent_half=2,
                     opp_speed=0.45, dtype=np.float32),
        make_jax_env("ApexRallySmall-v0"), steps=400, seed=5)


def test_rally_default_dtype_unchanged():
    """The dtype knob's float64 default is bit-identical to the pre-knob
    python-float arithmetic — the calibrated certificate ladders keep
    their trajectories."""
    a, b = toy.RallyEnv(), toy.RallyEnv(dtype=np.float64)
    oa, _ = a.reset(seed=9)
    ob, _ = b.reset(seed=9)
    np.testing.assert_array_equal(oa, ob)
    for t in range(200):
        oa, ra, ta, tra, _ = a.step(t % 3)
        ob, rb, tb, trb, _ = b.step(t % 3)
        np.testing.assert_array_equal(oa, ob)
        assert ra == rb and ta == tb and tra == trb


def test_jittable_flag_and_geometry():
    assert jittable_env("ApexCatch-v0")
    assert jittable_env("ApexRallySmall-v0")
    assert not jittable_env("ApexCartPole-v0")
    assert not jittable_env("SeaquestNoFrameskip-v4")
    for env_id in ("ApexCatchSmall-v0", "ApexCatchMedium-v0",
                   "ApexRally-v0", "ApexRallySmall-v0"):
        jenv = make_jax_env(env_id)
        ref = make_env(env_id, EnvConfig(frame_stack=1), stack_frames=False)
        assert jenv.frame_shape == tuple(ref.observation_space.shape)
        assert jenv.num_actions == int(ref.action_space.n)
        ref.close()


def test_make_jax_env_rejects_non_jittable():
    with pytest.raises(ValueError, match="ApexCartPole-v0"):
        make_jax_env("ApexCartPole-v0")
    with pytest.raises(ValueError, match="ondevice"):
        make_jax_env("ApexContinuousNav-v0")


def test_scanned_batch_rollout_smoke():
    """The ports' raison d'être: vmapped env batches stepped under
    lax.scan in one jitted program, auto-reset keeping every lane live."""
    import jax.numpy as jnp

    env = make_jax_env("ApexCatchSmall-v0")
    B, T = 4, 40
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        jax.random.key(0), np.arange(B, dtype=np.uint32))
    states, obs = jax.vmap(env.reset)(keys)

    def body(carry, key):
        st, _ = carry
        acts = jax.random.randint(key, (B,), 0, env.num_actions)
        # apexlint: disable=J004 -- action draw vs per-slot env keys: randint(key) and fold_in(key, slot) are disjoint streams
        ks = jax.vmap(jax.random.fold_in, (None, 0))(
            key, jnp.arange(B, dtype=jnp.uint32))
        st, ob, r, d, _ff = jax.vmap(env.step)(st, acts, ks)
        return (st, ob), (r, d)

    @jax.jit
    def run(states, obs, key):
        return jax.lax.scan(body, (states, obs),
                            jax.random.split(key, T))

    (states, obs), (rewards, dones) = run(states, obs, jax.random.key(1))
    assert rewards.shape == (T, B) and dones.shape == (T, B)
    assert int(dones.sum()) >= B          # 18-step episodes: all lanes reset
    assert obs.shape == (B, 42, 42, 1) and obs.dtype == jnp.uint8


def test_tokens_trajectory_parity_bitwise():
    """``ApexTokens-v0``: integers only, so numpy env and port agree bit
    for bit over three whole episodes on keyed draws (prompt, reward of the
    arithmetic next id, context shift, truncation at 64 steps)."""
    cfg = EnvConfig(env_id="ApexTokens-v0", token_context=12,
                    token_vocab=300)
    np_env = make_env("ApexTokens-v0", cfg, stack_frames=False)
    jenv = make_jax_env("ApexTokens-v0", cfg)
    assert jittable_env("ApexTokens-v0")
    assert jenv.frame_shape == (24,) and jenv.num_actions == 300
    assert np_env.observation_space.shape == (24,)
    dones = assert_trajectory_parity(np_env, jenv, steps=3 * 64 + 5, seed=9,
                                     n_actions=300)
    assert dones == 3


def test_tokens_reward_is_the_arithmetic_next_id():
    env = toy.TokensEnv(context=4, vocab=50)
    obs, _ = env.reset(seed=3)
    ids = obs.reshape(-1, 2).astype(int) @ np.array([1, 256])
    want = (31 * ids[-1] + ids[-2] + 7) % 50
    obs, r, term, trunc, _ = env.step(int(want))
    assert (r, term, trunc) == (1.0, False, False)
    after = obs.reshape(-1, 2).astype(int) @ np.array([1, 256])
    np.testing.assert_array_equal(after, list(ids[1:]) + [want])
    _, r, _, _, _ = env.step(int((want + 1) % 50))
    wrong_want = (31 * after[-1] + after[-2] + 7) % 50
    assert r == (1.0 if (want + 1) % 50 == wrong_want else 0.0)
