"""Jittable functional ports of the toy pixel envs (Anakin substrate).

``envs/toy.py`` holds the numpy gymnasium envs the host actor fleet steps
one python call at a time.  Catch and Rally are integer/float32 grid worlds
with no emulator dependency, so they can run INSIDE the accelerator: this
module re-expresses them as pure functions

    reset(key)               -> (state, obs)
    step(state, action, key) -> (state, obs, reward, done, final_frame)

over small array states, vmappable across env batches and scannable with
``lax.scan`` (the co-located batched-simulation economics of Accelerated
Methods, arxiv 1803.02811, and the Anakin/commodity-hardware setups of
arxiv 2111.01264).  ``apex_tpu/training/anakin.py`` fuses them with the
epsilon-greedy policy and on-device chunk assembly into one scanned
rollout program.

Parity contract (pinned in tests/test_jax_envs.py): stepped under the SAME
seeds and actions, a port's trajectory — rendered uint8 observations,
rewards, terminations — is IDENTICAL to the numpy env's.  Randomness is
the one seam: the numpy envs draw from gymnasium's PCG64 stream while the
ports draw with ``jax.random`` — so every draw site here has a FIXED
fold-in tag (the ``_T_*`` constants), and the parity tests drive the numpy
env through a keyed ``np_random`` shim that replays the same
``fold_in(key, tag)`` draws.  Because keyed draws are stateless, unused
draws cost nothing and can never desync the two sides.

Auto-reset lives INSIDE ``step`` (a scanned rollout cannot stop to call
``reset``): on ``done`` the returned ``obs`` is the NEXT episode's reset
observation while ``final_frame`` is the terminal render — exactly the two
frames the host loop hands ``FrameChunkBuilder.add_step`` /
``begin_episode``.  On ordinary steps ``final_frame is obs``.

Catch dynamics are pure integers => bitwise parity over full trajectories.
Rally computes in float32 where the numpy env uses float64; every op is
the same correctly-rounded IEEE elementary op, and the parity test pins a
fixed-seed trajectory exactly (the dynamics lattice keeps f32 and f64
agreeing on every discrete observable over the pinned horizon).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

# -- draw-site tags ----------------------------------------------------------
# Step-scope draws fold these onto the per-step env key; reset-scope draws
# (initial reset AND in-step auto-reset) use the _T_RESET_* tags, so a
# terminal step's dead serve draws and its auto-reset draws can never
# collide.  tests/test_jax_envs.py's KeyedNpRandom shim replays the same
# (key, tag) -> value mapping into the numpy envs.
_T_COIN = 0          # in-step coin (Rally deflect sign)
_T_INT = 1           # in-step integer draw (Catch drop column, Rally serve row)
_T_CHOICE = 2        # in-step choice draw (Rally serve vy)
_T_RESET_COIN = 3    # reset-scope coin (Rally serve direction)
_T_RESET_INT = 4     # reset-scope integer draw
_T_RESET_CHOICE = 5  # reset-scope choice draw


def _coin(key, tag: int):
    """random() < 0.5, keyed."""
    return jax.random.uniform(jax.random.fold_in(key, tag)) < 0.5


def _randint(key, tag: int, low: int, high: int):
    return jax.random.randint(jax.random.fold_in(key, tag), (), low, high)


class JaxEnv(NamedTuple):
    """One jittable env: pure reset/step plus the spec the chunk plane
    needs.  ``step`` returns ``(state, obs, reward, done, final_frame)``
    with auto-reset folded in (module docstring)."""

    reset: Callable[..., Any]
    step: Callable[..., Any]
    frame_shape: tuple[int, ...]
    num_actions: int
    env_id: str


# -- Catch -------------------------------------------------------------------


class CatchState(NamedTuple):
    paddle: jax.Array       # i32
    ball_x: jax.Array       # i32
    ball_y: jax.Array       # i32
    remaining: jax.Array    # i32


@dataclass(frozen=True)
class CatchParams:
    """Twin of :class:`apex_tpu.envs.toy.CatchEnv`'s constructor knobs."""

    grid: int = 21
    pixels: int = 84
    balls: int = 5

    @property
    def scale(self) -> int:
        return self.pixels // self.grid


def _catch_render(p: CatchParams, state: CatchState) -> jax.Array:
    """Bitwise port of ``CatchEnv._render``: ball block at (ball_y,
    ball_x), 3-cell paddle row at the bottom drawn AFTER the ball (the
    paddle overwrites where they overlap)."""
    s = p.scale
    rows = jnp.arange(p.pixels, dtype=jnp.int32)[:, None]
    cols = jnp.arange(p.pixels, dtype=jnp.int32)[None, :]
    by, bx = state.ball_y * s, state.ball_x * s
    ball = ((rows >= by) & (rows < by + s)
            & (cols >= bx) & (cols < bx + s))
    py = (p.grid - 1) * s
    p0 = jnp.maximum(state.paddle - 1, 0) * s
    p1 = (jnp.minimum(state.paddle + 1, p.grid - 1) + 1) * s
    pad = (rows >= py) & (rows < py + s) & (cols >= p0) & (cols < p1)
    img = jnp.where(ball, jnp.uint8(255), jnp.uint8(0))
    img = jnp.where(pad, jnp.uint8(128), img)
    return img[:, :, None]


def make_catch(grid: int = 21, pixels: int = 84, balls: int = 5,
               env_id: str = "ApexCatch-v0") -> JaxEnv:
    p = CatchParams(grid=grid, pixels=pixels, balls=balls)

    def reset(key) -> tuple[CatchState, jax.Array]:
        state = CatchState(
            paddle=jnp.int32(p.grid // 2),
            ball_x=_randint(key, _T_RESET_INT, 0, p.grid),
            ball_y=jnp.int32(0),
            remaining=jnp.int32(p.balls))
        return state, _catch_render(p, state)

    def step(state: CatchState, action, key):
        move = jnp.asarray([0, -1, 1], jnp.int32)[action]
        paddle = jnp.clip(state.paddle + move, 0, p.grid - 1)
        ball_y = state.ball_y + 1
        landed = ball_y == p.grid - 1
        caught = jnp.abs(state.ball_x - paddle) <= 1
        reward = jnp.where(
            landed, jnp.where(caught, jnp.float32(1.0), jnp.float32(-1.0)),
            jnp.float32(0.0))
        remaining = state.remaining - landed.astype(jnp.int32)
        done = landed & (remaining == 0)
        # drop within the episode (landed, balls left): new column from the
        # in-step tag — the terminal render keeps the OLD ball position
        drop = landed & ~done
        mid = CatchState(
            paddle=paddle,
            ball_x=jnp.where(drop, _randint(key, _T_INT, 0, p.grid),
                             state.ball_x),
            ball_y=jnp.where(drop, jnp.int32(0), ball_y),
            remaining=remaining)
        final_frame = _catch_render(p, mid)
        # auto-reset (reset-scope tags, same key — mirrors the host driver
        # calling env.reset() right after the terminal step)
        fresh = CatchState(
            paddle=jnp.int32(p.grid // 2),
            # apexlint: disable=J004 -- every draw site folds a DISTINCT _T_* tag onto the step key (module docstring): tagged fold_in IS the fresh-subkey discipline here
            ball_x=_randint(key, _T_RESET_INT, 0, p.grid),
            ball_y=jnp.int32(0),
            remaining=jnp.int32(p.balls))
        out = jax.tree.map(lambda a, b: jnp.where(done, a, b), fresh, mid)
        obs = jnp.where(done, _catch_render(p, fresh), final_frame)
        return out, obs, reward, done, final_frame

    return JaxEnv(reset=reset, step=step,
                  frame_shape=(pixels, pixels, 1), num_actions=3,
                  env_id=env_id)


# -- Rally -------------------------------------------------------------------


class RallyState(NamedTuple):
    agent_y: jax.Array      # f32
    opp_y: jax.Array        # f32
    bx: jax.Array           # f32 (half-integer courts exist: grid=14)
    by: jax.Array           # f32
    vx: jax.Array           # i32 (+1 toward agent)
    vy: jax.Array           # f32
    played: jax.Array       # i32


@dataclass(frozen=True)
class RallyParams:
    grid: int = 21
    pixels: int = 84
    points: int = 3
    paddle_half: int = 1
    agent_half: int | None = None
    opp_speed: float = 1.0

    # derived, matching toy.RallyEnv.__init__
    a_half: int = field(init=False)

    def __post_init__(self) -> None:
        object.__setattr__(self, "a_half",
                           self.paddle_half if self.agent_half is None
                           else self.agent_half)

    @property
    def scale(self) -> int:
        return self.pixels // self.grid


_MAX_VY = 1.75
_MIN_VY = 0.5


def _rally_serve(p: RallyParams, key, toward_agent, reset_scope: bool):
    """(bx, by, vx, vy) of a fresh serve — ``toy.RallyEnv._serve``."""
    t_int = _T_RESET_INT if reset_scope else _T_INT
    t_choice = _T_RESET_CHOICE if reset_scope else _T_CHOICE
    # apexlint: disable=J004 -- distinct fold-in tags per draw site (module docstring), not key reuse
    by = _randint(key, t_int, 2, p.grid - 2).astype(jnp.float32)
    vy = jnp.asarray([-1.0, -0.5, 0.5, 1.0], jnp.float32)[
        # apexlint: disable=J004 -- distinct fold-in tags per draw site (module docstring), not key reuse
        _randint(key, t_choice, 0, 4)]
    return (jnp.float32((p.grid - 1) / 2), by,
            jnp.where(toward_agent, jnp.int32(1), jnp.int32(-1)), vy)


def _rally_reset_state(p: RallyParams, key) -> RallyState:
    mid = jnp.float32((p.grid - 1) / 2)
    # apexlint: disable=J004 -- distinct fold-in tags per draw site (module docstring), not key reuse
    bx, by, vx, vy = _rally_serve(p, key, _coin(key, _T_RESET_COIN),
                                  reset_scope=True)
    return RallyState(agent_y=mid, opp_y=mid, bx=bx, by=by, vx=vx, vy=vy,
                      played=jnp.int32(0))


def _rally_deflect(key, offset):
    """``toy.RallyEnv._deflect``: center -> shallow, edge -> steep, with
    the coin-flipped minimum-speed floor."""
    vy = jnp.float32(_MAX_VY) * offset
    sign = jnp.where(_coin(key, _T_COIN), jnp.float32(1.0),
                     jnp.float32(-1.0))
    vy = jnp.where(jnp.abs(vy) < _MIN_VY, jnp.float32(_MIN_VY) * sign, vy)
    return jnp.clip(vy, -_MAX_VY, _MAX_VY)


def _rally_render(p: RallyParams, state: RallyState) -> jax.Array:
    """Bitwise port of ``toy.RallyEnv._render`` (opponent, agent, then the
    ball — later draws overwrite)."""
    s = p.scale
    rows = jnp.arange(p.pixels, dtype=jnp.int32)[:, None]
    cols = jnp.arange(p.pixels, dtype=jnp.int32)[None, :]

    def block(row, col, h):
        r = jnp.round(row).astype(jnp.int32)
        r0 = jnp.clip(r - h, 0, p.grid - 1) * s
        r1 = (jnp.clip(r + h, 0, p.grid - 1) + 1) * s
        return ((rows >= r0) & (rows < r1)
                & (cols >= col * s) & (cols < (col + 1) * s))

    bx = jnp.clip(jnp.round(state.bx).astype(jnp.int32), 0, p.grid - 1)
    img = jnp.where(block(state.opp_y, jnp.int32(0), p.paddle_half),
                    jnp.uint8(128), jnp.uint8(0))
    img = jnp.where(block(state.agent_y, jnp.int32(p.grid - 1), p.a_half),
                    jnp.uint8(128), img)
    img = jnp.where(block(state.by, bx, 0), jnp.uint8(255), img)
    return img[:, :, None]


def make_rally(grid: int = 21, pixels: int = 84, points: int = 3,
               paddle_half: int = 1, agent_half: int | None = None,
               opp_speed: float = 1.0,
               env_id: str = "ApexRally-v0") -> JaxEnv:
    p = RallyParams(grid=grid, pixels=pixels, points=points,
                    paddle_half=paddle_half, agent_half=agent_half,
                    opp_speed=opp_speed)
    g, half, ahalf = p.grid, p.paddle_half, p.a_half
    speed = jnp.float32(p.opp_speed)

    def reset(key) -> tuple[RallyState, jax.Array]:
        state = _rally_reset_state(p, key)
        return state, _rally_render(p, state)

    def step(state: RallyState, action, key):
        move = jnp.asarray([0.0, -1.0, 1.0], jnp.float32)[action]
        agent_y = jnp.clip(state.agent_y + move, jnp.float32(ahalf),
                           jnp.float32(g - 1 - ahalf))
        opp_y = jnp.clip(
            state.opp_y + jnp.clip(state.by - state.opp_y, -speed, speed),
            jnp.float32(half), jnp.float32(g - 1 - half))
        bx = state.bx + state.vx.astype(jnp.float32)
        by = state.by + state.vy
        # wall reflection (|vy| <= 1.75 < g-1 => at most one bounce)
        hit_low, hit_high = by < 0, by > g - 1
        by = jnp.where(hit_low, -by, jnp.where(hit_high, 2 * (g - 1) - by,
                                               by))
        vy = jnp.where(hit_low | hit_high, -state.vy, state.vy)

        at_opp = bx <= 0
        at_agent = bx >= g - 1
        opp_saves = jnp.abs(by - opp_y) <= half + 0.5
        agent_saves = jnp.abs(by - agent_y) <= ahalf + 0.5
        opp_deflect = at_opp & opp_saves
        agent_deflect = at_agent & agent_saves
        agent_scores = at_opp & ~opp_saves
        opp_scores = at_agent & ~agent_saves
        scored = agent_scores | opp_scores

        reward = jnp.where(agent_scores, jnp.float32(1.0),
                           jnp.where(opp_scores, jnp.float32(-1.0),
                                     jnp.float32(0.0)))
        # deflections: position snaps to the goal column, vy from the
        # normalized hit offset (the one per-step paddle contact)
        off = jnp.where(opp_deflect, (by - opp_y) / jnp.float32(half + 0.5),
                        (by - agent_y) / jnp.float32(ahalf + 0.5))
        dvy = _rally_deflect(key, off)
        any_deflect = opp_deflect | agent_deflect
        bx = jnp.where(opp_deflect, jnp.float32(0.0),
                       jnp.where(agent_deflect, jnp.float32(g - 1), bx))
        vx = jnp.where(opp_deflect, jnp.int32(1),
                       jnp.where(agent_deflect, jnp.int32(-1), state.vx))
        vy = jnp.where(any_deflect, dvy, vy)
        # serve after a point (toward the side that conceded)
        # apexlint: disable=J004 -- distinct fold-in tags per draw site (module docstring), not key reuse
        sbx, sby, svx, svy = _rally_serve(p, key, opp_scores,
                                          reset_scope=False)
        bx = jnp.where(scored, sbx, bx)
        by = jnp.where(scored, sby, by)
        vx = jnp.where(scored, svx, vx)
        vy = jnp.where(scored, svy, vy)
        played = state.played + scored.astype(jnp.int32)
        done = played >= p.points

        mid = RallyState(agent_y=agent_y, opp_y=opp_y, bx=bx, by=by,
                         vx=vx, vy=vy, played=played)
        final_frame = _rally_render(p, mid)
        # apexlint: disable=J004 -- auto-reset draws use the _T_RESET_* tag family, disjoint from the in-step tags above
        fresh = _rally_reset_state(p, key)
        out = jax.tree.map(lambda a, b: jnp.where(done, a, b), fresh, mid)
        obs = jnp.where(done, _rally_render(p, fresh), final_frame)
        return out, obs, reward, done, final_frame

    return JaxEnv(reset=reset, step=step,
                  frame_shape=(pixels, pixels, 1), num_actions=3,
                  env_id=env_id)


# -- Tokens ------------------------------------------------------------------


class TokensState(NamedTuple):
    ids: jax.Array          # i32[T] the context
    steps: jax.Array        # i32


def make_tokens(context: int = 16, vocab: int = 64,
                episode_steps: int = 64,
                env_id: str = "ApexTokens-v0") -> JaxEnv:
    """Bitwise port of :class:`apex_tpu.envs.toy.TokensEnv` (integers
    only).  The prompt is ONE keyed draw of ``context`` ids at the
    reset-scope integer tag."""

    def frame(ids):
        return jnp.stack([ids % 256, ids // 256], 1).reshape(-1).astype(
            jnp.uint8)

    def fresh(key) -> TokensState:
        ids = jax.random.randint(jax.random.fold_in(key, _T_RESET_INT),
                                 (context,), 0, vocab)
        return TokensState(ids=ids.astype(jnp.int32), steps=jnp.int32(0))

    def reset(key) -> tuple[TokensState, jax.Array]:
        state = fresh(key)
        return state, frame(state.ids)

    def step(state: TokensState, action, key):
        c = state.ids
        want = (31 * c[-1] + c[-2] + 7) % vocab
        action = action.astype(jnp.int32)
        reward = jnp.where(action == want, jnp.float32(1.0),
                           jnp.float32(0.0))
        mid = TokensState(ids=jnp.concatenate([c[1:], action[None]]),
                          steps=state.steps + 1)
        done = mid.steps >= episode_steps
        final_frame = frame(mid.ids)
        # auto-reset from the same key's reset-scope tag
        new = fresh(key)
        out = jax.tree.map(lambda a, b: jnp.where(done, a, b), new, mid)
        obs = jnp.where(done, frame(new.ids), final_frame)
        return out, obs, reward, done, final_frame

    return JaxEnv(reset=reset, step=step, frame_shape=(2 * context,),
                  num_actions=vocab, env_id=env_id)
