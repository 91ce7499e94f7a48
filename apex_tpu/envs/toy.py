"""Numpy-native environments with the gymnasium API.

The reference assumes gym[atari]'s ALE emulator (``create_env.sh:5``,
``wrapper.py:257``).  This image has no ALE, and CI must never depend on it,
so the framework ships two self-contained numpy envs:

* :class:`CartPoleEnv` — the classic control task (Barto et al. dynamics),
  1-D observations, exercises the MLP trunk; learning curves are fast enough
  for CI learning tests.
* :class:`CatchEnv` — a pixel env (falling ball, movable paddle) rendered to
  84x84x1 uint8, exercising the full conv/WarpFrame/FrameStack path without
  an emulator.

Both are cheap enough that hundreds of actor processes can run per host.
"""

from __future__ import annotations

import gymnasium as gym
import numpy as np


class CartPoleEnv(gym.Env):
    """Pole balancing; physics constants from the classic task definition."""

    metadata: dict = {}

    GRAVITY = 9.8
    CART_MASS = 1.0
    POLE_MASS = 0.1
    POLE_HALF_LEN = 0.5
    FORCE_MAG = 10.0
    TAU = 0.02
    THETA_LIMIT = 12 * 2 * np.pi / 360
    X_LIMIT = 2.4

    def __init__(self, max_episode_steps: int = 500):
        self.observation_space = gym.spaces.Box(-np.inf, np.inf, (4,),
                                                np.float32)
        self.action_space = gym.spaces.Discrete(2)
        self._max_steps = max_episode_steps
        self._state = np.zeros(4, np.float64)
        self._steps = 0

    def reset(self, *, seed=None, options=None):
        super().reset(seed=seed)
        self._state = self.np_random.uniform(-0.05, 0.05, size=4)
        self._steps = 0
        return self._state.astype(np.float32), {}

    def step(self, action):
        x, x_dot, theta, theta_dot = self._state
        force = self.FORCE_MAG if action == 1 else -self.FORCE_MAG
        total_mass = self.CART_MASS + self.POLE_MASS
        pole_ml = self.POLE_MASS * self.POLE_HALF_LEN

        cos, sin = np.cos(theta), np.sin(theta)
        temp = (force + pole_ml * theta_dot ** 2 * sin) / total_mass
        theta_acc = (self.GRAVITY * sin - cos * temp) / (
            self.POLE_HALF_LEN * (4.0 / 3.0 - self.POLE_MASS * cos ** 2 /
                                  total_mass))
        x_acc = temp - pole_ml * theta_acc * cos / total_mass

        self._state = np.array([
            x + self.TAU * x_dot,
            x_dot + self.TAU * x_acc,
            theta + self.TAU * theta_dot,
            theta_dot + self.TAU * theta_acc,
        ])
        self._steps += 1

        terminated = bool(abs(self._state[0]) > self.X_LIMIT
                          or abs(self._state[2]) > self.THETA_LIMIT)
        truncated = self._steps >= self._max_steps
        return (self._state.astype(np.float32), 1.0, terminated, truncated, {})


class VelocityMask(gym.ObservationWrapper):
    """Hide CartPole's velocity components — the classic DRQN/partially-
    observable variant (Hausknecht & Stone 2015): the agent sees only
    ``(x, theta)`` and must infer velocities from history, which a
    feedforward Q-network cannot do and a recurrent one can.  This is the
    learning certificate env for the R2D2 family."""

    _KEEP = np.array([0, 2])

    def __init__(self, env: gym.Env):
        super().__init__(env)
        self.observation_space = gym.spaces.Box(-np.inf, np.inf, (2,),
                                                np.float32)

    def observation(self, obs):
        return np.asarray(obs, np.float32)[self._KEEP]


class ContinuousNavEnv(gym.Env):
    """Continuous-action navigation: drive a point to the origin.

    The CI-scale continuous-control task for AQL (the reference exercises
    AQL on gym Box-action tasks, ``model.py:174-176``).  Observation is the
    agent's position in ``[-2, 2]^dim``; the action is a velocity in
    ``[-1, 1]^dim`` scaled by 0.2; reward is ``-|position|_2`` per step, so
    an optimal policy proposes actions pointing at the origin and episode
    return climbs toward 0.  Episodes truncate at ``max_episode_steps``.
    """

    metadata: dict = {}

    def __init__(self, dim: int = 2, max_episode_steps: int = 30,
                 step_scale: float = 0.2):
        self.dim, self._max_steps, self._scale = dim, max_episode_steps, \
            step_scale
        self.observation_space = gym.spaces.Box(-2.0, 2.0, (dim,), np.float32)
        self.action_space = gym.spaces.Box(-1.0, 1.0, (dim,), np.float32)
        self._pos = np.zeros(dim, np.float64)
        self._steps = 0

    def reset(self, *, seed=None, options=None):
        super().reset(seed=seed)
        self._pos = self.np_random.uniform(-2.0, 2.0, size=self.dim)
        self._steps = 0
        return self._pos.astype(np.float32), {}

    def step(self, action):
        a = np.clip(np.asarray(action, np.float64), -1.0, 1.0)
        self._pos = np.clip(self._pos + self._scale * a, -2.0, 2.0)
        self._steps += 1
        reward = -float(np.linalg.norm(self._pos))
        truncated = self._steps >= self._max_steps
        return self._pos.astype(np.float32), reward, False, truncated, {}


class RallyEnv(gym.Env):
    """Two-paddle rally against a scripted opponent — the Pong-shaped
    pixel task (ALE is absent from this image; ``origin_repo/create_env.sh:5``
    / ``wrapper.py:257`` assume it).  Unlike :class:`CatchEnv`'s drop-and-
    catch loop, this has OPPONENT DYNAMICS and long multi-rally credit
    horizons: points are scored tens of steps after the stroke that won
    them, and beating the opponent requires discovering the edge-shot
    mechanic rather than just tracking the ball.

    Court: ``grid x grid`` cells, rendered to ``pixels x pixels x 1``
    uint8.  The opponent guards column 0, the agent column ``grid-1``;
    actions 0=stay, 1=up, 2=down.  The ball advances one column per step;
    vertical speed is set by WHERE it strikes a paddle (center -> shallow,
    edge -> steep, the classic Pong deflection) and reflects off the
    walls.  The opponent tracks the incoming ball at speed 1 — it returns
    every shallow ball, but an edge hit sends the ball at |vy| = 1.75,
    which outruns it across the court: the agent must learn to RECEIVE
    anywhere and STRIKE with its paddle edge.  Reward +1 when the
    opponent misses, -1 when the agent does; an episode is ``points``
    points (eval metric = score differential, the reference's unclipped
    eval convention, ``origin_repo/eval.py:49-87``).
    """

    metadata: dict = {}

    MAX_VY = 1.75          # edge-hit deflection; outruns the speed-1 opponent
    MIN_VY = 0.5           # center hits stay live (no horizontal stalemates)

    def __init__(self, grid: int = 21, pixels: int = 84, points: int = 3,
                 paddle_half: int = 1, agent_half: int | None = None,
                 opp_speed: float = 1.0, dtype=np.float64):
        # ``agent_half`` widens ONLY the agent's paddle (easier receiving
        # without making the opponent harder to score past) and
        # ``opp_speed`` caps the opponent's per-step tracking — the two
        # difficulty knobs the Small certificate variant uses; the full
        # variant keeps the symmetric speed-1 game
        self.grid, self.pixels, self.points = grid, pixels, points
        self.half = paddle_half
        self.agent_half = self.half if agent_half is None else agent_half
        self.opp_speed = opp_speed
        # Continuous-state compute dtype.  float64 (the python-float
        # default) is bit-identical to the pre-knob behavior; float32
        # makes every op the same correctly-rounded IEEE-f32 op the
        # jittable port (envs/jax_envs.py) runs, so the exact-trajectory
        # parity pin can compare like with like — the deflection lattice
        # is non-dyadic (7/12ths), so f64 and f32 trajectories disagree
        # at round()-to-pixel boundaries after a few paddle contacts.
        self._scalar = np.dtype(dtype).type
        self.observation_space = gym.spaces.Box(0, 255, (pixels, pixels, 1),
                                                np.uint8)
        self.action_space = gym.spaces.Discrete(3)
        self._scale = pixels // grid

    # -- mechanics ---------------------------------------------------------

    def reset(self, *, seed=None, options=None):
        super().reset(seed=seed)
        self._agent_y = self._opp_y = self._scalar((self.grid - 1) / 2)
        self._played = 0
        self._serve(toward_agent=bool(self.np_random.random() < 0.5))
        return self._render(), {}

    def _serve(self, toward_agent: bool) -> None:
        self._bx = self._scalar((self.grid - 1) / 2)
        self._by = self._scalar(self.np_random.integers(2, self.grid - 2))
        self._vx = 1 if toward_agent else -1
        self._vy = self._scalar(self.np_random.choice([-1.0, -0.5, 0.5, 1.0]))

    def _deflect(self, offset: float) -> float:
        """Paddle-contact vertical speed from the normalized hit offset
        (center 0 -> shallow, edge +-1 -> MAX_VY steep)."""
        vy = self.MAX_VY * offset
        if abs(vy) < self.MIN_VY:
            sign = 1.0 if self.np_random.random() < 0.5 else -1.0
            vy = self.MIN_VY * sign
        return self._scalar(np.clip(vy, -self.MAX_VY, self.MAX_VY))

    def step(self, action):
        g, half, ahalf = self.grid, self.half, self.agent_half
        # agent paddle
        self._agent_y = self._scalar(np.clip(
            self._agent_y + (0, -1, 1)[int(action)], ahalf, g - 1 - ahalf))
        # scripted opponent: track the ball at ALL times (a re-centering
        # opponent loses to plain tracking — measured; this one only
        # loses to deliberately generated steep angles, or — at reduced
        # opp_speed — to sustained accurate returns)
        self._opp_y = self._scalar(np.clip(
            self._opp_y + np.clip(self._by - self._opp_y,
                                  -self.opp_speed, self.opp_speed),
            half, g - 1 - half))
        # ball advance + wall reflection
        self._bx += self._vx
        self._by += self._vy
        while self._by < 0 or self._by > g - 1:
            if self._by < 0:
                self._by = -self._by
            else:
                self._by = 2 * (g - 1) - self._by
            self._vy = -self._vy

        reward = 0.0
        if self._bx <= 0:                       # opponent's goal column
            if abs(self._by - self._opp_y) <= half + 0.5:
                self._bx, self._vx = self._scalar(0.0), 1
                self._vy = self._deflect(
                    (self._by - self._opp_y) / (half + 0.5))
            else:
                reward = 1.0
                self._played += 1
                self._serve(toward_agent=False)
        elif self._bx >= g - 1:                 # agent's goal column
            if abs(self._by - self._agent_y) <= ahalf + 0.5:
                self._bx, self._vx = self._scalar(g - 1), -1
                self._vy = self._deflect(
                    (self._by - self._agent_y) / (ahalf + 0.5))
            else:
                reward = -1.0
                self._played += 1
                self._serve(toward_agent=True)
        terminated = self._played >= self.points
        return self._render(), reward, terminated, False, {}

    # -- rendering ---------------------------------------------------------

    def _block(self, img, row: float, col: int, h: int, value: int) -> None:
        s = self._scale
        r0 = int(np.clip(round(row) - h, 0, self.grid - 1)) * s
        r1 = (int(np.clip(round(row) + h, 0, self.grid - 1)) + 1) * s
        img[r0:r1, col * s:(col + 1) * s] = value

    def _render(self) -> np.ndarray:
        img = np.zeros((self.pixels, self.pixels, 1), np.uint8)
        self._block(img, self._opp_y, 0, self.half, 128)
        self._block(img, self._agent_y, self.grid - 1, self.agent_half, 128)
        bx = int(np.clip(round(self._bx), 0, self.grid - 1))
        self._block(img, self._by, bx, 0, 255)
        return img


class CatchEnv(gym.Env):
    """Catch a falling ball with a paddle; pixel observations.

    Internal grid is ``grid x grid``; observations are rendered to
    ``pixels x pixels x 1`` uint8 (default 84, matching WarpFrame geometry).
    Reward +1 for a catch, -1 for a miss; an episode is ``balls`` drops.
    Actions: 0=stay, 1=left, 2=right.
    """

    metadata: dict = {}

    def __init__(self, grid: int = 21, pixels: int = 84, balls: int = 5):
        self.grid, self.pixels, self.balls = grid, pixels, balls
        self.observation_space = gym.spaces.Box(0, 255, (pixels, pixels, 1),
                                                np.uint8)
        self.action_space = gym.spaces.Discrete(3)
        self._scale = pixels // grid

    def reset(self, *, seed=None, options=None):
        super().reset(seed=seed)
        self._paddle = self.grid // 2
        self._drop()
        self._remaining = self.balls
        return self._render(), {}

    def _drop(self):
        self._ball_x = int(self.np_random.integers(0, self.grid))
        self._ball_y = 0

    def step(self, action):
        self._paddle = int(np.clip(self._paddle + (0, -1, 1)[int(action)],
                                   0, self.grid - 1))
        self._ball_y += 1
        reward, terminated = 0.0, False
        if self._ball_y == self.grid - 1:
            reward = 1.0 if abs(self._ball_x - self._paddle) <= 1 else -1.0
            self._remaining -= 1
            if self._remaining == 0:
                terminated = True
            else:
                self._drop()
        return self._render(), reward, terminated, False, {}

    def _render(self) -> np.ndarray:
        s = self._scale
        img = np.zeros((self.pixels, self.pixels, 1), np.uint8)
        by, bx = self._ball_y * s, self._ball_x * s
        img[by:by + s, bx:bx + s] = 255
        py = (self.grid - 1) * s
        p0 = max(self._paddle - 1, 0) * s
        p1 = (min(self._paddle + 1, self.grid - 1) + 1) * s
        img[py:py + s, p0:p1] = 128
        return img


class TokensEnv(gym.Env):
    """``ApexTokens-v0``: next-token choice over a context of token ids,
    the stand-in for a text environment a language-model torso acts in.

    Integers only, so the jittable port (``envs/jax_envs.py``) is bitwise.
    Reset draws a prompt of ``context`` ids below ``vocab``; the action is
    the next id; the reward is 1.0 when it equals ``(31 c[T-1] + c[T-2] +
    7) mod vocab`` of the context it was chosen in, else 0; the context
    shifts by one and takes the action; an episode is ``EPISODE_STEPS``
    steps.  The frame is the context as ``u8[2 * context]``, two bytes an
    id, low byte first (frame stack 1)."""

    metadata: dict = {}

    EPISODE_STEPS = 64

    def __init__(self, context: int = 16, vocab: int = 64):
        if not 2 <= vocab <= 65536 or context < 2:
            raise ValueError(f"ApexTokens needs 2 <= vocab <= 65536 and "
                             f"context >= 2, got {vocab}, {context}")
        self.context, self.vocab = context, vocab
        self.observation_space = gym.spaces.Box(0, 255, (2 * context,),
                                                np.uint8)
        self.action_space = gym.spaces.Discrete(vocab)
        self._ids = np.zeros(context, np.int32)
        self._steps = 0

    def _frame(self) -> np.ndarray:
        ids = self._ids.astype(np.int64)
        return np.stack([ids % 256, ids // 256], 1).reshape(-1).astype(
            np.uint8)

    def reset(self, *, seed=None, options=None):
        super().reset(seed=seed)
        self._ids = np.asarray(
            self.np_random.integers(0, self.vocab, size=self.context),
            np.int32)
        self._steps = 0
        return self._frame(), {}

    def step(self, action):
        c = self._ids.astype(np.int64)
        want = (31 * c[-1] + c[-2] + 7) % self.vocab
        reward = 1.0 if int(action) == int(want) else 0.0
        self._ids = np.concatenate([self._ids[1:],
                                    np.asarray([action], np.int32)])
        self._steps += 1
        return (self._frame(), reward, False,
                self._steps >= self.EPISODE_STEPS, {})
