"""On-device Anakin rollouts: env + policy + chunk assembly in one scan.

The host actor plane (:mod:`apex_tpu.actors.vector`) pays one policy
dispatch, B python ``env.step`` calls, and B ``FrameChunkBuilder.add_step``
calls per vector step — ~50 env-frames/s end to end on the 1-core CI box.
For the jittable envs (:func:`apex_tpu.envs.registry.make_jax_env`) the
whole loop moves inside the accelerator: ONE ``lax.scan`` of ``T`` steps
over ``B`` vectorized envs runs

    acting-stack gather -> epsilon-greedy policy -> env step (auto-reset)
    -> n-step window -> chunk assembly

per step, emitting sealed chunks that are schema- and bit-compatible with
:class:`~apex_tpu.replay.frame_chunks.FrameChunkBuilder` output — the SAME
message dicts ``drain_builder_chunks`` ships, so they flow into the
existing replay path (in-learner fused ingest, the ingest pipeline's
merge/stack contract, the sharded replay service) unchanged
(tests/test_anakin.py pins chunk-for-chunk equality and FramePoolReplay
ingest parity against a host builder replaying the same trajectory).

The builder port is an exact state machine twin: per-episode frame
registration with chunk-relative refs, the n-step window with full-window
``gamma**n`` emission and terminal tails, flush-on-K and flush-for-frames
with episode frame carry, pad-rows-repeat-last, and acting-time TD
priorities.  n-step returns fold host-precomputed ``float32(gamma**i)``
coefficients left-to-right, which is bit-identical to the host builder's
float64 fold whenever a window holds at most one nonzero reward — always
true for Catch/Rally, whose scores are >= n steps apart.

Two consumers:

* :class:`AnakinPool` — an ActorPool-shaped adapter co-locating rollouts
  with the learner (``--rollout ondevice``): params hand over as on-device
  arrays (never leaving the device), chunks surface through the standard
  ``poll_chunks`` interface, heartbeats/episode stats through
  ``poll_stats``.  Optionally wraps an inner pool (socket RemotePool) so a
  fleet can mix on-device rollouts with host actors/evaluators.
* ``--role loadgen`` (:func:`apex_tpu.runtime.roles.run_loadgen`) — the
  standalone synthetic-traffic generator driving the replay shards and the
  learner ingest at device rate.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import deque
from typing import Any, NamedTuple

import numpy as np

from apex_tpu.config import ApexConfig
from apex_tpu.replay.frame_chunks import FRAME_MARGIN

# per-step key derivation tags (the parity tests replay these)
T_POLICY = 0      # policy_fn key for the step
T_ENV = 1         # env key root; per-slot keys fold the slot index on top


class RolloutCarry(NamedTuple):
    """Vectorized builder + env state between scan steps (leading axis B).

    Deliberately SMALL: the scan carry holds only bookkeeping (int32 row
    maps, the n-step window, the S-frame acting stack; of each Q row the
    two numbers the acting-time priority reads, ``q[a]`` and ``max q``,
    both picked without arithmetic and so the bits the host builder reads
    off whole rows) — frame BYTES leave
    the scan as per-step outputs, land in an append-only per-dispatch ring,
    and materialize into chunk layout once per dispatch (``fmap`` maps each
    chunk row to its ring row).  A first cut kept ``[B, M, Kf, D]`` frame
    buffers in the carry and lost 30x to XLA:CPU copying them per scan
    step; with index bookkeeping the hot loop moves 4 bytes where it used
    to move a frame.

    The outbox holds ``M`` chunk slots per env slot; slot ``sealed[b]`` is
    the in-progress chunk (at most one seal per step — ``_flush``),
    earlier slots are sealed this dispatch."""

    env: Any                # env-state pytree
    stack: Any              # u8[B, S, D] acting stack, oldest frame first
    fmap: Any               # i32[B, M, Kf] chunk row -> dispatch ring row
    action: Any             # i32[B, M, K]
    rd: Any                 # f32[B, M, K, 2] (reward, discount) pairs
    refs: Any               # i32[B, M, K, 2, S] (obs_ref, next_ref) pairs
    q: Any                  # f32[B, M, K, 2] (q0[a], max qn) pairs
    counts: Any             # i32[B, M, 2] (n_frames, n_trans) at seal
    sealed: Any             # i32[B] chunks sealed this dispatch (= cur slot)
    cur_nf: Any             # i32[B] in-progress frame count
    cur_nt: Any             # i32[B] in-progress transition count
    ep_step: Any            # i32[B] episode frame index of newest frame
    rows: Any               # i32[B, W] chunk rows of the last W ep frames
    w_obs: Any              # i32[B, n+1]
    w_act: Any              # i32[B, n+1]
    w_rew: Any              # f32[B, n+1]
    w_q: Any                # f32[B, n+1, 2] (q[a], max q) of each step
    w_len: Any              # i32[B]
    ep_ret: Any             # f32[B]
    ep_len: Any             # i32[B]


class AnakinRollout:
    """The fused rollout engine for one jittable env.

    ``rollout(params)`` runs one jitted dispatch of ``rollout_len`` scanned
    steps over ``n_envs`` slots and returns ``(messages, stats)`` — chunk
    messages in the ``drain_builder_chunks`` schema plus
    :class:`~apex_tpu.actors.pool.EpisodeStat` records for episodes that
    ended inside the dispatch.  Between dispatches the in-progress chunk's
    frames persist in ``carry_frames`` (ring rows ``[0, Kf)`` of the next
    dispatch); everything else carries as index bookkeeping.
    """

    def __init__(self, env, policy_fn, *, n_envs: int, epsilons,
                 slot_ids=None, n_steps: int = 3, gamma: float = 0.99,
                 frame_stack: int = 4, chunk_transitions: int = 64,
                 rollout_len: int | None = None,
                 frame_margin: int = FRAME_MARGIN, seed: int = 0,
                 acting_params=None):
        import jax

        self.env = env
        self.policy_fn = policy_fn
        self.B = int(n_envs)
        self.n = int(n_steps)
        self.S = int(frame_stack)
        self.K = int(chunk_transitions)
        self.Kf = self.K + int(frame_margin)
        self.W = self.S + self.n + 1
        self.T = int(rollout_len or chunk_transitions)
        # transitions emitted per dispatch <= leftover window + T + n, and
        # every seal consumes at least one; +1 in-progress slot, +1 slack
        # for frame-overflow partial seals (overflow past M is detected
        # loudly in rollout(), never silent corruption)
        self.M = (self.T + self.n + self.K - 1) // self.K + 3
        self.D = int(np.prod(env.frame_shape))
        self.frame_shape = tuple(env.frame_shape)
        self.slot_ids = list(slot_ids if slot_ids is not None
                             else range(self.B))
        self.epsilons = np.asarray(epsilons, np.float32)
        if len(self.epsilons) != self.B:
            raise ValueError(
                f"epsilons arity {len(self.epsilons)} != n_envs {self.B}")
        # host-f64 gamma powers as f32 constants: the device return fold
        # uses the exact coefficients the host builder's f64 math rounds to
        self.gpow = np.asarray([np.float64(gamma) ** i
                                for i in range(self.n + 1)], np.float32)
        self.key = jax.random.key(seed)
        self.key, init_key = jax.random.split(self.key)
        self.carry, self.carry_frames = self._init_carry(init_key)

        def anakin_rollout(*args):      # the program's name in a trace
            return self._dispatch(*args)

        self._jit = jax.jit(anakin_rollout)
        # how a publish snapshots the learner's parameters for this policy
        # (AnakinPool): the model's cast to what it multiplies with; None
        # copies
        self.acting_params = acting_params
        # counters (host-side observability)
        self.dispatches = 0
        self.chunks = 0
        self.frames = 0
        self.transitions = 0

    # -- construction ------------------------------------------------------

    def reset_keys(self, key):
        """Per-slot env reset keys — ``fold_in(key, slot)`` (the parity
        replay reproduces this chain)."""
        import jax
        return jax.vmap(jax.random.fold_in, (None, 0))(
            key, np.arange(self.B, dtype=np.uint32))

    def _init_carry(self, key):
        import jax
        import jax.numpy as jnp

        states, obs = jax.vmap(self.env.reset)(self.reset_keys(key))
        B, M, K, Kf, S = self.B, self.M, self.K, self.Kf, self.S
        flat = obs.reshape(B, self.D)
        # begin_episode: reset frame is episode frame 0 = chunk row 0;
        # the acting stack starts as S copies of it (host FrameStack.reset)
        carry_frames = jnp.zeros((B, Kf, self.D), jnp.uint8).at[:, 0].set(
            flat)
        carry = RolloutCarry(
            env=states,
            stack=jnp.broadcast_to(flat[:, None], (B, S, self.D)),
            fmap=jnp.zeros((B, M, Kf), jnp.int32),
            action=jnp.zeros((B, M, K), jnp.int32),
            rd=jnp.zeros((B, M, K, 2), jnp.float32),
            refs=jnp.zeros((B, M, K, 2, S), jnp.int32),
            q=jnp.zeros((B, M, K, 2), jnp.float32),
            counts=jnp.zeros((B, M, 2), jnp.int32),
            sealed=jnp.zeros(B, jnp.int32),
            cur_nf=jnp.ones(B, jnp.int32),
            cur_nt=jnp.zeros(B, jnp.int32),
            ep_step=jnp.zeros(B, jnp.int32),
            rows=jnp.zeros((B, self.W), jnp.int32),
            w_obs=jnp.zeros((B, self.n + 1), jnp.int32),
            w_act=jnp.zeros((B, self.n + 1), jnp.int32),
            w_rew=jnp.zeros((B, self.n + 1), jnp.float32),
            w_q=jnp.zeros((B, self.n + 1, 2), jnp.float32),
            w_len=jnp.zeros(B, jnp.int32),
            ep_ret=jnp.zeros(B, jnp.float32),
            ep_len=jnp.zeros(B, jnp.int32))
        return carry, carry_frames

    # -- builder-port primitives (all batched over B, masked) --------------

    def _row_of(self, c: RolloutCarry, ep_idx):
        """Chunk row of episode frame ``ep_idx`` (clamped to frame 0, the
        host builder's episode-start repeat) via the recent-rows ring."""
        import jax.numpy as jnp
        idx = (self.W - 1) - (c.ep_step - jnp.maximum(ep_idx, 0))
        idx = jnp.clip(idx, 0, self.W - 1)
        return c.rows[jnp.arange(self.B), idx]

    def _rows_of(self, c: RolloutCarry, ep_idx):
        """Batched :meth:`_row_of` over a ``[B, J]`` episode-index matrix
        — ONE gather where a per-column loop would issue J."""
        import jax.numpy as jnp
        idx = (self.W - 1) - (c.ep_step[:, None]
                              - jnp.maximum(ep_idx, 0))
        idx = jnp.clip(idx, 0, self.W - 1)
        return jnp.take_along_axis(c.rows, idx, axis=1)

    def _flush(self, c: RolloutCarry, do) -> RolloutCarry:
        """``FrameChunkBuilder._flush``: seal when transitions exist (else
        drop the frame-only chunk), then carry the episode frames the live
        window and acting stack still need into the fresh chunk — an int32
        remap of ``fmap`` rows, no frame bytes move."""
        import jax.numpy as jnp
        ar = jnp.arange(self.B)
        seal = do & (c.cur_nt >= 1)
        active = do & ((c.cur_nt >= 1) | (c.cur_nf >= 1))
        # sealed-slot counts (write-through; masked writes drop)
        sl = jnp.where(seal, c.sealed, self.M)
        counts = c.counts.at[ar, sl].set(
            jnp.stack([c.cur_nf, c.cur_nt], 1), mode="drop")
        new_cur = c.sealed + seal.astype(jnp.int32)
        # frame carry: episode frames oldest..ep_step -> rows 0..count-1
        has_ep = c.ep_step >= 0
        head = jnp.where(c.w_len > 0, c.w_obs[:, 0], c.ep_step)
        oldest = jnp.maximum(head - (self.S - 1), 0)
        count = jnp.where(active & has_ep, c.ep_step - oldest + 1, 0)
        # gather the carried ring rows first, then ONE batched scatter
        # (functional, so a same-slot carry — dropped frame-only chunk —
        # cannot self-clobber); per-row validity folds into the slot index
        src_rows = self._rows_of(c, oldest[:, None]
                                 + jnp.arange(self.W)[None, :])
        carried = c.fmap[ar[:, None], c.sealed[:, None], src_rows]
        j_idx = jnp.arange(self.W)[None, :]
        dst_slot = jnp.where(active[:, None] & (j_idx < count[:, None]),
                             new_cur[:, None], self.M)
        fmap = c.fmap.at[ar[:, None], dst_slot, j_idx].set(
            carried, mode="drop")
        # recent-rows remap: ep frame f's new chunk row is f - oldest
        ring_ep = (jnp.arange(self.W)[None, :]
                   + (c.ep_step - (self.W - 1))[:, None])
        rows = jnp.where(active[:, None] & has_ep[:, None],
                         ring_ep - oldest[:, None], c.rows)
        return c._replace(
            fmap=fmap, counts=counts, rows=rows,
            sealed=jnp.where(seal, new_cur, c.sealed),
            cur_nf=jnp.where(active, count, c.cur_nf),
            cur_nt=jnp.where(seal, 0, c.cur_nt))

    def _register(self, c: RolloutCarry, ring_row, do) -> RolloutCarry:
        """Append one frame (already written at ``ring_row`` of the
        dispatch ring) to the in-progress chunk + shift the recent ring."""
        import jax.numpy as jnp
        ar = jnp.arange(self.B)
        row = c.cur_nf
        fmap = c.fmap.at[
            ar, jnp.where(do, c.sealed, self.M), row].set(
            jnp.full(self.B, ring_row, jnp.int32), mode="drop")
        rows = jnp.where(do[:, None],
                         jnp.concatenate([c.rows[:, 1:], row[:, None]], 1),
                         c.rows)
        return c._replace(fmap=fmap, rows=rows,
                          cur_nf=c.cur_nf + do.astype(jnp.int32))

    def _stack_refs(self, c: RolloutCarry, end):
        """Rows of the S-stack ending at episode frame ``end`` (oldest
        first) — ``FrameChunkBuilder._stack_refs``."""
        import jax.numpy as jnp
        offs = jnp.arange(self.S - 1, -1, -1)[None, :]
        return self._rows_of(c, end[:, None] - offs)

    def _push(self, c: RolloutCarry, ret, next_end, disc, qn_max, do):
        """Emit one transition from the window head, then flush at K."""
        import jax.numpy as jnp
        ar = jnp.arange(self.B)
        head = c.w_obs[:, 0]
        obs_ref = self._stack_refs(c, head)
        next_ref = self._stack_refs(c, next_end)
        sl = jnp.where(do, c.sealed, self.M)
        pos = c.cur_nt
        c = c._replace(
            action=c.action.at[ar, sl, pos].set(c.w_act[:, 0],
                                                mode="drop"),
            rd=c.rd.at[ar, sl, pos].set(jnp.stack([ret, disc], 1),
                                        mode="drop"),
            refs=c.refs.at[ar, sl, pos].set(
                jnp.stack([obs_ref, next_ref], 1), mode="drop"),
            q=c.q.at[ar, sl, pos].set(
                jnp.stack([c.w_q[:, 0, 0], qn_max], 1), mode="drop"),
            cur_nt=c.cur_nt + do.astype(jnp.int32))
        return self._flush(c, do & (c.cur_nt == self.K))

    def _popleft(self, c: RolloutCarry, do) -> RolloutCarry:
        import jax.numpy as jnp
        m = do[:, None]

        def roll(a):
            r = jnp.concatenate([a[:, 1:], a[:, :1]], 1)
            mm = m[..., None] if a.ndim == 3 else m
            return jnp.where(mm, r, a)

        return c._replace(w_obs=roll(c.w_obs), w_act=roll(c.w_act),
                          w_rew=roll(c.w_rew), w_q=roll(c.w_q),
                          w_len=c.w_len - do.astype(jnp.int32))

    def _nstep_return(self, c: RolloutCarry, k):
        """Left-fold of ``gpow[i] * w_rew[i]`` over ``i < k`` — the host
        builder's ``sum(gamma**i * r_i)`` with host-rounded coefficients
        (bit-identical whenever a window holds at most one nonzero reward,
        which Catch/Rally score spacing guarantees)."""
        import jax.numpy as jnp
        acc = jnp.zeros(self.B, jnp.float32)
        for i in range(self.n + 1):
            acc = acc + jnp.where(i < k, self.gpow[i] * c.w_rew[:, i],
                                  jnp.float32(0.0))
        return acc

    # -- the scanned step --------------------------------------------------

    def _policy_obs(self, c: RolloutCarry):
        import jax.numpy as jnp
        shp = self.frame_shape
        stk = c.stack.reshape(self.B, self.S, *shp)
        stk = jnp.moveaxis(stk, 1, -2)
        return stk.reshape(self.B, *shp[:-1], self.S * shp[-1])

    def _step(self, params, eps, c: RolloutCarry, xs):
        import jax
        import jax.numpy as jnp

        step_key, t = xs
        actions, q = self.policy_fn(params, self._policy_obs(c), eps,
                                    jax.random.fold_in(step_key, T_POLICY))
        # both numbers as max-reductions of the row, the taken one over a
        # one-hot mask: a one-element gather fused into the arithmetic
        # that made the row rounded otherwise than the row the host
        # builder reads (XLA:CPU summed the dueling mean in another order)
        q = q.astype(jnp.float32)
        taken = jnp.arange(q.shape[1])[None, :] == actions[:, None]
        q_pair = jnp.stack([jnp.max(jnp.where(taken, q, -jnp.inf), axis=1),
                            q.max(axis=1)], 1)
        env_key = jax.random.fold_in(step_key, T_ENV)
        env_state, obs, reward, done, final_frame = jax.vmap(
            lambda s, a, i: self.env.step(s, a,
                                          jax.random.fold_in(env_key, i)))(
            c.env, actions, jnp.arange(self.B, dtype=jnp.uint32))
        c = c._replace(env=env_state)
        always = jnp.ones(self.B, bool)
        final_flat = final_frame.reshape(self.B, self.D)
        obs_flat = obs.reshape(self.B, self.D)
        # dispatch-ring rows of this step's two frames (epilogue layout:
        # carry region [0, Kf) then the interleaved per-step pairs)
        final_row = self.Kf + 2 * t
        obs_row = final_row + 1

        # add_step: flush-for-frames, register, window append
        c = self._flush(c, c.cur_nf + 1 > self.Kf)
        obs_idx = c.ep_step
        c = c._replace(ep_step=c.ep_step + 1)
        c = self._register(c, final_row, always)
        ar = jnp.arange(self.B)
        pos = c.w_len
        c = c._replace(
            w_obs=c.w_obs.at[ar, pos].set(obs_idx),
            w_act=c.w_act.at[ar, pos].set(actions.astype(jnp.int32)),
            w_rew=c.w_rew.at[ar, pos].set(reward),
            w_q=c.w_q.at[ar, pos].set(q_pair),
            w_len=c.w_len + 1)
        # full-window emission (gamma**n bootstrap)
        full = c.w_len == self.n + 1
        c = self._push(c, self._nstep_return(c, jnp.int32(self.n)),
                       c.w_obs[:, 0] + self.n,
                       jnp.full(self.B, self.gpow[self.n]),
                       c.w_q[:, self.n, 1], full)
        c = self._popleft(c, full)
        # terminal tails (discount 0, next stack = masked obs stack)
        for _ in range(self.n):
            m = done & (c.w_len > 0)
            k = c.w_len
            qn_max = c.w_q[ar, jnp.clip(k - 1, 0, self.n), 1]
            c = self._push(c, self._nstep_return(c, k), c.w_obs[:, 0],
                           jnp.zeros(self.B, jnp.float32), qn_max, m)
            c = self._popleft(c, m)
        c = c._replace(ep_step=jnp.where(done, -1, c.ep_step))
        # auto-reset: begin_episode(obs) for done slots
        c = self._flush(c, done & (c.cur_nf + 1 > self.Kf))
        c = c._replace(ep_step=jnp.where(done, 0, c.ep_step),
                       w_len=jnp.where(done, 0, c.w_len))
        c = self._register(c, obs_row, done)
        # acting stack: roll the new frame in; a reset rebuilds all S
        # positions from the reset frame (host bind_acting_view semantics)
        stack = jnp.concatenate([c.stack[:, 1:], final_flat[:, None]], 1)
        stack = jnp.where(done[:, None, None],
                          jnp.broadcast_to(obs_flat[:, None],
                                           stack.shape), stack)
        # episode accounting
        ep_ret = c.ep_ret + reward
        ep_len = c.ep_len + 1
        c = c._replace(stack=stack,
                       ep_ret=jnp.where(done, 0.0, ep_ret),
                       ep_len=jnp.where(done, 0, ep_len))
        return c, (final_flat, obs_flat, done, ep_ret, ep_len)

    # -- the jitted dispatch ----------------------------------------------

    def _rebase(self, c: RolloutCarry) -> RolloutCarry:
        """Dispatch prologue: the in-progress chunk moves to slot 0, its
        frames now live at identity rows of the ring's carry region."""
        import jax.numpy as jnp
        ar = jnp.arange(self.B)
        src = jnp.minimum(c.sealed, self.M - 1)

        def move(a):
            return a.at[:, 0].set(a[ar, src])

        fmap = move(c.fmap).at[:, 0].set(
            jnp.arange(self.Kf, dtype=jnp.int32)[None, :])
        return c._replace(
            fmap=fmap, action=move(c.action), rd=move(c.rd),
            refs=move(c.refs), q=move(c.q),
            rows=jnp.clip(c.rows, 0, self.Kf - 1),
            sealed=jnp.zeros(self.B, jnp.int32))

    def _dispatch(self, params, eps, c: RolloutCarry, carry_frames, key):
        import jax
        import jax.numpy as jnp

        c = self._rebase(c)
        keys = jax.random.split(key, self.T)
        with jax.named_scope("rollout"):
            c, ys = jax.lax.scan(
                lambda cc, xs: self._step(params, eps, cc, xs), c,
                (keys, jnp.arange(self.T)))
        final_flat, obs_flat, done, ep_ret, ep_len = ys
        # the dispatch ring: carry region + this dispatch's frame pairs
        pairs = jnp.stack([jnp.moveaxis(final_flat, 0, 1),
                           jnp.moveaxis(obs_flat, 0, 1)], 2)
        ring = jnp.concatenate(
            [carry_frames, pairs.reshape(self.B, 2 * self.T, self.D)], 1)
        # write-through the in-progress counts, then pad + materialize
        ar = jnp.arange(self.B)
        sl = jnp.minimum(c.sealed, self.M - 1)
        counts = c.counts.at[ar, sl].set(
            jnp.stack([c.cur_nf, c.cur_nt], 1))
        nf, nt = counts[..., 0], counts[..., 1]

        def pad(a, counts, length):
            idx = jnp.minimum(jnp.arange(length)[None, None, :],
                              jnp.maximum(counts - 1, 0)[:, :, None])
            idx = idx.reshape(idx.shape + (1,) * (a.ndim - 3))
            return jnp.take_along_axis(a, idx, axis=2)

        fmap = pad(c.fmap, nf, self.Kf)
        frames = jnp.take_along_axis(
            ring, fmap.reshape(self.B, self.M * self.Kf, 1), axis=1
        ).reshape(self.B, self.M, self.Kf, self.D)
        carry_next = frames[ar, sl]
        rd = pad(c.rd, nt, self.K)
        refs = pad(c.refs, nt, self.K)
        q = pad(c.q, nt, self.K)
        out = dict(frames=frames, action=pad(c.action, nt, self.K),
                   reward=rd[..., 0], discount=rd[..., 1],
                   obs_ref=refs[..., 0, :], next_ref=refs[..., 1, :],
                   q_taken=q[..., 0], qn_max=q[..., 1],
                   nf=nf, nt=nt, sealed=c.sealed,
                   stepped=(done, ep_ret, ep_len))
        return c, carry_next, out

    # -- host surface ------------------------------------------------------

    def rollout(self, params):
        """One dispatch; returns ``(messages, stats)``."""
        return self.collect(self.launch(params))

    def launch(self, params):
        """Enqueue one dispatch (span ``rollout_dispatch`` on the calling
        thread); :meth:`collect` waits for what it returns.  ``params`` is
        read by the program enqueued here and may be donated the moment
        this returns."""
        import jax

        from apex_tpu.obs.trace import get_ring

        self.key, k = jax.random.split(self.key)
        with get_ring().span("rollout_dispatch", "rollout",
                             {"lanes": self.B, "steps": self.T}):
            self.carry, self.carry_frames, out = self._jit(
                params, self.epsilons, self.carry, self.carry_frames, k)
        return out

    def collect(self, out):
        """The launched dispatch's ``(messages, stats)``."""
        import jax

        from apex_tpu.actors.pool import EpisodeStat
        from apex_tpu.obs import spans as obs_spans

        got = jax.device_get(out)
        sealed = got["sealed"]
        if int(sealed.max(initial=0)) > self.M - 1:
            raise RuntimeError(
                f"anakin outbox overflow: {int(sealed.max())} seals > "
                f"{self.M - 1} sealed slots — raise rollout_len headroom")
        # acting-time TD priorities in the exact numpy ops the host
        # builder runs (FrameChunkBuilder._materialize): on device XLA
        # fuses reward + discount*max into an FMA, which rounds once
        # where numpy rounds twice — a 1-ulp drift the bit-compat
        # contract forbids.  Vectorized host epilogue, not per-step work.
        target = got["reward"] + got["discount"] * got["qn_max"]
        priorities = (np.abs(target - got["q_taken"]).astype(np.float32)
                      + np.float32(1e-6))
        stamped = obs_spans.enabled()
        msgs = []
        for b in range(self.B):
            for j in range(int(sealed[b])):
                chunk = dict(
                    frames=got["frames"][b, j],
                    n_frames=np.int32(got["nf"][b, j]),
                    n_trans=np.int32(got["nt"][b, j]),
                    action=got["action"][b, j],
                    reward=got["reward"][b, j],
                    discount=got["discount"][b, j],
                    obs_ref=got["obs_ref"][b, j],
                    next_ref=got["next_ref"][b, j])
                msg = {"payload": chunk,
                       "priorities": priorities[b, j],
                       "n_trans": int(got["nt"][b, j])}
                if stamped:
                    msg[obs_spans.SPAN_KEY] = [
                        obs_spans.new_span(hop="sealed")]
                msgs.append(msg)
        done, ep_ret, ep_len = got["stepped"]
        stats = [EpisodeStat(self.slot_ids[b], float(ep_ret[t, b]),
                             int(ep_len[t, b]))
                 for t in range(self.T) for b in range(self.B)
                 if done[t, b]]
        self.dispatches += 1
        self.chunks += len(msgs)
        self.frames += self.T * self.B
        self.transitions += sum(m["n_trans"] for m in msgs)
        return msgs, stats


def make_anakin_engine(cfg: ApexConfig, rollout_len: int | None = None,
                       n_envs: int | None = None, slot_band: int = 0,
                       total_slots: int | None = None) -> AnakinRollout:
    """Engine wired from the shared config: jittable env port (guarded by
    :func:`~apex_tpu.envs.registry.make_jax_env`'s ValueError for
    non-jittable ids), the DQN policy, and the epsilon ladder.

    Defaults build the co-located engine owning the WHOLE fleet's slots
    (``n_actors * n_envs_per_actor`` env lanes, ladder spanning them all).
    A loadgen process ``i`` of ``N`` passes ``n_envs=n_envs_per_actor,
    slot_band=i, total_slots=N * n_envs_per_actor`` — the same contiguous
    ladder band a host vector worker with that actor id would own
    (:func:`apex_tpu.actors.vector.worker_slots`)."""
    from apex_tpu.actors.pool import actor_epsilons
    from apex_tpu.envs.registry import make_jax_env
    from apex_tpu.models import acting_params, make_q_network, note_torso
    from apex_tpu.models.dueling import make_policy_fn
    from apex_tpu.training.apex import dqn_env_specs

    env = make_jax_env(cfg.env.env_id, cfg.env)
    model_spec, _shape, _dtype, frame_stack = dqn_env_specs(cfg)
    model = make_q_network(model_spec)
    note_torso(model, "rollout")
    b = n_envs or max(cfg.actor.n_actors, 1) * max(
        1, cfg.actor.n_envs_per_actor)
    total = max(total_slots or 0, (slot_band + 1) * b)
    ladder = actor_epsilons(total, cfg.actor.eps_base, cfg.actor.eps_alpha)
    slot_ids = list(range(slot_band * b, (slot_band + 1) * b))
    return AnakinRollout(
        env, make_policy_fn(model),
        n_envs=b, epsilons=ladder[slot_ids], slot_ids=slot_ids,
        n_steps=cfg.learner.n_steps, gamma=cfg.learner.gamma,
        frame_stack=frame_stack,
        chunk_transitions=cfg.actor.send_interval,
        rollout_len=rollout_len,
        # distinct key chains per ladder band so N loadgen processes
        # explore different trajectories (the host fleet's per-slot seed
        # discipline, lifted to the band level)
        seed=cfg.env.seed + 1000 * (slot_band + 1),
        acting_params=functools.partial(acting_params, model))


class AnakinPool:
    """ActorPool-shaped adapter over :class:`AnakinRollout` for the
    co-located training mode (``--rollout ondevice``).

    Params hand over as ON-DEVICE arrays (``accepts_device_params`` — the
    trainer passes its live tree on the loop thread, no ``device_get``):
    a publish takes a SNAPSHOT of them as the policy multiplies them (the
    engine's ``acting_params``: the compute dtype, so half the bytes and
    the same Q bits), written into the buffers of the snapshot it replaces
    (donated), so exactly one snapshot lives however large the network.
    The lock orders a publish against the launch of a rollout that reads
    the snapshot: device programs run in the order they were enqueued, so
    a donated snapshot is never read after its buffers changed hands.
    Rollout dispatches run lazily inside ``poll_chunks`` (so the trainer's
    replay-ratio backpressure gates collection for free), and heartbeats +
    episode stats surface through ``poll_stats`` like any worker fleet.
    ``inner`` (a socket RemotePool) keeps host actors/evaluators riding
    alongside: their chunks/stats merge in, and publishes fan out to them
    as host params, from the thread that polls (the inner pool's owner)."""

    accepts_device_params = True

    def __init__(self, cfg: ApexConfig, engine: AnakinRollout | None = None,
                 inner=None, identity: str = "ondevice-0"):
        from apex_tpu.fleet.heartbeat import HeartbeatEmitter

        self.cfg = cfg
        self.engine = engine or make_anakin_engine(cfg)
        self.inner = inner
        self._params = None
        self._version = 0
        self._lock = threading.Lock()
        self._snap = self._snap_into = None
        self._wire_due = False      # inner fleet owes a host publish
        self._pending: deque = deque()
        self._stats: deque = deque()
        self._beat = HeartbeatEmitter(
            identity, role="rollout",
            interval_s=cfg.comms.heartbeat_interval_s,
            gauges_fn=self.ondevice_counters)
        self._t0 = time.monotonic()

    def __getattr__(self, name):
        # unknown surface (wire_rejected, rejoin_admitted, acks_withheld,
        # ...) delegates to the inner pool so the trainer's getattr-probed
        # counters stay live in hybrid mode; pure on-device pools simply
        # lack them
        inner = self.__dict__.get("inner")
        if inner is not None:
            return getattr(inner, name)
        raise AttributeError(name)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        if self.inner is not None:
            self.inner.start()

    def cleanup(self) -> None:
        if self.inner is not None:
            self.inner.cleanup()

    # -- param plane -------------------------------------------------------

    def publish_params(self, version: int, params) -> None:
        """Snapshot ``params`` (the learner's live tree, or any tree) for
        the engine; an inner fleet gets its host copy at the next poll."""
        import jax

        if self._snap is None:
            cast = self.engine.acting_params or (
                lambda p: jax.tree.map(lambda x: x, p))
            # jit outputs never alias an input that is not donated: the
            # snapshot owns its buffers, whatever the caller donates next
            self._snap = jax.jit(cast)
            self._snap_into = jax.jit(lambda p, old: cast(p),
                                      donate_argnums=(1,), keep_unused=True)
        with self._lock:
            # the first publish runs both programs, so neither compiles
            # later (a benchmark's window allows no compile)
            old = self._params if self._params is not None \
                else self._snap(params)
            self._params = self._snap_into(params, old)
            self._version = version
            self._wire_due = self.inner is not None

    def _publish_wire(self) -> None:
        """The inner fleet's host copy of the snapshot, float32 on the
        wire as before (the values the policy multiplies with)."""
        import jax
        with self._lock:
            if not self._wire_due:
                return
            self._wire_due = False
            version, host = self._version, jax.device_get(self._params)
        self.inner.publish_params(version, jax.tree.map(
            lambda x: np.asarray(x).astype(np.float32), host))

    @property
    def needs_warmup_republish(self) -> bool:
        return bool(getattr(self.inner, "needs_warmup_republish", False))

    def set_learner_epoch(self, epoch: int) -> None:
        setter = getattr(self.inner, "set_learner_epoch", None)
        if setter is not None:
            setter(epoch)

    def peer_seen(self):
        seen = getattr(self.inner, "peer_seen", None)
        return seen() if callable(seen) else {}

    # -- data plane --------------------------------------------------------

    def poll_chunks(self, max_chunks: int, timeout: float = 0.0) -> list:
        out = []
        if self.inner is not None:
            self._publish_wire()
            out = self.inner.poll_chunks(max_chunks, timeout=0)
        dry = 0
        while len(out) < max_chunks:
            if not self._pending:
                # a short-rollout dispatch can seal nothing (the n-step
                # window lags the first emissions); each dispatch strictly
                # advances the stream, so a couple of retries always
                # produce — the cap only guards a pathological config
                if self._params is None or dry >= 4:
                    break
                with self._lock:
                    launched = self.engine.launch(self._params)
                msgs, stats = self.engine.collect(launched)
                self._pending.extend(msgs)
                self._stats.extend(stats)
                dry = 0 if msgs else dry + 1
                continue
            out.append(self._pending.popleft())
        return out

    def poll_stats(self) -> list:
        out = list(self._stats)
        self._stats.clear()
        self._beat.tick(0)
        hb = self._beat.maybe_beat(self._version)
        if hb is not None:
            e = self.engine
            hb.fps = round(e.frames / max(time.monotonic() - self._t0,
                                          1e-9), 1)
            hb.chunks_sent = e.chunks
            out.append(hb)
        if self.inner is not None:
            out.extend(self.inner.poll_stats())
        return out

    def ondevice_counters(self) -> dict:
        """``fleet_summary.json``'s ``ondevice`` section (the anakin-smoke
        CI job asserts these are nonzero)."""
        e = self.engine
        return {"dispatches": e.dispatches, "chunks": e.chunks,
                "frames": e.frames, "transitions": e.transitions,
                "rollout_len": e.T, "n_envs": e.B}
