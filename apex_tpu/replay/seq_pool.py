"""Frame-dedup prioritized SEQUENCE replay for the recurrent (R2D2) family.

The stacked sequence layout (:mod:`apex_tpu.training.r2d2` on
:class:`~apex_tpu.replay.device.DeviceReplay`) stores every sequence's
``[T, H, W, c]`` observation block verbatim.  With R2D2's overlapping
windows (stride = unroll/2) each env frame appears in ~``t_total/stride``
sequences (~3.4x at defaults, ~6x at Atari-scale unrolls) — the sequence
analogue of the stacked-observation blowup the transition family solves
with :class:`~apex_tpu.replay.frame_pool.FramePoolReplay`, and of the
reference's host-side LazyFrames dedup (``origin_repo/wrapper.py:218-252``).

This module applies the same cure to sequences:

* a frame ring ``u8[F, D]`` stores every env frame ONCE;
* sequences store a ``[T]``-windowed id table (``obs_ids``) into the ring
  alongside their scalar-per-step leaves (action/reward/discount/mask) and
  the stored recurrent state;
* sampling gathers ``B*T`` rows and reshapes to ``[B, T, *frame_shape]``
  inside the fused step — bit-identical batches to the stacked layout
  (pinned in ``tests/test_seq_pool.py``).

Ingest contract (messages built by
:func:`apex_tpu.actors.r2d2.pooled_sequence_message`): every message is
SELF-CONTAINED — it ships each referenced frame exactly once (message-
relative refs in ``[0, Kf)``), row 0 is an all-zero frame shared by every
padded sequence position, pad frame rows are all-zero and redirect onto
row 0's slot, and pad sequences repeat the last real sequence — in every
case the FramePool duplicate-write invariant applies unchanged: a scatter
whose duplicate indices carry identical values writes nothing new.

Staleness is handled exactly as in :class:`FramePoolReplay`: each sequence
records the frame-cursor epoch of its message, and sampled sequences whose
epoch has aged out of the ring redirect to the newest (always-valid) slot.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from flax import struct

from apex_tpu.ops import tree as tree_ops
from apex_tpu.replay.base import PERMethods


@struct.dataclass
class SequenceFramePoolState:
    """Donated-buffer state of one pooled sequence-replay shard."""

    frames: jax.Array       # u8[F, D] (or tiled [F, 8, D/8]) — frame ring
    action: jax.Array       # i32[C, T]
    reward: jax.Array       # f32[C, T]
    discount: jax.Array     # f32[C, T]
    mask: jax.Array         # f32[C, T]
    state_c: jax.Array      # f32[C, H] — stored recurrent state (cell)
    state_h: jax.Array      # f32[C, H]
    obs_ids: jax.Array      # i32[C, T] — frame-ring rows, in step order
    frame_epoch: jax.Array  # i32[C] — frame cursor at ingest (staleness)
    sum_tree: jax.Array     # f32[2C]
    min_tree: jax.Array     # f32[2C]
    pos: jax.Array          # i32 — next sequence write index
    f_epoch: jax.Array      # i32 — total frames ever written
    size: jax.Array         # i32 — live sequence count
    max_priority: jax.Array  # f32


@dataclass(frozen=True)
class SequenceFramePoolReplay(PERMethods):
    """Static spec + pure methods (hashable; closes over jits).

    ``t_total`` is the stored sequence length (burn_in + unroll + n_steps),
    ``lstm_features`` the recurrent state width.  ``frame_shape`` is one
    frame — the recurrent family acts on single frames (the LSTM is the
    memory), so there is no frame-stack axis here.
    """

    capacity: int                                 # sequences
    t_total: int
    lstm_features: int
    frame_shape: tuple[int, ...] = (84, 84, 1)
    frame_capacity: int | None = None
    frame_dtype: str = "uint8"
    alpha: float = 0.6
    eps: float = 1e-6
    gather_mode: str = "auto"   # see FramePoolReplay.gather_mode

    def __post_init__(self):
        tree_ops._check_capacity(self.capacity)
        # f_capacity needs no power-of-2 shape: the ring uses plain
        # modular arithmetic, only the TREES (over `capacity`) require it
        if self.f_capacity <= 0:
            raise ValueError(f"frame_capacity must be positive, "
                             f"got {self.f_capacity}")
        if self.f_capacity < self.t_total:
            raise ValueError(
                f"frame_capacity={self.f_capacity} cannot hold one "
                f"{self.t_total}-step sequence window")

    # -- geometry (shared conventions with FramePoolReplay) ----------------

    @property
    def f_capacity(self) -> int:
        # sequences reference ~stride new frames each; 4*capacity covers
        # the default stride=8 at half occupancy — drivers size this
        # explicitly from the configured stride (build_r2d2)
        return (self.frame_capacity if self.frame_capacity is not None
                else 4 * self.capacity)

    @property
    def frame_dim(self) -> int:
        return math.prod(self.frame_shape)

    @property
    def row_dim(self) -> int:
        """Tile-padded row width — same rule as
        :meth:`FramePoolReplay.row_dim` so the pallas gather kernel can
        DMA single rows of pixel rings."""
        from apex_tpu.ops.gather import ROW_UNIT, pallas_eligible
        d = self.frame_dim
        padded = -(-d // ROW_UNIT) * ROW_UNIT
        if d >= ROW_UNIT // 2 and pallas_eligible(padded, self.frame_dtype):
            return padded
        return d

    @property
    def ring_shape(self) -> tuple[int, ...]:
        """Kernel-eligible rings store the tiled 3-D view (see
        :meth:`FramePoolReplay.ring_shape`)."""
        from apex_tpu.ops.gather import pallas_eligible
        if pallas_eligible(self.row_dim, self.frame_dtype):
            return (self.f_capacity, 8, self.row_dim // 8)
        return (self.f_capacity, self.row_dim)

    def hbm_bytes(self) -> int:
        """Estimated HBM footprint of one shard (drivers budget-check this
        BEFORE allocating)."""
        c, t, h = self.capacity, self.t_total, self.lstm_features
        frame_bytes = (self.f_capacity * self.row_dim
                       * jnp.dtype(self.frame_dtype).itemsize)
        per_seq = 4 * (5 * t + 2 * h + 1)   # 4 [T] f32/i32 + ids + state + epoch
        tree_bytes = 2 * (2 * c) * 4
        return frame_bytes + c * per_seq + tree_bytes

    # -- construction ------------------------------------------------------

    def init(self, example_item=None) -> SequenceFramePoolState:
        """``example_item`` accepted and ignored (interface parity with
        :meth:`DeviceReplay.init`; shapes come from the spec)."""
        c, t, h = self.capacity, self.t_total, self.lstm_features
        return SequenceFramePoolState(
            frames=jnp.zeros(self.ring_shape, jnp.dtype(self.frame_dtype)),
            action=jnp.zeros((c, t), jnp.int32),
            reward=jnp.zeros((c, t), jnp.float32),
            discount=jnp.zeros((c, t), jnp.float32),
            mask=jnp.zeros((c, t), jnp.float32),
            state_c=jnp.zeros((c, h), jnp.float32),
            state_h=jnp.zeros((c, h), jnp.float32),
            obs_ids=jnp.zeros((c, t), jnp.int32),
            frame_epoch=jnp.full(c, jnp.int32(-(2 ** 30))),  # born stale
            sum_tree=tree_ops.init_sum_tree(c),
            min_tree=tree_ops.init_min_tree(c),
            pos=jnp.int32(0),
            f_epoch=jnp.int32(0),
            size=jnp.int32(0),
            max_priority=jnp.float32(1.0),
        )

    # -- mutation (pure) ---------------------------------------------------

    @jax.named_scope("ingest")
    def add(self, state: SequenceFramePoolState, chunk: dict,
            priorities: jax.Array) -> SequenceFramePoolState:
        """Ingest one self-contained pooled sequence message.

        ``chunk`` keys: ``frames`` u8[Kf, D], ``n_frames`` i32, ``n_seqs``
        i32, ``obs_ref`` i32[G, T] (message-relative), ``action`` i32[G, T],
        ``reward``/``discount``/``mask`` f32[G, T], ``state_c``/``state_h``
        f32[G, H].  ``priorities`` f32[G].  Pad frame rows are all-zero
        and redirect onto row 0 (the message's shared zero frame); pad
        sequences repeat the last real sequence — both duplicate-write
        safe (module docstring).
        """
        kf = chunk["frames"].shape[0]
        g = priorities.shape[0]
        f, c, t = self.f_capacity, self.capacity, self.t_total
        if kf > f:
            raise ValueError(
                f"message carries {kf} frame rows > frame_capacity={f}")
        if g > c:
            raise ValueError(
                f"message carries {g} sequences > capacity={c}")
        if chunk["frames"].shape[1] != self.frame_dim:
            raise ValueError(
                f"message frame_dim {chunk['frames'].shape[1]} != spec "
                f"frame_dim {self.frame_dim}")
        if tuple(chunk["obs_ref"].shape) != (g, t):
            raise ValueError(
                f"message obs_ref shape {tuple(chunk['obs_ref'].shape)} "
                f"!= ({g}, {t})")

        fpos = state.f_epoch % f
        # pad rows (>= n_frames) are ALL-ZERO by the message contract and
        # redirect onto row 0 — the message's shared zero frame — so the
        # duplicate writes carry identical (zero) values and clobber
        # nothing (cf. FramePoolReplay's repeat-last-row variant)
        ar = jnp.arange(kf, dtype=jnp.int32)
        frow = jnp.where(ar < chunk["n_frames"], ar, 0)
        fidx = (fpos + frow) % f
        rows = chunk["frames"]
        if len(self.ring_shape) == 3:            # tile-align (ring_shape)
            rows = jnp.pad(rows, ((0, 0), (0, self.row_dim - self.frame_dim)))
            rows = rows.reshape(kf, 8, self.row_dim // 8)
        frames = state.frames.at[fidx].set(rows)

        srow = jnp.minimum(jnp.arange(g, dtype=jnp.int32),
                           chunk["n_seqs"] - 1)
        tidx = (state.pos + srow) % c
        obs_ids = (fpos + chunk["obs_ref"]) % f

        p_alpha = self._to_tree_priority(priorities)
        sum_tree, min_tree = tree_ops.update_both(
            state.sum_tree, state.min_tree, tidx, p_alpha)

        return state.replace(
            frames=frames,
            action=state.action.at[tidx].set(
                chunk["action"].astype(jnp.int32)),
            reward=state.reward.at[tidx].set(
                chunk["reward"].astype(jnp.float32)),
            discount=state.discount.at[tidx].set(
                chunk["discount"].astype(jnp.float32)),
            mask=state.mask.at[tidx].set(chunk["mask"].astype(jnp.float32)),
            state_c=state.state_c.at[tidx].set(
                chunk["state_c"].astype(jnp.float32)),
            state_h=state.state_h.at[tidx].set(
                chunk["state_h"].astype(jnp.float32)),
            obs_ids=state.obs_ids.at[tidx].set(obs_ids),
            frame_epoch=state.frame_epoch.at[tidx].set(state.f_epoch),
            sum_tree=sum_tree, min_tree=min_tree,
            pos=(state.pos + chunk["n_seqs"]) % c,
            f_epoch=state.f_epoch + chunk["n_frames"],
            size=jnp.minimum(state.size + chunk["n_seqs"], c),
            max_priority=jnp.maximum(state.max_priority, priorities.max()),
        )

    # update_priorities / is_weights / _to_tree_priority: PERMethods.

    # -- sampling ----------------------------------------------------------

    def sample(self, state: SequenceFramePoolState, key: jax.Array,
               batch_size: int, beta: float | jax.Array,
               axis_name: str | None = None):
        """Stratified PER sample; returns ``(batch, weights, idx)`` with
        the SAME batch schema as the stacked sequence layout — ``obs``
        gathered ``[B, T, *frame_shape]`` from the ring."""
        with jax.named_scope("sample"):
            idx = tree_ops.stratified_sample(state.sum_tree, key, batch_size,
                                             state.size)
            age = state.f_epoch - state.frame_epoch[idx]
            newest = (state.pos - 1) % self.capacity
            idx = jnp.where(age <= self.f_capacity, idx, newest)
        with jax.named_scope("gather"):
            batch = dict(
                obs=self._gather_sequences(state, state.obs_ids[idx]),
                action=state.action[idx],
                reward=state.reward[idx],
                discount=state.discount[idx],
                mask=state.mask[idx],
                state_c=state.state_c[idx],
                state_h=state.state_h[idx],
            )
        with jax.named_scope("sample"):
            weights = self.is_weights(state, idx, beta, axis_name=axis_name)
        return batch, weights, idx

    def _gather_sequences(self, state: SequenceFramePoolState,
                          ids: jax.Array) -> jax.Array:
        """(B, T) frame-ring rows -> (B, T, *frame_shape), step order
        preserved (no channel stacking — single frames, the LSTM is the
        memory)."""
        from apex_tpu.ops.gather import gather_rows
        b, t = ids.shape
        rows = gather_rows(state.frames, ids.reshape(-1),
                           mode=self.gather_mode)       # (B*T, row_dim)
        rows = rows[:, :self.frame_dim]                 # drop tile padding
        return rows.reshape(b, t, *self.frame_shape)
