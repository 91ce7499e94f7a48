"""Frame-pool prioritized replay: stacks reconstructed on device at sample time.

The memory problem this solves: storing stacked observations materializes
every 84x84 frame ``2 * frame_stack`` times (obs + next_obs of neighboring
transitions share stack-1 frames).  The reference dedups with host-side
LazyFrames (``wrapper.py:218-252``) and still needs a 128GB replay host for
2e6 transitions.  On TPU the replay lives in HBM (16GB/chip), so the dedup
must move into the storage layout itself:

* a frame ring ``u8[F, D]`` stores every frame ONCE, flattened to D bytes so
  XLA's (8,128) tiling pads <2% instead of padding 84 -> 128;
* transitions store ``int32`` frame indices (``obs_ids``/``next_ids`` of
  shape ``[C, S]``); sampling gathers ``B*S`` rows and reassembles the
  NHWC stack (oldest first, matching :class:`apex_tpu.envs.wrappers.FrameStack`)
  inside the same fused XLA step.

Net: ~8x more capacity per chip than stacked storage (one frame per step vs
2S frames per transition).

Ingest contract (chunks built by
:class:`apex_tpu.replay.frame_chunks.FrameChunkBuilder`): every chunk is
SELF-CONTAINED — it ships all frames its transitions reference, with
chunk-relative refs in ``[0, Kf)``.  Chunks from many actors can interleave
freely.  Fixed shapes with variable fill: a chunk carries ``n_frames <=
Kf`` real frames and ``n_trans <= K`` real transitions (``n_trans >= 1``,
``n_frames >= 1`` — the builder never ships empty chunks), and the ring
cursors advance by the REAL counts.  Pad rows (which the builder fills by
REPEATING the last real row, priorities included) are written to the SAME
ring slot as that last real row: a scatter with duplicate indices all
carrying identical values is deterministic, so padding writes nothing new
and can never clobber older live entries.

Liveness: a transition's frames can be overwritten before the transition
itself when frames arrive faster than ~(frame_capacity/capacity) per
transition — e.g. bursts of length-1 episodes plus chunk-boundary carry.
Rather than relying on a static sizing invariant, staleness is DETECTED at
sample time: each transition records the frame-cursor epoch of its chunk,
and sampled transitions whose epoch has fallen out of the frame ring's
horizon are redirected to the newest (always-valid) slot.  All redirected
rows share that slot's data, so their TD errors — and the duplicate
priority write-back — are identical, keeping the tree deterministic.  With
the default ``frame_capacity = 2 * capacity`` redirection is a measure-zero
event for normal workloads; it is a graceful degradation, never silent
corruption.
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
class FramePoolState:
    """Donated-buffer state of one frame-pool shard."""

    frames: jax.Array       # u8[F, D] — flattened frame ring
    extras: dict            # f32[C, ...] per-transition sidecars (extra_spec)
    action: jax.Array       # i32[C]
    reward: jax.Array       # f32[C] — pre-accumulated n-step return
    discount: jax.Array     # f32[C] — bootstrap coefficient (0 at terminal)
    obs_ids: jax.Array      # i32[C, S] — frame-ring rows, oldest first
    next_ids: jax.Array     # i32[C, S]
    frame_epoch: jax.Array  # i32[C] — frame-cursor epoch at ingest (for
                            #   staleness detection; i32 wraparound-safe
                            #   because only differences < 2^31 matter)
    sum_tree: jax.Array     # f32[2C]
    min_tree: jax.Array     # f32[2C]
    pos: jax.Array          # i32 — next transition write index
    f_epoch: jax.Array      # i32 — total frames ever written (frame cursor
                            #   is f_epoch % F)
    size: jax.Array         # i32 — live transition count
    max_priority: jax.Array  # f32


@dataclass(frozen=True)
class FramePoolReplay(PERMethods):
    """Static spec + pure methods (hashable; closes over jits).

    ``frame_shape`` is one frame's shape — (H, W, c) for pixels, (D,) for
    vector observations (``frame_stack=1`` stores plain vectors; >1
    concatenates on the last axis like pixel channel stacking).  Sampled
    observations are ``(B, *frame_shape[:-1], S * frame_shape[-1])`` in
    ``frame_dtype``, oldest frame first on the last axis.
    """

    capacity: int
    frame_shape: tuple[int, ...] = (84, 84, 1)
    frame_stack: int = 4
    frame_capacity: int | None = None
    frame_dtype: str = "uint8"
    alpha: float = 0.6
    eps: float = 1e-6
    # Per-transition float32 sidecar arrays: ((name, trailing_shape), ...).
    # Stored [C, *shape], written from chunk["extras"][name] [K, *shape],
    # returned as top-level batch keys at sample time.  The AQL family
    # stores its candidate set here (a_mu [T, a_dim]) so pixel AQL gets
    # frame dedup instead of 8x stacked storage.
    extra_spec: tuple[tuple[str, tuple[int, ...]], ...] = ()
    # Frame-row gather backend.  "auto" = jnp.take everywhere, with the
    # pallas scalar-prefetch kernel reachable only via the
    # APEX_GATHER_MODE=pallas opt-in (eligibility-gated per operand);
    # "pallas" forces the kernel — see ops/gather.py:resolved_mode for
    # why the kernel stays opt-in until a chip trace says it wins.
    gather_mode: str = "auto"

    def __post_init__(self):
        tree_ops._check_capacity(self.capacity)
        tree_ops._check_capacity(self.f_capacity)
        if self.f_capacity < self.frame_stack:
            raise ValueError(
                f"frame_capacity={self.f_capacity} cannot hold one "
                f"{self.frame_stack}-frame stack")
        reserved = {"obs", "action", "reward", "next_obs", "discount"}
        for name, _ in self.extra_spec:
            if name in reserved:
                raise ValueError(f"extra_spec name {name!r} collides with "
                                 f"a builtin batch key")

    def hbm_bytes(self) -> int:
        """Estimated HBM footprint of one shard's :class:`FramePoolState` —
        drivers validate this against the chip budget BEFORE allocating so a
        mis-sized config fails with an actionable error instead of an opaque
        XLA OOM."""
        c, s = self.capacity, self.frame_stack
        frame_bytes = (self.f_capacity * self.row_dim
                       * jnp.dtype(self.frame_dtype).itemsize)
        # action/reward/discount/frame_epoch i32|f32 + 2 id tables + 2 trees
        per_trans = 4 * 4 + 2 * 4 * s
        per_trans += sum(4 * math.prod(shape)
                         for _, shape in self.extra_spec)
        tree_bytes = 2 * (2 * c) * 4
        return frame_bytes + c * per_trans + tree_bytes

    @property
    def f_capacity(self) -> int:
        return (self.frame_capacity if self.frame_capacity is not None
                else 2 * self.capacity)

    @property
    def frame_dim(self) -> int:
        return math.prod(self.frame_shape)

    @property
    def row_dim(self) -> int:
        """Stored row width: pixel rows pad up to whole (8, 128) tiles so
        the pallas gather kernel can DMA single rows (ops/gather.py module
        docstring); 84x84 pads 7056 -> 7168 (+1.6%).  Small vector rows
        stay unpadded — they take the XLA gather path."""
        from apex_tpu.ops.gather import ROW_UNIT, pallas_eligible
        d = self.frame_dim
        padded = -(-d // ROW_UNIT) * ROW_UNIT
        if d >= ROW_UNIT // 2 and pallas_eligible(padded, self.frame_dtype):
            return padded
        return d

    @property
    def ring_shape(self) -> tuple[int, ...]:
        """Kernel-eligible rings are STORED in the tiled 3-D view
        ``(F, 8, row_dim/8)``: handing the kernel a pre-shaped operand is
        what keeps the pallas call zero-copy (reshaping inside the fused
        jit step would materialize the whole ring per step).  Eligibility —
        not "was padding needed" — decides the view, so exact-fit rows
        (frame_dim already a ROW_UNIT multiple) take the kernel path too."""
        from apex_tpu.ops.gather import pallas_eligible
        if pallas_eligible(self.row_dim, self.frame_dtype):
            return (self.f_capacity, 8, self.row_dim // 8)
        return (self.f_capacity, self.row_dim)

    # -- construction ------------------------------------------------------

    def init(self, example_item=None) -> FramePoolState:
        """``example_item`` is accepted and ignored for interface parity
        with :meth:`DeviceReplay.init` (shapes come from the spec)."""
        c, s = self.capacity, self.frame_stack
        return FramePoolState(
            frames=jnp.zeros(self.ring_shape, jnp.dtype(self.frame_dtype)),
            extras={name: jnp.zeros((c,) + tuple(shape), jnp.float32)
                    for name, shape in self.extra_spec},
            action=jnp.zeros(c, jnp.int32),
            reward=jnp.zeros(c, jnp.float32),
            discount=jnp.zeros(c, jnp.float32),
            obs_ids=jnp.zeros((c, s), jnp.int32),
            next_ids=jnp.zeros((c, s), jnp.int32),
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
    def add(self, state: FramePoolState, chunk: dict,
            priorities: jax.Array, valid=None) -> FramePoolState:
        """Ingest one self-contained chunk (see module docstring).

        ``chunk`` keys: ``frames`` u8[Kf, D], ``n_frames`` i32, ``n_trans``
        i32, ``action``/``reward``/``discount`` [K], ``obs_ref``/``next_ref``
        i32[K, S] (chunk-relative).  ``priorities`` f32[K].

        Pad rows (>= n_frames / n_trans, repeats of the last real row) are
        redirected onto the last real row's slot — identical duplicate
        writes, so nothing old is clobbered.

        Optional ``epoch_off`` i32[K]: per-transition offset added to the
        recorded frame epoch.  Merged payloads
        (:func:`apex_tpu.training.ingest_pipeline.merge_chunk_messages`)
        carry the cumulative frame offset of each transition's source
        chunk here, so one merged ingest records the SAME per-transition
        epochs a sequential chunk-by-chunk ingest would — bit-identical
        staleness detection, pinned in tests/test_ingest_pipeline.py.

        ``valid`` (scalar bool, traced) masks the WHOLE ingest: False
        leaves every field of ``state`` bit-identical, True is
        bit-identical to the unmasked call (both pinned in
        tests/test_ondevice_replay.py).  The fused on-device loop
        (:mod:`apex_tpu.ondevice.fused`) scans over a fixed chunk-slot
        grid whose unsealed slots carry garbage — this is how they
        ingest as no-ops inside one compiled program.  ``None`` (the
        host path) compiles exactly the historical program: no selects,
        no redirects.
        """
        kf = chunk["frames"].shape[0]
        k = priorities.shape[0]
        f, c = self.f_capacity, self.capacity
        # Shape validation runs at trace time (shapes are static under jit).
        # Oversized chunks would make the duplicate-write padding invariant
        # silently clobber live ring entries — reject them loudly instead.
        if kf > f:
            raise ValueError(
                f"chunk carries {kf} frame rows > frame_capacity={f}")
        if k > c:
            raise ValueError(
                f"chunk carries {k} transition rows > capacity={c}")
        if chunk["frames"].shape[1] != self.frame_dim:
            raise ValueError(
                f"chunk frame_dim {chunk['frames'].shape[1]} != spec "
                f"frame_dim {self.frame_dim}")
        for ref in ("obs_ref", "next_ref"):
            if tuple(chunk[ref].shape) != (k, self.frame_stack):
                raise ValueError(
                    f"chunk {ref} shape {tuple(chunk[ref].shape)} != "
                    f"({k}, {self.frame_stack})")
        epoch_off = chunk.get("epoch_off")
        if epoch_off is not None and tuple(epoch_off.shape) != (k,):
            raise ValueError(
                f"chunk epoch_off shape {tuple(epoch_off.shape)} != ({k},)")
        for name, shape in self.extra_spec:
            got = tuple(chunk["extras"][name].shape)
            if got != (k,) + tuple(shape):
                raise ValueError(
                    f"chunk extras[{name!r}] shape {got} != "
                    f"{(k,) + tuple(shape)}")
        fpos = state.f_epoch % f

        frow = jnp.minimum(jnp.arange(kf, dtype=jnp.int32),
                           chunk["n_frames"] - 1)
        fidx = (fpos + frow) % f
        rows = chunk["frames"]
        if len(self.ring_shape) == 3:            # tile-align (see ring_shape)
            rows = jnp.pad(rows, ((0, 0), (0, self.row_dim - self.frame_dim)))
            rows = rows.reshape(kf, 8, self.row_dim // 8)

        trow = jnp.minimum(jnp.arange(k, dtype=jnp.int32),
                           chunk["n_trans"] - 1)
        tidx = (state.pos + trow) % c
        obs_ids = (fpos + chunk["obs_ref"]) % f
        next_ids = (fpos + chunk["next_ref"]) % f

        p_alpha = self._to_tree_priority(priorities)
        if valid is None:
            frames = state.frames.at[fidx].set(rows)

            def tset(arr, vals):
                return arr.at[tidx].set(vals)

            sum_tree, min_tree = tree_ops.update_both(
                state.sum_tree, state.min_tree, tidx, p_alpha)
        else:
            # masked ingest: scatters redirect to an out-of-range row and
            # DROP; the trees instead re-write their CURRENT leaf values
            # (propagation recomputes identical reductions — a bit-exact
            # no-op), because a dropped leaf write would still recompute
            # ancestors from an out-of-bounds child gather
            frames = state.frames.at[
                jnp.where(valid, fidx, f)].set(rows, mode="drop")
            tdrop = jnp.where(valid, tidx, c)

            def tset(arr, vals):
                return arr.at[tdrop].set(vals, mode="drop")

            sum_tree = tree_ops.update_sum(
                state.sum_tree, tidx,
                jnp.where(valid, p_alpha,
                          tree_ops.get_leaves(state.sum_tree, tidx)))
            min_tree = tree_ops.update_min(
                state.min_tree, tidx,
                jnp.where(valid, p_alpha,
                          tree_ops.get_leaves(state.min_tree, tidx)))

        epoch = state.f_epoch
        if epoch_off is not None:
            epoch = epoch + epoch_off.astype(jnp.int32)

        def scalar(new, old):
            return new if valid is None else jnp.where(valid, new, old)

        return state.replace(
            frames=frames,
            extras={name: tset(state.extras[name],
                               chunk["extras"][name].astype(jnp.float32))
                    for name, _ in self.extra_spec},
            action=tset(state.action, chunk["action"].astype(jnp.int32)),
            reward=tset(state.reward, chunk["reward"].astype(jnp.float32)),
            discount=tset(state.discount,
                          chunk["discount"].astype(jnp.float32)),
            obs_ids=tset(state.obs_ids, obs_ids),
            next_ids=tset(state.next_ids, next_ids),
            frame_epoch=tset(state.frame_epoch,
                             jnp.broadcast_to(epoch, (k,))),
            sum_tree=sum_tree, min_tree=min_tree,
            pos=scalar((state.pos + chunk["n_trans"]) % c, state.pos),
            f_epoch=scalar(state.f_epoch + chunk["n_frames"],
                           state.f_epoch),
            size=scalar(jnp.minimum(state.size + chunk["n_trans"], c),
                        state.size),
            max_priority=scalar(
                jnp.maximum(state.max_priority, priorities.max()),
                state.max_priority),
        )

    # update_priorities / is_weights / _to_tree_priority: PERMethods.

    # -- sampling ----------------------------------------------------------

    def sample(self, state: FramePoolState, key: jax.Array, batch_size: int,
               beta: float | jax.Array, axis_name: str | None = None):
        """Stratified PER sample; returns ``(batch, weights, idx)`` with
        stacks gathered from the frame ring.  ``axis_name``: globalize the
        IS-weight normalizers over a sharded mesh axis
        (:meth:`PERMethods.is_weights`).

        Staleness guard (module docstring): transitions whose chunk's frames
        have aged out of the ring are redirected to the newest slot.  i32
        wraparound in the epoch difference is safe for ages < 2^31.
        """
        with jax.named_scope("sample"):
            idx = tree_ops.stratified_sample(state.sum_tree, key, batch_size,
                                             state.size)
            age = state.f_epoch - state.frame_epoch[idx]
            newest = (state.pos - 1) % self.capacity
            idx = jnp.where(age <= self.f_capacity, idx, newest)
        with jax.named_scope("gather"):
            batch = dict(
                obs=self._gather_stacks(state, state.obs_ids[idx]),
                action=state.action[idx],
                reward=state.reward[idx],
                next_obs=self._gather_stacks(state, state.next_ids[idx]),
                discount=state.discount[idx],
                **{name: state.extras[name][idx]
                   for name, _ in self.extra_spec},
            )
        with jax.named_scope("sample"):
            weights = self.is_weights(state, idx, beta, axis_name=axis_name)
        return batch, weights, idx

    def _gather_stacks(self, state: FramePoolState,
                       ids: jax.Array) -> jax.Array:
        """(B, S) frame-ring rows -> (B, *shape[:-1], S*shape[-1]),
        oldest frame first on the last axis."""
        from apex_tpu.ops.gather import gather_rows
        b, s = ids.shape
        shape = self.frame_shape
        rows = gather_rows(state.frames, ids.reshape(-1),
                           mode=self.gather_mode)       # (B*S, row_dim)
        rows = rows[:, :self.frame_dim]                 # drop tile padding
        rows = rows.reshape(b, s, *shape)
        rows = jnp.moveaxis(rows, 1, -2)                # stack before channel
        return rows.reshape(b, *shape[:-1], s * shape[-1])

    # -- helpers -----------------------------------------------------------

    def _to_tree_priority(self, priorities: jax.Array) -> jax.Array:
        p = jnp.maximum(priorities.astype(jnp.float32), self.eps)
        return p ** self.alpha
