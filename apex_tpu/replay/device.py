"""HBM-resident prioritized replay.

TPU re-design of the reference's replay stack (``memory.py:146-391``): instead
of a Python list of pickled tuples guarded by one asyncio lock — the
reference's acknowledged system-wide bottleneck (``origin_repo/README.md:11``,
``replay.py:92-93,141-143``) — the buffer is a struct-of-arrays pytree of
preallocated device arrays plus flat sum/min trees (:mod:`apex_tpu.ops.tree`).
Every operation (add-with-priority, stratified sample + IS weights, priority
update) is a pure function of ``ReplayState`` and traces into the learner's
single fused XLA step; concurrency is resolved by program order inside the
compiled step rather than locks.

Semantic parity:

* ``add`` takes caller-computed priorities, merging add+update exactly like
  ``CustomPrioritizedReplayBuffer.add`` (``memory.py:334-346``); ring-buffer
  positioning matches ``ReplayBuffer.add`` (``memory.py:162-169``).
* ``sample`` reproduces proportional stratified sampling with importance
  weights normalized by the max weight derived from the min-priority leaf
  (``memory.py:252-298``).
* ``update_priorities`` stores ``priority ** alpha`` and tracks the running
  max priority (``memory.py:300-320``).

Observations should be stored ``uint8`` and scaled inside the model — HBM
bandwidth is the bottleneck resource, and uint8 keeps both the ring and the
sampled batch 4x smaller than f32.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from flax import struct

from apex_tpu.ops import tree as tree_ops
from apex_tpu.replay.base import PERMethods


@struct.dataclass
class ReplayState:
    """Donated-buffer state of one replay shard."""

    storage: Any                # pytree of (capacity, ...) arrays
    sum_tree: jax.Array         # (2*capacity,) f32
    min_tree: jax.Array         # (2*capacity,) f32
    pos: jax.Array              # i32 scalar — next write index
    size: jax.Array             # i32 scalar — current element count
    max_priority: jax.Array     # f32 scalar — reference memory.py:233


@dataclass(frozen=True)
class DeviceReplay(PERMethods):
    """Static spec + pure methods.  Hashable, so it can close over jits."""

    capacity: int
    alpha: float = 0.6
    eps: float = 1e-6

    def __post_init__(self):
        tree_ops._check_capacity(self.capacity)

    # -- construction ------------------------------------------------------

    def hbm_bytes(self, example_item: Any) -> int:
        """Estimated HBM footprint of one shard's :class:`ReplayState` for
        this item pytree (drivers check vs the chip budget pre-alloc)."""
        import numpy as np
        per_item = sum(
            int(np.prod(jnp.shape(x))) * np.dtype(x.dtype).itemsize
            for x in jax.tree.leaves(example_item))
        tree_bytes = 2 * (2 * self.capacity) * 4
        return self.capacity * per_item + tree_bytes

    def init(self, example_item: Any) -> ReplayState:
        """Allocate zeroed storage shaped like one transition pytree."""
        storage = jax.tree.map(
            lambda x: jnp.zeros((self.capacity,) + jnp.shape(x),
                                dtype=jnp.asarray(x).dtype),
            example_item)
        return ReplayState(
            storage=storage,
            sum_tree=tree_ops.init_sum_tree(self.capacity),
            min_tree=tree_ops.init_min_tree(self.capacity),
            pos=jnp.int32(0),
            size=jnp.int32(0),
            max_priority=jnp.float32(1.0),
        )

    # -- mutation (pure) ---------------------------------------------------

    @jax.named_scope("ingest")
    def add(self, state: ReplayState, batch: Any,
            priorities: jax.Array) -> ReplayState:
        """Fused ring-write + priority set for K transitions."""
        k = priorities.shape[0]
        idx = (state.pos + jnp.arange(k, dtype=jnp.int32)) % self.capacity
        storage = jax.tree.map(lambda s, b: s.at[idx].set(b.astype(s.dtype)),
                               state.storage, batch)
        p_alpha = self._to_tree_priority(priorities)
        sum_tree, min_tree = tree_ops.update_both(
            state.sum_tree, state.min_tree, idx, p_alpha)
        return state.replace(
            storage=storage, sum_tree=sum_tree, min_tree=min_tree,
            pos=(state.pos + k) % self.capacity,
            size=jnp.minimum(state.size + k, self.capacity),
            max_priority=jnp.maximum(state.max_priority, priorities.max()),
        )

    def add_max_priority(self, state: ReplayState, batch: Any) -> ReplayState:
        """Insert at the running max priority (``memory.py:235-240``)."""
        k = jax.tree.leaves(batch)[0].shape[0]
        prios = jnp.full((k,), state.max_priority, dtype=jnp.float32)
        return self.add(state, batch, prios)

    # update_priorities / is_weights / _to_tree_priority: PERMethods.

    # -- sampling ----------------------------------------------------------

    def sample(self, state: ReplayState, key: jax.Array, batch_size: int,
               beta: float | jax.Array, axis_name: str | None = None):
        """Returns ``(batch, weights, idx)``; weights normalized by max
        weight (globally, via collectives, when ``axis_name`` names a
        sharded mesh axis — see :meth:`PERMethods.is_weights`)."""
        with jax.named_scope("sample"):
            idx = tree_ops.stratified_sample(state.sum_tree, key, batch_size,
                                             state.size)
        with jax.named_scope("gather"):
            batch = jax.tree.map(lambda s: s[idx], state.storage)
        with jax.named_scope("sample"):
            weights = self.is_weights(state, idx, beta, axis_name=axis_name)
        return batch, weights, idx
