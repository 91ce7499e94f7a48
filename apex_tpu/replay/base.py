"""PER math shared by every replay layout.

Both :class:`apex_tpu.replay.device.DeviceReplay` (stacked storage) and
:class:`apex_tpu.replay.frame_pool.FramePoolReplay` (frame-pool storage)
keep identical ``sum_tree``/``min_tree``/``size``/``max_priority`` fields in
their state; the priority-update and importance-weight math over those
fields lives here once so the two layouts cannot diverge semantically
(reference: ``memory.py:252-320``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.ops import tree as tree_ops


class PERMethods:
    """Mixin over frozen replay specs with ``alpha``/``eps`` fields and
    states carrying ``sum_tree``/``min_tree``/``size``/``max_priority``."""

    @jax.named_scope("writeback")
    def update_priorities(self, state, idx: jax.Array,
                          priorities: jax.Array):
        """Store ``priority ** alpha`` and track the running max
        (``memory.py:300-320``).  Duplicate ``idx`` entries must carry equal
        values (they do on every call path: duplicates share batch rows).
        Traced under the scope ``writeback`` (one of the step program's
        five, see :func:`apex_tpu.training.learner.td_update`)."""
        p_alpha = self._to_tree_priority(priorities)
        sum_tree, min_tree = tree_ops.update_both(
            state.sum_tree, state.min_tree, idx, p_alpha)
        return state.replace(
            sum_tree=sum_tree, min_tree=min_tree,
            max_priority=jnp.maximum(state.max_priority, priorities.max()))

    def is_weights(self, state, idx: jax.Array,
                   beta: float | jax.Array,
                   axis_name: str | None = None) -> jax.Array:
        """IS weights normalized by the max weight from the min-priority
        leaf (``memory.py:252-298``).

        ``axis_name``: inside a ``shard_map`` over a dp-sharded replay.
        Each shard samples from its OWN tree, so a transition's true
        inclusion probability is ``leaf / (n_shards * shard_total)`` — the
        LOCAL total and LOCAL size reproduce exactly that
        (``local_p * local_size == global_p_eff * global_size``), making
        the bias correction unbiased for the sampler actually used even
        when priority mass concentrates unevenly across shards (a pure
        psum'd-total formula would assume a global sampler that doesn't
        exist).  Only the max-weight NORMALIZER is collectived (one scalar
        ``pmax`` over ICI) so every shard scales its loss terms
        identically; with balanced shards this reduces bit-for-bit to the
        reference's single-buffer formula (``tests/test_parallel.py``)."""
        total = tree_ops.tree_total(state.sum_tree)
        size = state.size.astype(jnp.float32)
        p_min = tree_ops.tree_min(state.min_tree) / total
        max_weight = (p_min * size) ** (-beta)
        if axis_name is not None:
            max_weight = jax.lax.pmax(max_weight, axis_name)
        p_sample = tree_ops.get_leaves(state.sum_tree, idx) / total
        return ((p_sample * size) ** (-beta) / max_weight).astype(jnp.float32)

    def _to_tree_priority(self, priorities: jax.Array) -> jax.Array:
        p = jnp.maximum(priorities.astype(jnp.float32), self.eps)
        return p ** self.alpha


def check_hbm_budget(estimated_bytes: int, budget_gb: float,
                     what: str, capacity: int) -> None:
    """Refuse to allocate a replay shard over the chip budget — an
    actionable error instead of an opaque XLA OOM mid-run.  Every driver
    construction path calls this before ``init``."""
    budget = int(budget_gb * 2 ** 30)
    if estimated_bytes > budget:
        raise ValueError(
            f"{what} would need ~{estimated_bytes / 2**30:.1f} GiB HBM, "
            f"over the {budget_gb:.1f} GiB budget (replay.hbm_budget_gb). "
            f"Shrink replay.capacity (currently {capacity}) or raise the "
            f"budget; multi-chip slices scale total capacity by the dp "
            f"degree, so per-chip capacity stays modest.")
