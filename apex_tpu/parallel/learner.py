"""Multi-chip learner: sharded replay + psum-grad training in one program.

Design (BASELINE.json north star; SURVEY.md §7 step 5):

* The replay buffer is SHARDED across the ``dp`` axis — every chip owns an
  independent ring + sum/min trees in its own HBM.  Ingest chunks are split
  across chips; each chip samples ``batch/dp`` locally (its own stratified
  descent, no cross-chip tree walk); gradients are ``pmean``-ed over ICI;
  priority write-back is local.  This dissolves the reference's central
  replay-server bottleneck (``origin_repo/README.md:11``) instead of
  re-implementing it: there is no global lock because there is no global
  tree.
* Params/optimizer state are replicated; identical pmean'd updates keep them
  bit-identical per chip (standard DP invariant).
* Everything — ingest, sample, loss, all-reduce, update, priority write —
  is ONE ``shard_map``-ped, jitted program with donated buffers.

Sampling semantics note: each shard samples ``batch/dp`` from its OWN tree,
so a transition's true inclusion probability is ``leaf / (dp *
shard_total)`` — under heavy priority skew (one shard holding more mass
than the others) that deviates from the reference's global stratification;
round-robin chunk ingest spreads bursts evenly but cannot equalize
heavy-tailed leaf values.  The IS weights therefore correct for the sampler
ACTUALLY USED: local total/size (whose product equals the true effective
global probability times the global size) with one ``pmax``-collectived
max-weight normalizer so every shard scales identically — an unbiased
estimator regardless of how mass concentrates, reducing bit-for-bit to the
single-buffer formula when shards are balanced.  ``tests/test_parallel.py``
pins both properties under a x1000 priority burst.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.replay.device import ReplayState
from apex_tpu.training.learner import LearnerCore
from apex_tpu.training.state import TrainState


@dataclass(frozen=True)
class ShardedLearner:
    """Wraps a learner core with a dp-sharded execution plan.

    Works for any core with the :class:`LearnerCore` method shape
    (``replay``/``batch_size``/``update_from_batch``); cores whose update
    consumes a PRNG key (AQL's NoisyNet draws) set ``update_needs_key =
    True`` and the per-chip body splits its key between sampling and the
    update, mirroring ``AQLCore.train_step``."""

    core: LearnerCore
    mesh: Mesh

    @property
    def n_dp(self) -> int:
        return self.mesh.shape["dp"]

    @property
    def _needs_key(self) -> bool:
        return getattr(self.core, "update_needs_key", False)

    def _update(self, ts, batch, weights, key):
        if self._needs_key:
            return self.core.update_from_batch(ts, batch, weights, key,
                                               axis_name="dp")
        return self.core.update_from_batch(ts, batch, weights,
                                           axis_name="dp")

    # -- state construction ------------------------------------------------

    def init_replay(self, example_item: Any = None) -> ReplayState:
        """Per-chip replay shards, stacked on a sharded leading axis.

        Total capacity = ``core.replay.capacity * n_dp`` — capacity scales
        with the slice, which is exactly how HBM grows.

        The init runs as ONE jitted program whose outputs are declared
        ``P("dp")``, so every chip materializes only its own ``[1, ...]``
        slice: no ``[dp, ...]`` array and no single-shard copy ever sits
        on one device (at 2^19 transitions a shard is ~7.5 GB — a tiled
        copy on chip 0 would not fit a 16 GB chip).
        """
        n = self.n_dp

        def stacked():
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape),
                self.core.replay.init(example_item))

        return jax.jit(stacked, out_shardings=NamedSharding(
            self.mesh, P("dp")))()

    def replicate_train_state(self, ts: TrainState) -> TrainState:
        return jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(self.mesh, P())), ts)

    # -- the sharded fused step --------------------------------------------

    def _per_chip_batch(self) -> int:
        """batch/dp, validated loudly (a ``ValueError`` survives
        ``python -O`` where an assert would vanish into a silent
        shape mismatch inside the shard_map trace)."""
        per_chip, rem = divmod(self.core.batch_size, self.n_dp)
        if rem:
            raise ValueError(
                f"learner.batch_size={self.core.batch_size} must be "
                f"divisible by the dp axis (dp={self.n_dp}, from "
                f"learner.mesh_shape) — raise batch_size or shrink "
                f"the mesh")
        return per_chip

    def make_fused_step(self):
        core = self.core
        per_chip_batch = self._per_chip_batch()

        def per_chip(ts: TrainState, rs: ReplayState, ingest: Any,
                     prios: jax.Array, key: jax.Array, beta: jax.Array):
            # leading shard axis of size 1 inside shard_map -> strip it
            rs = jax.tree.map(lambda x: x[0], rs)
            ingest = jax.tree.map(lambda x: x[0], ingest)
            prios = prios[0]
            key = jax.random.wrap_key_data(key[0])

            if self._needs_key:
                key, k_update = jax.random.split(key)
            else:
                k_update = None
            rs = core.replay.add(rs, ingest, prios)
            batch, weights, idx = core.replay.sample(
                rs, key, per_chip_batch, beta, axis_name="dp")
            new_ts, priorities, metrics = self._update(
                ts, batch, weights, k_update)
            rs = core.replay.update_priorities(rs, idx, priorities)
            rs = jax.tree.map(lambda x: x[None], rs)    # restore shard axis
            return new_ts, rs, metrics

        shard = P("dp")
        repl = P()
        mapped = jax.shard_map(
            per_chip, mesh=self.mesh,
            in_specs=(repl, shard, shard, shard, shard, repl),
            out_specs=(repl, shard, repl),
            check_vma=False)
        return jax.jit(mapped, donate_argnums=(0, 1))

    def make_train_step(self):
        """Sample/update only (no ingest) — the learner's catch-up step when
        no chunk is pending."""
        core = self.core
        per_chip_batch = self._per_chip_batch()

        def per_chip(ts: TrainState, rs: ReplayState, key: jax.Array,
                     beta: jax.Array):
            rs = jax.tree.map(lambda x: x[0], rs)
            key = jax.random.wrap_key_data(key[0])
            if self._needs_key:
                key, k_update = jax.random.split(key)
            else:
                k_update = None
            batch, weights, idx = core.replay.sample(
                rs, key, per_chip_batch, beta, axis_name="dp")
            new_ts, priorities, metrics = self._update(
                ts, batch, weights, k_update)
            rs = core.replay.update_priorities(rs, idx, priorities)
            rs = jax.tree.map(lambda x: x[None], rs)
            return new_ts, rs, metrics

        mapped = jax.shard_map(
            per_chip, mesh=self.mesh,
            in_specs=(P(), P("dp"), P("dp"), P()),
            out_specs=(P(), P("dp"), P()),
            check_vma=False)
        return jax.jit(mapped, donate_argnums=(0, 1))

    def make_ingest(self):
        """Ingest only (pre-warmup): one chunk per chip, no training."""
        core = self.core

        def per_chip(rs: ReplayState, ingest: Any, prios: jax.Array):
            rs = jax.tree.map(lambda x: x[0], rs)
            ingest = jax.tree.map(lambda x: x[0], ingest)
            rs = core.replay.add(rs, ingest, prios[0])
            return jax.tree.map(lambda x: x[None], rs)

        mapped = jax.shard_map(
            per_chip, mesh=self.mesh,
            in_specs=(P("dp"), P("dp"), P("dp")),
            out_specs=P("dp"),
            check_vma=False)
        return jax.jit(mapped, donate_argnums=(0,))

    # -- host-side helpers -------------------------------------------------

    def split_ingest(self, batch: dict[str, jax.Array], prios: jax.Array):
        """Reshape a host chunk (K, ...) -> (dp, K/dp, ...) for sharded ingest.

        Round-robin interleave: consecutive transitions land on different
        chips, keeping shard statistics identical in distribution.
        """
        n = self.n_dp

        def split(x):
            k = x.shape[0]
            if k % n != 0:
                raise ValueError(
                    f"ingest chunk of {k} transitions must be divisible "
                    f"by the dp axis (dp={n}, from learner.mesh_shape) — "
                    f"align actor.send_interval / learner.ingest_chunk "
                    f"with the mesh")
            return x.reshape(k // n, n, *x.shape[1:]).swapaxes(0, 1)

        return ({k: split(v) for k, v in batch.items()}, split(prios))

    def shard_put(self, tree_obj: Any) -> Any:
        """Place a host tree whose leading axis is the dp shard axis into
        device memory, one shard slice per chip (NamedSharding over dp).
        The ingest pipeline's staging thread uses this so the sharded
        dispatch finds its operands already resident (H2D overlaps the
        previous step's compute)."""
        sharding = NamedSharding(self.mesh, P("dp"))
        return jax.tree.map(lambda x: jax.device_put(x, sharding), tree_obj)

    def device_keys(self, key: jax.Array) -> jax.Array:
        """One PRNG key per chip as raw key data (uint32), sharded over dp.

        Raw data rather than typed keys so the leading axis shards cleanly;
        the per-chip body re-wraps with ``wrap_key_data``.
        """
        keys = jax.random.key_data(jax.random.split(key, self.n_dp))
        return jax.device_put(keys, NamedSharding(self.mesh, P("dp")))
