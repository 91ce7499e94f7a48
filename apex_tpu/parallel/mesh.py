"""Device-mesh utilities.

The reference has NO gradient data-parallelism — its learner is a single GPU
(SURVEY.md §2 parallelism table; no torch.distributed anywhere in the tree).
Scaling the learner across a TPU slice is therefore a new capability, designed
the XLA way: one ``jax.sharding.Mesh``, shardings annotated per-array, and
collectives (``psum``/``pmean``) riding ICI inside the compiled step — the
role NCCL would have played in a scaled-out reference learner.

Axes: ``dp`` (data/replay parallel) is the only sized axis for these model
scales; ``tp`` exists in the API so tensor-parallel sharding rules can be
added without re-plumbing (kept size 1, see SURVEY.md §2).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(dp: int | None = None, tp: int = 1,
              devices: list | None = None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    dp = dp if dp is not None else len(devices) // tp
    if dp * tp > len(devices):
        raise ValueError(f"mesh {dp}x{tp} needs {dp*tp} devices, "
                         f"have {len(devices)}")
    arr = np.asarray(devices[:dp * tp]).reshape(dp, tp)
    return Mesh(arr, ("dp", "tp"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def dp_sharded(mesh: Mesh, axis: int = 0) -> NamedSharding:
    spec = [None] * (axis + 1)
    spec[axis] = "dp"
    return NamedSharding(mesh, P(*spec))
