"""Central mesh construction — ONE place that turns a topology into a
``jax.sharding.Mesh``.

Before this module, bench rigs, the engine CLI, the ring-attention
tests and the multinode configs each built meshes ad hoc (a
``np.array(jax.devices()[:n]).reshape(...)`` with hand-typed axis-name
tuples).  Each hand-typed ``("data", "model")`` is a chance for the
runtime and the sharding lint plane (``analysis/shardcheck.py``) to
disagree about what the mesh even is — and a renamed axis in a
PartitionSpec then *silently replicates* instead of sharding.  Every
mesh in the repo now comes from here, with the axis names imported
from ``obs/topology.py`` (the versioned hardware-constants table the
perf and shard planes already share):

- :func:`build_mesh` — a real device mesh, over ``jax.devices()`` by
  default (post-``multihost.bootstrap`` that is the GLOBAL device
  list, so the same call works single-host and multi-host).  Axis
  order follows ``jax.devices()`` ordering: one process's devices are
  contiguous, so the LAST axes land within a host — put
  ``AXIS_MODEL``/TP there (its collectives ride intra-host ICI) and
  let ``AXIS_DATA``/DP span hosts over DCN (the scaling-book layout).
- :func:`abstract_mesh` — the same topology as a
  ``jax.sharding.AbstractMesh``: axis *names and sizes* with no
  devices attached, what the lint planes use to reason about specs
  and trace ``shard_map`` bodies without owning hardware.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

from dynamo_tpu.obs.topology import (  # noqa: F401  (re-exported)
    AXIS_DATA,
    AXIS_MODEL,
    AXIS_SP,
    MESH_AXES,
)

__all__ = [
    "AXIS_DATA",
    "AXIS_MODEL",
    "AXIS_SP",
    "MESH_AXES",
    "abstract_mesh",
    "build_mesh",
]


def _shape(topology) -> tuple[int, ...]:
    if isinstance(topology, int):
        return (topology,)
    return tuple(int(n) for n in topology)


def build_mesh(topology, axes: Sequence[str] = MESH_AXES, *,
               devices: Optional[Sequence] = None):
    """Mesh of ``topology`` (an int or a tuple of per-axis sizes) over
    ``devices`` (default: the full ``jax.devices()`` list — global
    across hosts once ``multihost.bootstrap`` has run)."""
    import jax
    import numpy as np

    shape = _shape(topology)
    axes = tuple(axes)
    if len(shape) != len(axes):
        raise ValueError(
            f"mesh topology {shape} has {len(shape)} axes but "
            f"{len(axes)} names {axes}"
        )
    devs = list(devices) if devices is not None else jax.devices()
    need = math.prod(shape)
    if need > len(devs):
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} needs {need} devices, "
            f"have {len(devs)}"
        )
    return jax.sharding.Mesh(np.array(devs[:need]).reshape(shape), axes)


def abstract_mesh(topology, axes: Sequence[str] = MESH_AXES):
    """The same topology as an ``AbstractMesh`` (axis names + sizes, no
    devices): enough to prune/evaluate PartitionSpecs and trace
    shard_map bodies shape-only — what the sharding and perf lint
    planes use so auditing a 4-chip layout never requires 4 chips."""
    import jax

    shape = _shape(topology)
    axes = tuple(axes)
    if len(shape) != len(axes):
        raise ValueError(
            f"mesh topology {shape} has {len(shape)} axes but "
            f"{len(axes)} names {axes}"
        )
    return jax.sharding.AbstractMesh(shape, axes)
