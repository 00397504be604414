"""Device mesh helpers.

The TPU-native replacement for the reference's device enumeration/affinity layer
(ref ParallelWrapper.java:119-137 AffinityManager thread pinning): a jax.sharding.Mesh
over the chips of a slice (axes: data/model/pipeline/sequence), with ICI collectives
(psum/all-gather) taking the role of Nd4j.averageAndPropagate (ref SURVEY §2.6).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(num_devices: Optional[int] = None,
              axes: Tuple[str, ...] = ("data",),
              shape: Optional[Sequence[int]] = None) -> Mesh:
    """Build a Mesh over the first `num_devices` devices. With multiple axes, `shape`
    gives the per-axis sizes (product must equal device count)."""
    devices = jax.devices()
    n = num_devices or len(devices)
    if n > len(devices):
        raise ValueError(f"Requested {n} devices, have {len(devices)}")
    devs = np.array(devices[:n])
    if len(axes) == 1:
        return Mesh(devs, axes)
    if shape is None:
        raise ValueError("shape required for multi-axis mesh")
    if int(np.prod(shape)) != n:
        raise ValueError(f"mesh shape {shape} != device count {n}")
    return Mesh(devs.reshape(shape), axes)


def compat_shard_map(fn, mesh: Mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off (the repo idiom — the
    bodies use collectives whose replication the checker can't always
    prove)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def replica_submeshes(mesh: Mesh, inner_axis: Optional[str] = None
                      ) -> list:
    """Split a 2-axis mesh into one single-axis Mesh per leading-axis row.

    The serving replica groups (serving/sharding.py, ISSUE 10) build one
    `(replica, tensor)` mesh for the whole fleet and hand each data-parallel
    engine replica its own row as an independent `(tensor,)` mesh: the
    replicas never communicate (each owns its params, KV pool, and
    scheduler), so a shared mesh axis would only couple their dispatches.
    `inner_axis` defaults to the mesh's second axis name."""
    if len(mesh.axis_names) != 2:
        raise ValueError(f"expected a 2-axis mesh, got {mesh.axis_names}")
    if inner_axis is None:
        inner_axis = mesh.axis_names[1]
    return [Mesh(row, (inner_axis,)) for row in mesh.devices]


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh, axis: str = "data") -> NamedSharding:
    return NamedSharding(mesh, P(axis))


def replica_stacked(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Sharding for arrays with a leading per-replica axis (ParallelWrapper model zoo:
    one replica per device, ref DefaultTrainer replica-per-device design)."""
    return NamedSharding(mesh, P(axis))
