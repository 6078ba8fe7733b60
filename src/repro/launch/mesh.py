"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module does not touch jax device state.  The dry-run process
sets XLA_FLAGS --xla_force_host_platform_device_count=512 before any jax
import; everything else (smoke tests, benches) sees the real single device.
"""
from __future__ import annotations

import dataclasses
import os

import jax
from jax.sharding import AxisType, Mesh

#: Recipe for getting C virtual devices on a CPU host (must be set before
#: the first jax import; see README "Scaling across clusters").
HOST_DEVICE_RECIPE = (
    "XLA_FLAGS=--xla_force_host_platform_device_count=8")


@dataclasses.dataclass(frozen=True)
class ClusterMesh:
    """The serving engine's device mesh: C PMCA clusters x H head shards.

    HERO §2.1: the PMCA scales by adding clusters behind one SVM fabric.
    The serving adaptation maps each cluster to a data-parallel lane group
    with its own KV page shard, and splits attention heads (GQA-aware)
    tensor-parallel inside a cluster over the ``head`` axis.  Axis names
    are fixed: ``("cluster", "head")``.
    """

    mesh: Mesh
    clusters: int
    heads: int

    AXIS_NAMES = ("cluster", "head")

    @property
    def devices(self) -> int:
        return self.clusters * self.heads


def make_serving_mesh(clusters: int = 1, heads: int = 1) -> ClusterMesh:
    """Build the ``("cluster", "head")`` serving mesh.

    Works on CPU via forced virtual devices::

        XLA_FLAGS=--xla_force_host_platform_device_count=8 python ...
    """
    n = len(jax.devices())
    if clusters * heads > n:
        raise ValueError(
            f"mesh {clusters}x{heads} needs {clusters * heads} devices, "
            f"only {n} visible (on CPU, relaunch with {HOST_DEVICE_RECIPE}; "
            f"XLA_FLAGS now: {os.environ.get('XLA_FLAGS', '<unset>')!r})")
    # Auto axes: the engine's eager per-lane updates of cluster-sharded
    # state need no out_sharding (jax.make_mesh defaults to Explicit)
    mesh = jax.make_mesh((clusters, heads), ClusterMesh.AXIS_NAMES,
                         axis_types=(AxisType.Auto,) * 2)
    return ClusterMesh(mesh=mesh, clusters=clusters, heads=heads)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many (possibly virtual) devices exist."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants (the roofline target)
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_LINK_BW = 50e9             # bytes/s per link
