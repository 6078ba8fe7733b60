"""Fig.4 reproduction: matrix-matrix multiplication parallelized over
clusters (1/2/4/6/8), bus vs NoC interconnect.

Two measurements:
  1. real multi-(virtual-)device run: shard_map row-tiled matmul over a
     'cluster' mesh axis, wall-clock per iteration (run in a subprocess with
     8 host devices so the rest of the suite keeps seeing 1 device);
  2. the analytic interconnect model (core/cluster.py) reproducing the
     paper's observation: ideal speedup at 2/4/6 clusters, ~2% below ideal
     at 8 on the bus, recovered by the NoC.

Also reports the §1 nominal-GIPS throughput scaling (--throughput).
"""
from __future__ import annotations

import json
import subprocess
import sys
import os

from repro.core.cluster import ClusterConfig, interconnect_model

SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time, json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

M = N = K = 1024
a = jnp.asarray(np.random.default_rng(0).standard_normal((M, K), np.float32))
b = jnp.asarray(np.random.default_rng(1).standard_normal((K, N), np.float32))
out = {}
for n in [1, 2, 4, 8]:
    mesh = Mesh(np.array(jax.devices()[:n]), ("cluster",))
    f = jax.jit(jax.shard_map(lambda at, bt: at @ bt, mesh=mesh,
                              in_specs=(P("cluster", None), P(None, None)),
                              out_specs=P("cluster", None)))
    r = f(a, b); r.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        r = f(a, b)
    r.block_until_ready()
    out[n] = (time.perf_counter() - t0) / 10
print(json.dumps(out))
"""


def measured_speedups():
    r = subprocess.run([sys.executable, "-c", SUBPROC], capture_output=True,
                       text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"})
    times = json.loads(r.stdout.strip().splitlines()[-1])
    base = times["1"]
    return {int(k): base / v for k, v in times.items()}


def modeled_speedups():
    rows = []
    # per-cluster work for an n-cluster row-tiled 2048^3 matmul
    total_compute_s = 1.0
    total_bytes = 512 * 2 ** 20
    for ic in ("bus", "noc"):
        for n in (1, 2, 4, 6, 8):
            cfg = ClusterConfig(n_clusters=n, interconnect=ic)
            m = interconnect_model(cfg, total_bytes // max(n, 1),
                                   total_compute_s / max(n, 1))
            rows.append(m)
    return rows


def main(throughput: bool = False):
    print("# Fig.4: cluster-parallel matmul speedup")
    print("## analytic interconnect model (bus vs NoC)")
    print("interconnect,n_clusters,speedup,ideal,efficiency")
    for m in modeled_speedups():
        print(f"{m['interconnect']},{m['n_clusters']},{m['speedup']:.3f},"
              f"{m['ideal']},{m['efficiency']:.4f}")
    print("## measured (8 virtual devices, shard_map row tiling)")
    try:
        sp = measured_speedups()
        for n, s in sorted(sp.items()):
            print(f"measured,{n},{s:.3f}")
    except Exception as e:  # single-core container: contention expected
        print(f"measured,unavailable,{e}")
    if throughput:
        print("## nominal GIPS (paper §1: 64 PEs @ >30 MHz -> >1.9 GIPS)")
        for n in (1, 2, 4, 8):
            cfg = ClusterConfig(n_clusters=n)
            print(f"gips,{cfg.total_pes},{cfg.nominal_gips():.2f}")


if __name__ == "__main__":
    main(throughput="--throughput" in sys.argv)
