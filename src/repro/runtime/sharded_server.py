"""Multi-cluster sharded paged serving engine (HERO §2.1 scaled out).

HERO's headline property is that the PMCA *scales*: throughput grows by
instantiating more RISC-V clusters behind one SVM/RAB fabric.  This module
is the serving-side reproduction of that scaling lever: the paged engine of
``runtime.server`` is sharded across a JAX device mesh of C "clusters"
(data-parallel lane groups) x H tensor-parallel head shards — the
``ClusterMesh`` with named axes ``("cluster", "head")``, which works on CPU
via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Mapping back to the paper:

* **per-cluster RAB + page shard** (§2.2) — every cluster owns a
  ``PagedKVPool`` slice of the fused KV slab with its own free list,
  refcounts, prefix index and ``RAB`` instance (``ClusterPagedPool``); a
  sequence lives entirely inside one cluster, so its block table holds
  cluster-local physical ids and the cluster id rides with the request;
* **cluster-aware admission** — placement is cache-affine least-loaded
  (largest prefix hit, then most obtainable pages); preemption stays
  cluster-local: a victim's pages swap out of *its* cluster's shard only;
* **one program, C clusters** (§3.2's shard_map discipline) — the jitted
  chunk/decode steps of ``runtime.server`` run unchanged as ``shard_map``
  bodies; lanes and their device-resident state (block tables, lengths,
  sampled tokens, per-lane sampling policy) shard over ``cluster``,
  attention heads GQA-aware over ``head`` (the only collective is one psum
  of the attention output per layer); with C = H = 1 the engine is
  token-for-token identical to the unsharded ``PagedServer`` — including
  sampled lanes, whose PRNG keys fold by (seed, position) and therefore
  never see the mesh;
* **tracing** (§2.3.1) — placement and the per-iteration cross-cluster
  token gather emit ``CLUSTER_DISPATCH`` / ``ALL_GATHER`` events, analyzed
  by ``core.analysis.layer2_cluster_balance``.

Configuration flows through the same :class:`~repro.runtime.EngineConfig`
as the unsharded engine (``clusters`` / ``heads`` / ``mesh`` select the
mesh; ``make_engine`` picks this class whenever the spec wants one).
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import List, Optional

import jax
from jax.sharding import NamedSharding
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.offload import DiskTier, HostBackingStore
from repro.core.rab import ClusterPagedPool, PagedKVPool, RABConfig
from repro.core.tracing import EventType, TraceBuffer
from repro.kernels.paged_attention.ops import validate_head_sharding
from repro.launch.mesh import make_serving_mesh
from repro.parallel.sharding import cluster_engine_specs
from repro.runtime.api import EngineConfig
from repro.runtime.server import (
    PagedServer, SeqState, _paged_chunk_step, _paged_decode_step,
    _paged_spec_step, named_step,
)

__all__ = ["ShardedPagedServer"]


class ShardedPagedServer(PagedServer):
    """``PagedServer`` sharded over a ``("cluster", "head")`` device mesh.

    ``EngineConfig.num_pages`` and ``EngineConfig.max_lanes`` are *per
    cluster* (so a 1-cluster sharded engine is configured exactly like the
    unsharded one); the fused device slab holds ``C * (num_pages + 1)``
    pages — each cluster's contiguous block ends with its own trash page —
    sharded over the ``cluster`` axis, kv heads over ``head``.
    """

    def __init__(self, cfg: ArchConfig, params,
                 engine: Optional[EngineConfig] = None, *,
                 tracer: Optional[TraceBuffer] = None):
        if engine is None:
            engine = EngineConfig()
        cmesh = engine.mesh if engine.mesh is not None else \
            make_serving_mesh(engine.clusters, engine.heads)
        self.cmesh = cmesh
        self.clusters = cmesh.clusters
        self.heads = cmesh.heads
        self.lanes_per_cluster = engine.max_lanes
        self._local_pages = engine.cache.num_pages
        validate_head_sharding(cfg.num_heads, cfg.num_kv_heads, cmesh.heads)
        super().__init__(
            cfg, params,
            dataclasses.replace(engine,
                                max_lanes=engine.max_lanes * cmesh.clusters),
            tracer=tracer)
        self.engine_cfg = engine        # the per-cluster spec, as given
        self.peak_pages = [0] * cmesh.clusters  # per-cluster occupancy peak
        self._fin_mark = 0
        self._parked_len: dict = {}     # rid -> seq_len across preemption

    # ------------------------------------------------------ construction --
    def _build_pool(self, num_pages: int, rab_cfg: RABConfig):
        # per-cluster pools/RABs instead of the base's single pool;
        # self.pool points at an aggregate view (stats/free_pages) for
        # external readers, never at an allocator
        self.cpool = ClusterPagedPool(self.clusters, num_pages,
                                      self.page_size, self.max_pages,
                                      rab_cfg, self.tracer)
        self.pool = self.cpool
        self.rabs = self.cpool.rabs
        self.rab = self.rabs[0]

    def _build_device_state(self, num_pages: int,
                            pages_per_step: Optional[int]):
        # the fused slab, re-laid-out: C contiguous (num_pages + 1) blocks
        # (trash page per cluster), pages sharded over `cluster`, kv heads
        # over `head`; lane state (incl. the sampling-policy rows) shards
        # its batch dim over `cluster`.  Every array is created in its
        # sharding: no device ever holds the whole slab.
        cfg, C = self.cfg, self.clusters
        L_, kv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
        dt = jnp.int8 if self.quant_kv else jnp.dtype(cfg.param_dtype)
        specs = cluster_engine_specs(self.params)
        mesh_ = self.cmesh.mesh
        ns = functools.partial(NamedSharding, mesh_)
        self.kv_pages = jnp.zeros(
            (L_, C * (num_pages + 1), 2, self.page_size, kv, hd), dt,
            device=ns(specs["kv"]))
        # per-page dequant scales for the int8 KV mode; allocated in both
        # modes so the step signatures stay uniform (bf16 jit DCEs it)
        self.kv_scales = jnp.zeros((L_, C * (num_pages + 1), 2, kv),
                                   jnp.float32, device=ns(specs["kv_scales"]))
        B = self.max_lanes
        lane = ns(specs["lane"])
        self.bt_dev = jnp.zeros((B, self.max_pages), jnp.int32,
                                device=ns(specs["lane2"]))
        self.len_dev = jnp.zeros((B,), jnp.int32, device=lane)
        self.active_dev = jnp.zeros((B,), jnp.int32, device=lane)
        self.last_tok = jnp.zeros((B,), jnp.int32, device=lane)
        self.seed_dev = jnp.zeros((B,), jnp.uint32, device=lane)
        self.temp_dev = jnp.zeros((B,), jnp.float32, device=lane)
        self.topk_dev = jnp.zeros((B,), jnp.int32, device=lane)
        self.topp_dev = jnp.ones((B,), jnp.float32, device=lane)
        # a no-op for parameters already drawn into these shardings
        # (launch.serve.build_engine); others move here once
        self.params = jax.tree.map(
            lambda x, s: jax.device_put(x, ns(s)), self.params,
            specs["params"])

        # the unsharded engine steps, shard_mapped: each (cluster, head)
        # shard runs the single-cluster program on its lane group, local
        # page block and local heads — HERO's "the per-cluster body is
        # literally the single-cluster program" discipline
        itp = jax.default_backend() != "tpu"
        out_specs = (specs["lane"], specs["kv"], specs["kv_scales"],
                     specs["lane"])
        sampling_specs = (specs["lane"],) * 4   # seeds, temps, topk, topp

        # the same two-variant dispatch as the unsharded engine (all-greedy
        # batches never trace the sampler), each variant the shard_map'd
        # single-cluster program; jit is lazy, so only used variants compile
        def mk(step_fn, kind, in_specs, outs):
            def one(s):
                body = named_step(functools.partial(
                    step_fn, cfg, self.use_kernel, pages_per_step, itp,
                    num_pages, axis_name="head", quant=self.quant_kv,
                    sample=s), kind, s)
                return jax.jit(jax.shard_map(body, mesh=mesh_,
                                             in_specs=in_specs,
                                             out_specs=outs,
                                             check_vma=False))
            return {s: one(s) for s in (False, True)}

        self._chunk_step = mk(
            _paged_chunk_step, "chunk",
            (specs["params"], specs["kv"], specs["kv_scales"],
             specs["lane2"], specs["lane"], specs["lane"], specs["lane2"],
             specs["lane"], specs["lane"]) + sampling_specs, out_specs)
        self._decode_step = mk(
            _paged_decode_step, "decode",
            (specs["params"], specs["kv"], specs["kv_scales"],
             specs["lane2"], specs["lane"], specs["lane"],
             specs["lane"]) + sampling_specs, out_specs)
        if self.spec_k:
            # the speculative verify step is the same shard_map discipline:
            # drafts/verdicts shard their lane dim over `cluster`, the
            # acceptance count is computed shard-locally per lane group
            self._spec_step = mk(
                _paged_spec_step, "spec",
                (specs["params"], specs["kv"], specs["kv_scales"],
                 specs["lane2"], specs["lane"], specs["lane"], specs["lane"],
                 specs["lane2"], specs["lane"]) + sampling_specs,
                (specs["lane2"], specs["kv"], specs["kv_scales"],
                 specs["lane"], specs["lane"]))

    def _build_backing_store(self) -> HostBackingStore:
        # cache spill tiers are per cluster (like the pools and prefix
        # indexes they back); swap traffic stays on ONE engine-wide store
        # because a preempted victim may resume on any cluster
        cc = self.cache_cfg
        self.tier_stores: List[HostBackingStore] = []
        for c in range(self.clusters):
            sub = None if cc.disk_dir is None else \
                os.path.join(cc.disk_dir, f"cluster{c}")
            disk = DiskTier(cc.disk_tier_pages, sub) \
                if cc.disk_tier_pages else None
            self.tier_stores.append(HostBackingStore(
                self.faults, host_pages=cc.host_tier_pages, disk_tier=disk))
        return HostBackingStore(self.faults)

    # ---------------------------------------------------------- pool seam --
    def _pool_of(self, cluster: int) -> PagedKVPool:
        return self.cpool.pools[cluster]

    def _all_pools(self) -> List[PagedKVPool]:
        return list(self.cpool.pools)

    def _capacity_pages(self) -> int:
        return self._local_pages

    def _gpage(self, req: SeqState, p: int) -> int:
        return self.cpool.global_page(req.cluster, p)

    def _gpage_c(self, cluster: int, p: int) -> int:
        return self.cpool.global_page(cluster, p)

    def _cache_store_of(self, cluster: int) -> HostBackingStore:
        return self.tier_stores[cluster]

    def _cache_stores(self) -> List[HostBackingStore]:
        return list(self.tier_stores)

    # --------------------------------------------------------- scheduler --
    def _free_lane(self, cluster: int) -> Optional[int]:
        lo = cluster * self.lanes_per_cluster
        for i in range(lo, lo + self.lanes_per_cluster):
            if self.lanes[i] is None:
                return i
        return None

    def _admit(self):
        """Cluster-aware admission: plan the queue head against every
        cluster with a free lane and place it cache-affine least-loaded —
        largest usable prefix hit first, then most obtainable pages, then
        lowest cluster id.  When no cluster fits, preemption reclaims the
        lowest-priority running lane (the sweep is cluster-local: only the
        victim's cluster shard is touched) and planning retries."""
        while self.queue:
            head = self._eligible_head()
            if head is None:
                break                 # every waiter is backing off
            best = None
            for c in range(self.clusters):
                lane = self._free_lane(c)
                if lane is None:
                    continue
                plan = self._plan(head, cluster=c)
                if not self._fits(plan):
                    continue
                score = (plan["usable"], self._pool_of(c).available(), -c)
                if best is None or score > best[0]:
                    best = (score, lane, plan)
            if best is None:
                victim = self._victim(head)
                if victim is None:
                    break
                with self.tracer.span("preempt", rid=victim.rid):
                    self._preempt(victim)
                continue
            self.queue.remove(head)
            with self.tracer.span("place", rid=head.rid):
                self._place(head, best[1], best[2])

    def _place(self, req: SeqState, lane: int, plan: dict):
        self.cpool.place(req.rid, plan["cluster"])
        self.tracer.record_host(EventType.CLUSTER_DISPATCH, req.rid,
                                plan["cluster"])
        if plan["resume"] and req.rid in self._parked_len:
            # re-install the sequence length into the (possibly different)
            # destination cluster's pool before the swap-in restores pages
            self._pool_of(plan["cluster"]).seq_len[req.rid] = \
                self._parked_len.pop(req.rid)
        super()._place(req, lane, plan)

    def _unplace(self, req: SeqState):
        # a deferred swap-in retry: re-park the sequence length and drop
        # the routing entry (mirroring _preempt) so the later retry may
        # place the request on ANY cluster again
        pool = self._pool(req)
        super()._unplace(req)
        self._parked_len[req.rid] = pool.seq_len.pop(req.rid, 0)
        self.cpool.forget(req.rid)

    def _preempt(self, req: SeqState):
        pool = self._pool(req)
        super()._preempt(req)
        if req.done:
            # the checkpoint sweep hit a persistent backing-store fault
            # and demoted the victim: _terminate already cleaned up
            return
        # the victim may be re-placed on ANY cluster (its KV payload is
        # host-resident now): park its sequence length with the scheduler
        # and drop the old cluster's routing entry
        self._parked_len[req.rid] = pool.seq_len.pop(req.rid, 0)
        self.cpool.forget(req.rid)

    def _finish(self, req: SeqState, reason: str):
        super()._finish(req, reason)
        self.cpool.forget(req.rid)

    def _terminate(self, req: SeqState, reason: str, event: str,
                   diag: Optional[str] = None):
        # every exceptional exit (abort / cancel / timeout / error / shed)
        # flows through here: drop the parked length and routing entry too
        super()._terminate(req, reason, event, diag)
        self._parked_len.pop(req.rid, None)
        self.cpool.forget(req.rid)

    # --------------------------------------------------------------- step --
    def step(self) -> bool:
        before_it = self.iterations
        occ0 = self.cpool.occupancy()
        progressed = super().step()
        if self.iterations > before_it:
            busy = {r.cluster for r in self.lanes if r is not None}
            busy |= {r.cluster for r in self.finished[self._fin_mark:]}
            self._fin_mark = len(self.finished)
            # the one D2H token pull per iteration gathers every active
            # cluster's sampled tokens through the mesh
            self.tracer.record_host(EventType.ALL_GATHER, self.iterations,
                                    len(busy))
            for c, (a, b) in enumerate(zip(occ0, self.cpool.occupancy())):
                self.peak_pages[c] = max(self.peak_pages[c], a, b)
        return progressed

    # ------------------------------------------------------------- report --
    def cluster_report(self) -> dict:
        """Per-cluster occupancy/balance summary for benchmarks."""
        occ = self.cpool.occupancy()
        return {
            "clusters": self.clusters,
            "heads": self.heads,
            "peak_pages_per_cluster": list(self.peak_pages),
            "pages_per_cluster": self._local_pages,
            "peak_occupancy_per_cluster": [
                p / self._local_pages for p in self.peak_pages],
            "live_pages_per_cluster": occ,
        }
