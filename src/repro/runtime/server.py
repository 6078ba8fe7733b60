"""Paged continuous-batching serving engine on the RAB + paged KV pool.

This is the serving-side integration of HERO's C1/C2: the host scheduler and
the accelerator share the *logical token address space* (SVM); the RAB
translates logical pages to physical KV pool slots; the attention kernels
(`kernels/paged_attention`) perform the translation on-device through the
scalar-prefetched block table; page allocation happens on the RAB miss path;
admit/finish/alloc/release are all traced (C4) so Fig.6-style timelines can
be reconstructed from a run.

The engine is driven through the unified generation API (``runtime.api``):
callers build an :class:`~repro.runtime.EngineConfig` (one spec for every
pool/scheduler/kernel/speculation knob — ``make_engine`` picks this class
or the sharded one from it) and submit frozen
:class:`~repro.runtime.GenerationRequest` objects whose
:class:`~repro.runtime.SamplingParams` carry the per-request decoding
policy.  Scheduler-internal mutable state (``fed``, ``lane``, ``swapped``,
``spec_*``) lives in the private :class:`SeqState`; what comes back is a
frozen :class:`~repro.runtime.GenerationResult` with a ``finish_reason``
(``stop`` / ``length`` / ``aborted`` / ``timeout`` / ``error`` /
``shed``).  ``engine.generate(requests)`` streams
:class:`~repro.runtime.TokenDelta` increments per iteration — ``run()``
is just the drained generator, and when its iteration cap is hit it
*aborts* (and surfaces) all still-queued/running work instead of
silently dropping it.

**Failure semantics** (HERO: run-time behavior must be *validatable* —
traced, perturbed, re-tested): every exceptional exit funnels through one
``_terminate`` path that releases pages with the same
refcount/CoW/reservation discipline as preemption.  Requests carry
optional deadlines (``timeout``), callers can ``cancel(rid)`` from the
streaming loop body (``aborted``), transient backing-store faults are
retried under a bounded budget — with ``retry_backoff_s > 0`` a failed
swap-in is *deferred* on the engine clock (the lane is released and other
lanes keep decoding; the resume retries when the backoff expires) rather
than sleeping in the tick — while persistent ones demote the *request* to
``error`` — never the engine; a drafter exception merely
disables speculation for its lane; a watchdog aborts lanes that stop
advancing; and when the queue exceeds ``max_queue_depth`` the
lowest-priority waiter is ``shed`` at admission.  All of it is traced
(``FAULT_INJECT`` / ``REQUEST_TIMEOUT`` / ``REQUEST_SHED`` / ``DEGRADE``)
so ``core.analysis.layer2_fault_recovery`` can audit a faulted run.

The hot path follows HERO's "keep the accelerator fed" discipline (Fig. 5 —
DMA double-buffering + zero-copy SVM so the host never serializes on the
data path):

* prompts are admitted through a *chunked prefill* step that consumes up to
  ``chunk`` tokens per engine iteration in one ``paged_prefill`` kernel
  launch (not token-by-token through the decode path);
* the decode step runs entirely from device-resident state — block tables,
  lengths, the active-lane mask, the previously sampled token AND the
  per-lane sampling policy (temperature / top-k / top-p / PRNG seed) all
  live on device; token selection is on-device (exact greedy argmax for
  ``temperature == 0`` lanes, batched temperature/top-k/top-p sampling
  otherwise, each lane's PRNG key folded by absolute sequence position so
  a request's stream is reproducible from its seed alone, independent of
  chunking, scheduling, preemption or sharding); the only per-iteration
  transfer is a single device->host pull of the sampled tokens;
* K and V for all new tokens of all lanes are written into the fused
  ``(L, P+1, 2, page, Kv, hd)`` pool with ONE scatter per layer (invalid
  slots are routed to a trash page, index ``P``, so no masking pass is
  needed);
* the device block table is repeat-padded (entries past the last mapped
  page repeat it) and updated incrementally — one small host->device row
  write per page allocation, amortized to ``<= 1/page_size`` per token.

Host<->device transfer events on this path are traced (``EventType.H2D`` /
``D2H``) so ``benchmarks/serve_throughput.py`` can count them.

On top of the hot path sit HERO's SVM page *sharing* and *reclamation*
(§2.2, §3.4), serving-side:

* **shared-prefix KV caching** — admission consults the pool's prefix
  index; pages already holding the request's prompt prefix are mapped into
  its block table (refcount bumped, RAB entries installed) and their
  prefill is skipped — only the tail chunk runs the prefill kernel.  A
  lane appending into a still-shared partial page is copy-on-written onto
  a private page through the ordinary allocation path;
* **preemptive scheduling** — admission is priority-ordered; when the pool
  (or lane set) is exhausted, the lowest-priority running lane is
  preempted: its pages swap out D2H to a ``HostBackingStore`` (non-shared
  pages are thereby reclaimed; shared ones drop this lane's refcount, the
  host copy making re-admission independent of the sharers' lifetimes)
  and swap back H2D on re-admission, with all traffic traced as
  SWAP_OUT/SWAP_IN plus the underlying H2D/D2H events.

**Speculative decoding** (``spec_k > 0``) is the host/accelerator split
itself: a cheap host-side drafter (``runtime.speculative``) proposes up to
K tokens per decode lane, the pool appends all K+1 candidate positions
(pages allocated, CoW applied — exactly the plain append path), and ONE
chunked verify step (``_paged_spec_step``, the chunk kernel re-used with
the drafts as the feed) greedily scores every position, counts the
accepted prefix on device and advances lengths by ``accepted + 1``.  The
host then *rolls back* the rejected tail: ``PagedKVPool.trim`` unmaps
pages wholly beyond the kept length (respecting refcounts, CoW copies and
the prefix index) and re-credits them to the request's reservation.
Greedy parity is structural — the accepted prefix plus the bonus token is
the exact greedy continuation — and therefore drafting is auto-restricted
to ``temperature == 0`` lanes: sampled lanes never propose drafts, but
they ride along in a verify iteration (their bonus token is drawn by the
same position-folded sampler the plain decode step uses, so their stream
is unchanged).  Per-lane K adapts to recent acceptance (full accept grows
it, zero accept halves it) and drafting is disabled while any request is
queued (preemption pressure: waiting work beats wider verification).
Proposals, acceptances and rollbacks are traced as SPEC_PROPOSE /
SPEC_ACCEPT / SPEC_ROLLBACK.

Demo-scale engine for plain-GQA transformer archs (yi/minitron/qwen3/olmoe
smoke configs).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Iterable, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.offload import (
    BackingStoreError, DiskTier, HostBackingStore,
    TIER_CODES, TIER_DEVICE, TIER_HOST,
)
from repro.core.rab import RAB, RABConfig, PagedKVPool
from repro.core.tracing import EventType, TraceBuffer
from repro.models import layers as L
from repro.models import model as M
from repro.models.layers import rope, rms_head_norm
from repro.kernels.paged_attention.ops import (
    paged_prefill_fused, page_counts_for,
)
from repro.kernels.paged_attention.ref import paged_prefill_ref
from repro.optim.compress import SCALE_EPS, headwise_scales, quantize_int8
from repro.runtime.api import (
    CacheStats, EngineConfig, GenerationRequest, GenerationResult,
    SamplingParams, TokenDelta, FINISH_ABORTED, FINISH_ERROR, FINISH_LENGTH,
    FINISH_SHED, FINISH_STOP, FINISH_TIMEOUT,
)
from repro.runtime.clock import MonotonicClock
from repro.runtime.frontdoor import GreedyChunkPolicy
from repro.runtime.speculative import NGramDrafter


@dataclasses.dataclass
class SeqState:
    """Scheduler-internal mutable state for one admitted request.

    This is deliberately NOT part of the public API: callers see the
    frozen ``GenerationRequest`` going in and the frozen
    ``GenerationResult`` coming out; everything the scheduler mutates
    mid-flight (``fed``, ``lane``, ``swapped``, the ``spec_*`` counters)
    stays private to the engine."""
    rid: int
    prompt: List[int]
    sampling: SamplingParams
    priority: int = 0                 # scheduler class; higher preempts lower
    out: List[int] = dataclasses.field(default_factory=list)
    fed: int = 0                      # prompt tokens already consumed
    lane: int = -1
    done: bool = False
    finish_reason: Optional[str] = None
    prefix_hit_tokens: int = 0        # prompt tokens reused from the cache
    preemptions: int = 0
    arrival: int = -1                 # FIFO tiebreak, assigned by submit()
    cluster: int = 0                  # owning PMCA cluster (sharded engine)
    reg_pages: int = 0                # prompt pages published to the index
    swapped: Optional[List[int]] = None   # lpages parked in the backing store
    promoting: bool = False           # admitted, gated on an in-flight
    #                                   prefix-page promotion (the lane
    #                                   feeds nothing until it lands)
    deadline_iter: Optional[int] = None   # absolute engine-iteration bound
    deadline_t: Optional[float] = None    # absolute engine-clock bound
    not_before: float = 0.0           # engine-clock time before which this
    #                                   queued request may not be placed
    #                                   (deferred swap-in retry backoff)
    retry_attempt: int = 0            # deferred swap-in retries consumed
    error: Optional[str] = None       # diagnostic for error/timeout finishes
    progress_marker: Tuple[int, int] = (-1, -1)   # (fed, len(out)) watermark
    progress_iter: int = 0            # iteration the marker last advanced
    spec_k_cur: int = 0               # adaptive per-lane draft depth
    spec_proposed: int = 0            # drafted tokens sent to verification
    spec_accepted: int = 0            # drafted tokens the target confirmed
    spec_rejected: int = 0            # drafted tokens rolled back

    @property
    def max_new(self) -> int:
        return self.sampling.max_new


def _pack_kv_page(pages: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """Fuse one page's int8 payload + f32 scales into a single 1-D uint8
    blob — the backing store's park/put contract is one ndarray per page
    (one CRC32 covers both, so a corrupted scale fails the checksum the
    same way corrupted page bytes do)."""
    return np.concatenate([
        np.ascontiguousarray(pages).view(np.uint8).reshape(-1),
        np.ascontiguousarray(scales).view(np.uint8).reshape(-1)])


def _unpack_kv_page(blob: np.ndarray, page_shape: tuple,
                    scale_shape: tuple) -> Tuple[np.ndarray, np.ndarray]:
    """Inverse of ``_pack_kv_page``."""
    split = int(np.prod(page_shape))
    blob = np.asarray(blob).view(np.uint8).reshape(-1)
    pages = blob[:split].view(np.int8).reshape(page_shape)
    scales = blob[split:].view(np.float32).reshape(scale_shape)
    return pages, scales


def named_step(fn, kind: str, sample: bool):
    """Name a step program ``paged_<kind>_step_<greedy|sampled>``: the
    module lowers as ``jit_<name>``, which the device trace shows."""
    fn.__name__ = f"paged_{kind}_step_{'sampled' if sample else 'greedy'}"
    return fn


class PagedServer:
    def __init__(self, cfg: ArchConfig, params,
                 engine: Optional[EngineConfig] = None, *,
                 tracer: Optional[TraceBuffer] = None):
        if engine is None:
            engine = EngineConfig()
        assert cfg.block_kind == "transformer" and cfg.attention_kind == "gqa" \
            and not cfg.local_global_period, \
            "paged engine supports plain-GQA transformer archs"
        self.engine_cfg = engine
        self.cfg, self.params = cfg, params
        # EngineConfig.__post_init__ folded any legacy flat knobs into the
        # grouped CacheConfig and mirrored them back, so `engine.cache` is
        # always the authoritative spelling here
        self.cache_cfg = engine.cache
        # quantized KV serving: int8 pages + per-(page, K/V, head) scales.
        # Attention math stays fp32/bf16 — only residency/traffic shrink.
        self.quant_kv = self.cache_cfg.kv_dtype == "int8"
        self.page_size, self.max_lanes = self.cache_cfg.page_size, \
            engine.max_lanes
        self.max_pages = self.cache_cfg.max_pages_per_seq
        self.chunk = max(1, engine.chunk)
        self.tracer = tracer or TraceBuffer()
        self.use_kernel = engine.use_kernel
        # one time source for every scheduler timestamp (deadline_s
        # binding, retry backoff, straggler EMA): inject a VirtualClock
        # and the whole tick path replays deterministically
        self.clock = engine.clock if engine.clock is not None \
            else MonotonicClock()
        # the chunked-prefill/decode interleave as an explicit object
        self.policy = engine.scheduler_policy \
            if engine.scheduler_policy is not None else GreedyChunkPolicy()
        # speculative decoding: drafter proposes, the verify step disposes
        self.spec_k = max(0, engine.spec_k)
        self.drafter = engine.drafter if engine.drafter is not None else \
            (NGramDrafter() if self.spec_k else None)
        # overridable construction hooks: the sharded subclass substitutes
        # per-cluster pools and mesh-sharded device state here instead of
        # allocating the unsharded versions only to discard them
        self._build_pool(self.cache_cfg.num_pages, engine.rab_cfg)
        self._build_device_state(self.cache_cfg.num_pages,
                                 engine.pages_per_step)
        self._bt_host = np.zeros((self.max_lanes, self.max_pages),
                                 np.int32)
        self.lanes: List[Optional[SeqState]] = [None] * self.max_lanes
        self.queue: List[SeqState] = []
        self.finished: List[GenerationResult] = []
        self.iterations = 0
        self.prefill_tokens = 0       # prompt tokens run through prefill
        self.h2d_events = 0
        self.d2h_events = 0
        # shared-prefix caching + preemption (HERO SVM page sharing and
        # reclamation on the serving path)
        self.enable_prefix_cache = self.cache_cfg.enable_prefix_cache
        # fault tolerance: the injector (if any) perturbs the swap path;
        # it traces every decision through THIS engine's tracer so the
        # injected-vs-recovered story reads from one event stream
        self.faults = engine.fault_injector
        if self.faults is not None and self.faults.tracer is None:
            self.faults.tracer = self.tracer
        # hierarchical prefix cache (HERO SVM ladder: scratchpad -> host
        # DRAM -> storage).  Swap traffic and demoted cache entries share
        # one tier chain; with no host tier configured the store degrades
        # to the flat host-dict it always was.
        self.backing = self._build_backing_store()
        if self.cache_cfg.spill_enabled and self.enable_prefix_cache:
            for p in self._all_pools():
                p.spill_enabled = True
        self._promotions: List[dict] = []   # in-flight H2D prefetches
        self.cache_hit_pages = {"device": 0, "host": 0, "disk": 0}
        self.cache_miss_pages = 0
        self.swap_retries = max(0, engine.swap_retries)
        self.retry_backoff_s = max(0.0, engine.retry_backoff_s)
        self.max_queue_depth = max(0, engine.max_queue_depth)
        self.watchdog_iters = max(0, engine.watchdog_iters)
        self.straggler_factor = max(0.0, engine.straggler_factor)
        self.fault_retries = 0        # transient-fault retries attempted
        self.recovered_faults = 0     # ops that succeeded after retrying
        self.timeouts = 0
        self.cancelled = 0
        self.errors = 0               # per-request "error" demotions
        self.shed_count = 0
        self.degrades = 0             # DEGRADE events emitted
        self.straggler_steps = 0      # iterations the EMA watchdog flagged
        self._ema_step_s: Optional[float] = None
        self.preemptions = 0
        self._dirty: set = set()      # lane rows to push before the kernel
        self._arrival = 0
        self._deltas: List[TokenDelta] = []   # streamed by generate()
        self.spec_iterations = 0      # engine iterations that verified drafts
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_rejected = 0

    # --------------------------------------------------------------- trace --
    def _h2d(self, n: int = 1):
        self.h2d_events += n
        self.tracer.record_host(EventType.H2D, n, 0)

    def _d2h(self, n: int = 1):
        self.d2h_events += n
        self.tracer.record_host(EventType.D2H, n, 0)

    def _delta(self, rid: int, tokens=(), event: str = "token", data: int = 0,
               reason: Optional[str] = None):
        self._deltas.append(TokenDelta(rid=rid, tokens=tuple(tokens),
                                       event=event, data=data,
                                       finish_reason=reason))

    # ------------------------------------------------------ construction --
    def _build_pool(self, num_pages: int, rab_cfg: RABConfig):
        self.rab = RAB(rab_cfg, self.tracer)
        self.pool = PagedKVPool(num_pages, self.page_size, self.max_pages,
                                self.rab)

    def _build_device_state(self, num_pages: int,
                            pages_per_step: Optional[int]):
        cfg = self.cfg
        L_, kv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
        dt = jnp.int8 if self.quant_kv else jnp.dtype(cfg.param_dtype)
        # fused K/V pool; the extra page (index num_pages) is the trash page
        # masked writes are routed to
        self.kv_pages = jnp.zeros(
            (L_, num_pages + 1, 2, self.page_size, kv, hd), dt)
        # per-(page, K/V, kv-head) dequant scales riding with the pool.
        # Allocated in both modes so every step has one signature; in bf16
        # mode the quant=False trace never reads it and jit DCEs the input.
        self.kv_scales = jnp.zeros((L_, num_pages + 1, 2, kv), jnp.float32)
        itp = jax.default_backend() != "tpu"

        # two variants per step, keyed by "does any active lane sample?":
        # the all-greedy variant compiles without the sampler (no per-lane
        # sorts/softmax whose results a where() would discard), so the
        # historical greedy hot path pays nothing for the sampling API;
        # jit is lazy, so greedy-only workloads never compile the other
        def mk(step_fn, kind):
            return {s: jax.jit(named_step(functools.partial(
                step_fn, cfg, self.use_kernel, pages_per_step, itp,
                num_pages, quant=self.quant_kv, sample=s), kind, s))
                for s in (False, True)}

        self._chunk_step = mk(_paged_chunk_step, "chunk")
        self._decode_step = mk(_paged_decode_step, "decode")
        if self.spec_k:
            self._spec_step = mk(_paged_spec_step, "spec")
        # device-resident engine state (HERO SVM: the scheduler and the
        # model share these without per-iteration re-uploads); the four
        # sampling-policy rows ride with the lane like lengths do
        self.bt_dev = jnp.zeros((self.max_lanes, self.max_pages), jnp.int32)
        self.len_dev = jnp.zeros((self.max_lanes,), jnp.int32)
        self.active_dev = jnp.zeros((self.max_lanes,), jnp.int32)
        self.last_tok = jnp.zeros((self.max_lanes,), jnp.int32)
        self.seed_dev = jnp.zeros((self.max_lanes,), jnp.uint32)
        self.temp_dev = jnp.zeros((self.max_lanes,), jnp.float32)
        self.topk_dev = jnp.zeros((self.max_lanes,), jnp.int32)
        self.topp_dev = jnp.ones((self.max_lanes,), jnp.float32)

    def _build_backing_store(self) -> HostBackingStore:
        cc = self.cache_cfg
        disk = DiskTier(cc.disk_tier_pages, cc.disk_dir) \
            if cc.disk_tier_pages else None
        return HostBackingStore(self.faults, host_pages=cc.host_tier_pages,
                                disk_tier=disk)

    # ---------------------------------------------------------- pool seam --
    # Every pool access for a placed request routes through these, so the
    # sharded subclass can substitute cluster-local pools and translate
    # local physical page ids into the fused device slab's global indices.
    def _pool_of(self, cluster: int) -> PagedKVPool:
        return self.pool

    def _pool(self, req: SeqState) -> PagedKVPool:
        return self._pool_of(req.cluster)

    def _all_pools(self) -> List[PagedKVPool]:
        """Every pool, indexed by cluster (one for the base engine)."""
        return [self.pool]

    def _capacity_pages(self) -> int:
        """Page capacity one request can draw from (per cluster)."""
        return self.pool.num_pages

    def _gpage(self, req: SeqState, p: int) -> int:
        """Pool-local physical page -> index into self.kv_pages."""
        return p

    def _gpage_c(self, cluster: int, p: int) -> int:
        """Cluster-local physical page -> index into self.kv_pages."""
        return p

    # ---------------------------------------------------- cache tier seam --
    def _cache_store_of(self, cluster: int) -> HostBackingStore:
        """Tier store carrying ``cluster``'s demoted prefix-cache pages
        (the sharded engine keeps one per cluster; swap traffic stays on
        ``self.backing`` regardless)."""
        return self.backing

    def _cache_stores(self) -> List[HostBackingStore]:
        return [self.backing]

    def close(self):
        """Release tier resources (disk-tier files and directories).
        Idempotent; the engine stays usable for stats reads afterwards."""
        stores = {id(s): s for s in self._cache_stores()}
        stores.setdefault(id(self.backing), self.backing)
        for st in stores.values():
            st.close()

    # ------------------------------------------------------------- admin --
    def submit(self, req: GenerationRequest):
        # real exceptions, not asserts: an unplaceable request at the queue
        # head would otherwise spin _admit forever (and -O strips asserts)
        if not req.prompt:
            # an empty prompt would enter decode seeded by whatever token
            # the lane's previous occupant left in last_tok
            raise ValueError("empty prompt")
        sp = req.sampling
        if len(req.prompt) + sp.max_new - 1 > \
                self.max_pages * self.page_size:
            raise ValueError("request exceeds max_pages_per_seq")
        seq = SeqState(rid=req.rid, prompt=list(req.prompt), sampling=sp,
                       priority=req.priority)
        if self._pages_needed(seq) + self._cow_budget(seq) > \
                self._capacity_pages():
            raise ValueError("request exceeds KV pool capacity")
        with self.tracer.span("submit", rid=req.rid):
            seq.arrival = self._arrival
            self._arrival += 1
            if req.deadline_iters is not None:
                seq.deadline_iter = self.iterations + req.deadline_iters
            if req.deadline_s is not None:
                # bound on the injected clock, not raw time.monotonic(): under
                # a VirtualClock the request times out at an exact, testable
                # tick; under the wall clock behaviour is unchanged
                seq.deadline_t = self.clock.now() + req.deadline_s
            self.tracer.record_host(EventType.REQUEST_ARRIVE, seq.rid,
                                    len(self.queue))
            if self.spec_k and sp.greedy:
                # drafting is greedy-lane-only: verification is greedy argmax,
                # so a sampled lane's drafts could never be parity-accepted
                seq.spec_k_cur = self.spec_k
            self.queue.append(seq)
            if self.max_queue_depth and len(self.queue) > self.max_queue_depth:
                # admission-time load shedding: rather than admit work that
                # will thrash the pool, reject the lowest-priority waiter
                # (youngest within a class — so on a priority tie the
                # newcomer itself is turned away).  Preemption re-queues
                # bypass this: a victim already holds parked state and must
                # be allowed back.
                victim = min(self.queue,
                             key=lambda r: (r.priority, -r.arrival))
                self.shed_count += 1
                self.tracer.record_host(EventType.REQUEST_SHED, victim.rid,
                                        len(self.queue))
                self._terminate(victim, FINISH_SHED, "shed",
                                diag="queue depth exceeded "
                                     f"{self.max_queue_depth}")

    def _pages_needed(self, req: SeqState) -> int:
        # every token the engine will *write* K/V for: the prompt plus all
        # generated tokens except the last (sampled but never fed back)
        total = len(req.prompt) + req.max_new - 1
        return int(page_counts_for(total, self.page_size))

    # --------------------------------------------------------- scheduler --
    def _cow_budget(self, req: SeqState) -> int:
        """One extra reserved page for a request whose prompt tail is
        partial: once that tail is *registered* in the prefix index, a
        later admission may share it, and this request's own next append
        then copy-on-writes — a page its plain per-page reservation never
        counted (the donor side of CoW must be budgeted too, or an
        admitted request could hit pool exhaustion mid-stream)."""
        return 1 if (self.enable_prefix_cache and req.max_new > 1
                     and len(req.prompt) % self.page_size) else 0

    def _plan(self, req: SeqState, cluster: int = 0) -> dict:
        """Admission plan against ``cluster``'s pool: which prefix-cache
        pages to map and how many pages to reserve.  ``need`` excludes only
        *stable* shared pages (fully written, never appended again); a
        shared partial tail keeps one reserved page as the sharer's
        copy-on-write budget, the donor-side CoW is budgeted by
        ``_cow_budget``, and a resuming request budgets every page it must
        restore or still allocate."""
        pool = self._pool_of(cluster)
        total = self._pages_needed(req) + self._cow_budget(req)
        ps = self.page_size
        if req.swapped is not None:            # resuming after preemption
            # preemption dropped every mapping, so the whole lifetime page
            # budget (restores + future allocations) is needed again
            return {"resume": True, "hits": [], "usable": 0,
                    "need": total, "cached_hits": 0, "cluster": cluster}
        usable, hits = 0, []
        if self.enable_prefix_cache and len(req.prompt) > 1:
            # the hit chain may cross tiers: ("device", ppage) entries map
            # by sharing, ("spilled", key) entries are non-resident and
            # cost a fresh page each (counted in `need` below) plus an
            # async promotion at placement
            entries, n = pool.match_prefix_tiered(req.prompt)
            # the final prompt token always runs through the model (it
            # produces the first sampled token), so it is never reused
            usable = min(n, len(req.prompt) - 1)
            hits = entries[:-(-usable // ps)] if usable else []
        # only *stable* device-resident full pages are free; spilled hits
        # still draw a page from the pool for their promoted payload
        full = usable // ps
        dev_full = sum(1 for i, (kind, _v) in enumerate(hits)
                       if kind == "device" and i < full)
        need = total - dev_full
        cached = sum(1 for kind, v in hits
                     if kind == "device" and v in pool.cached_free)
        plan = {"resume": False, "hits": hits, "usable": usable,
                "need": need, "cached_hits": cached, "cluster": cluster}
        if hits and not self._fits(plan):
            # hits sitting on cached-free pages cost evictable capacity a
            # no-sharing admission would simply reuse — never let the cache
            # starve a request that fits without it
            fallback = {"resume": False, "hits": [], "usable": 0,
                        "need": total, "cached_hits": 0, "cluster": cluster}
            if self._fits(fallback):
                return fallback
        return plan

    def _fits(self, plan: dict) -> bool:
        # reviving cached-free hit pages consumes them from the evictable
        # set, so they are budgeted on top of the reservation
        return self._pool_of(plan["cluster"]).available() >= \
            plan["need"] + plan["cached_hits"]

    def _victim(self, head: SeqState) -> Optional[SeqState]:
        """Lowest-priority running request (youngest within a class) —
        preemptable only by a strictly higher-priority waiter, so equal
        classes never churn each other."""
        running = [r for r in self.lanes if r is not None]
        if not running:
            return None
        v = min(running, key=lambda r: (r.priority, -r.arrival))
        return v if v.priority < head.priority else None

    def _eligible_head(self) -> Optional[SeqState]:
        """Highest-priority oldest waiter whose deferred-retry backoff (if
        any) has expired on the engine clock.  Deferred requests are
        skipped, not blocking: a lane freed behind one backing-off resume
        goes to the next waiter instead of idling."""
        self.queue.sort(key=lambda r: (-r.priority, r.arrival))
        now = self.clock.now()
        return next((r for r in self.queue if r.not_before <= now), None)

    def _admit(self):
        while self.queue:
            # re-sort every round (inside _eligible_head): _preempt
            # re-enqueues its victim, which must keep its priority rank
            # over lower-priority waiters
            head = self._eligible_head()
            if head is None:
                break                     # every waiter is backing off
            lane = next((i for i in range(self.max_lanes)
                         if self.lanes[i] is None), None)
            plan = self._plan(head)
            if lane is None or not self._fits(plan):
                victim = self._victim(head)
                if victim is None:
                    break
                with self.tracer.span("preempt", rid=victim.rid):
                    self._preempt(victim)
                continue                  # pool/lane state changed: re-plan
            self.queue.remove(head)
            with self.tracer.span("place", rid=head.rid):
                self._place(head, lane, plan)

    def _resolve_spilled_hits(self, req: SeqState, plan: dict):
        """Pull every spilled hit's payload out of the tier store *before*
        any pool mutation.  A fetch fault (CRC mismatch, injected pop
        fault past the retry budget) drops that entry from every tier and
        re-plans — dropping a spilled hit never changes ``need`` (device
        hits are untouched), so the replacement plan still fits and the
        admission proceeds with whatever prefix remains."""
        pool = self._pool_of(plan["cluster"])
        store = self._cache_store_of(plan["cluster"])
        while True:
            fetched: dict = {}
            ok = True
            for lp, (kind, val) in enumerate(plan["hits"]):
                if kind != "spilled":
                    continue
                eid = pool.key_ids[val]
                try:
                    payload, tier = self._with_retries(functools.partial(
                        store.fetch_cache, eid, req.rid), req.rid)
                except BackingStoreError:
                    pool.drop_spilled(val)
                    store.drop_cache(eid)
                    self._trace_store_moves(store)
                    ok = False
                    break
                fetched[lp] = (eid, payload, tier)
            if ok:
                return plan, fetched
            plan = self._plan(req, plan["cluster"])

    def _place(self, req: SeqState, lane: int, plan: dict):
        rid = req.rid
        req.lane = lane
        req.cluster = plan["cluster"]
        pool = self._pool(req)
        self.lanes[lane] = req
        fetched: dict = {}
        if not plan["resume"] and any(k == "spilled" for k, _ in
                                      plan["hits"]):
            # fetch before reserving: a fetch fault re-plans through
            # _fits, which must not see this request's own reservation
            plan, fetched = self._resolve_spilled_hits(req, plan)
        if not plan["resume"]:
            prompt_pages = -(-len(req.prompt) // self.page_size)
            self.cache_miss_pages += prompt_pages - len(plan["hits"])
        if plan["need"] > 0:
            # reserve the request's remaining lifetime page budget so
            # chunked prefill / restore can never hit exhaustion mid-stream
            pool.reserve(rid, plan["need"])
        req.progress_marker = (req.fed, len(req.out))
        req.progress_iter = self.iterations   # queue time never counts
        if plan["resume"]:
            try:
                self._swap_in(req)
            except BackingStoreError as e:
                if self._defer_resume(req, e):
                    # transient fault with backoff configured: undo the
                    # placement and re-queue the resume for a later tick —
                    # the lane goes back to the pool and every other lane
                    # keeps decoding while this request backs off
                    self._unplace(req)
                    return
                # the parked payload is unrestorable: demote THIS request
                # (reservation and any partial restore released through
                # _terminate) and keep serving everyone else
                self._fail(req, str(e))
                return
            if req.retry_attempt:
                # a deferred-retry resume finally restored: count the
                # recovery the in-place retry path would have counted
                self.recovered_faults += 1
                req.retry_attempt = 0
        elif plan["usable"]:
            # prefix-cache hit: map resident pages by sharing; adopt a
            # fresh page for each spilled hit (its payload was fetched
            # above and is uploaded in _begin_promotion below)
            promo: List[tuple] = []
            for lp, (kind, val) in enumerate(plan["hits"]):
                if kind == "device":
                    pool.share_page(rid, lp, val)
                    self.cache_hit_pages["device"] += 1
                else:
                    eid, payload, tier = fetched[lp]
                    p = pool.adopt_spilled(rid, lp, val)
                    self.cache_hit_pages[tier] += 1
                    promo.append((self._gpage(req, p), eid, payload, tier))
            pool.seq_len[rid] = plan["usable"]
            pool.stats["prefix_hit_tokens"] += plan["usable"]
            req.fed = plan["usable"]
            req.prefix_hit_tokens = plan["usable"]
            req.reg_pages = plan["usable"] // self.page_size
            self.tracer.record_host(EventType.PREFIX_HIT, rid,
                                    plan["usable"])
            self._delta(rid, event="prefix_hit", data=plan["usable"])
            if promo:
                # adopting may have evicted+spilled other entries: park
                # their payloads before the promotion upload can land on
                # a recycled page
                self._drain_tier_ops()
                self._begin_promotion(req, promo)
        self._refresh_row(lane, req)
        sp = req.sampling
        self.active_dev = self.active_dev.at[lane].set(
            0 if req.promoting else 1)
        self.len_dev = self.len_dev.at[lane].set(
            pool.seq_len.get(rid, 0))
        self.seed_dev = self.seed_dev.at[lane].set(sp.seed & 0xFFFFFFFF)
        self.temp_dev = self.temp_dev.at[lane].set(sp.temperature)
        self.topk_dev = self.topk_dev.at[lane].set(sp.top_k)
        self.topp_dev = self.topp_dev.at[lane].set(sp.top_p)
        if plan["resume"] and req.fed >= len(req.prompt) and req.out:
            # mid-decode resume: re-seed the device-resident last sample
            self.last_tok = self.last_tok.at[lane].set(req.out[-1])
        self._h2d(1)
        self.tracer.record_host(EventType.REQUEST_ADMIT, rid, lane)

    def _defer_resume(self, req: SeqState, e: BackingStoreError) -> bool:
        """Should this failed swap-in be rescheduled instead of demoting
        the request?  Yes iff the fault is transient, a backoff is
        configured (``retry_backoff_s > 0`` — with 0 the in-place retry
        loop already ran inside ``_swap_in``) and budget remains.  On
        True the request's ``not_before`` is set to the exponential-
        backoff deadline on the engine clock; the caller unwinds the
        placement.  The engine never sleeps: other lanes keep emitting
        tokens while this request waits out its backoff in the queue."""
        if not (e.transient and self.retry_backoff_s
                and req.retry_attempt < self.swap_retries):
            return False
        req.retry_attempt += 1
        self.fault_retries += 1
        req.not_before = self.clock.now() + \
            self.retry_backoff_s * (2 ** (req.retry_attempt - 1))
        return True

    def _unplace(self, req: SeqState):
        """Reverse an in-progress ``_place`` whose swap-in was deferred:
        free the lane, drop the reservation, re-queue the request (still
        ``swapped`` — ``_swap_in`` re-parked everything it had popped, so
        the backing store is exactly as before the attempt)."""
        pool, lane = self._pool(req), req.lane
        pool.reserved.pop(req.rid, None)
        self.lanes[lane] = None
        req.lane = -1
        self.queue.append(req)

    # -------------------------------------------------- page payload seam --
    # Every D2H snapshot / H2D restore of whole pages routes through these,
    # so the quantized path can carry the scales alongside the int8 bytes
    # (packed into one blob per page — one checksum, one tier entry) while
    # bf16 payloads keep their historical raw-array format.
    def _page_shapes(self) -> Tuple[tuple, tuple]:
        """(per-page payload shape, per-page scale shape) across layers."""
        L_ = self.cfg.num_layers
        kv, hd = self.cfg.num_kv_heads, self.cfg.resolved_head_dim
        return (L_, 2, self.page_size, kv, hd), (L_, 2, kv)

    def _snap_pages(self, idx: List[int]) -> List[np.ndarray]:
        """One gathered D2H pull of ``idx``'s pages; returns one payload
        per page (packed blobs in int8 mode)."""
        gi = jnp.asarray(idx)
        payload = np.asarray(self.kv_pages[:, gi])
        if not self.quant_kv:
            return [payload[:, j] for j in range(len(idx))]
        scales = np.asarray(self.kv_scales[:, gi])
        return [_pack_kv_page(payload[:, j], scales[:, j])
                for j in range(len(idx))]

    def _load_pages(self, phys: List[int], payloads: List[np.ndarray]):
        """One batched H2D restore of ``payloads`` into pool slots
        ``phys`` (unpacking blob payloads into pages + scales in int8
        mode)."""
        gi = jnp.asarray(phys)
        if not self.quant_kv:
            payload = jnp.stack([jnp.asarray(p) for p in payloads], axis=1)
            self.kv_pages = self.kv_pages.at[:, gi].set(
                payload.astype(self.kv_pages.dtype))
            return
        pshape, sshape = self._page_shapes()
        parts = [_unpack_kv_page(p, pshape, sshape) for p in payloads]
        pages = jnp.stack([jnp.asarray(pg) for pg, _ in parts], axis=1)
        scales = jnp.stack([jnp.asarray(sc) for _, sc in parts], axis=1)
        self.kv_pages = self.kv_pages.at[:, gi].set(
            pages.astype(self.kv_pages.dtype))
        self.kv_scales = self.kv_scales.at[:, gi].set(scales)

    def _preempt(self, req: SeqState):
        """Reclaim a running lane: every mapped page's payload goes D2H
        into the host backing store and the mapping drops.  Non-shared
        pages are thereby freed immediately; shared pages merely lose this
        request's refcount (they live on under their other owners or on
        the cached-free list), but checkpointing their payload too makes
        re-admission independent of those owners' lifetimes — so a full
        preemption sweep always reclaims everything a victim held and the
        scheduler can never pin the pool behind preempted sequences."""
        rid, i = req.rid, req.lane
        pool = self._pool(req)
        if req.promoting:
            # the promoted payload is already device-resident (uploaded at
            # placement), so the sweep below checkpoints correct data —
            # just close the in-flight promotion's books first
            self._land_promotions(force_rid=rid)
        mapped = pool.seq_pages(rid)
        if mapped:
            payloads = self._snap_pages([self._gpage(req, p)
                                         for _, p in mapped])
            self._d2h(len(mapped))    # one gather, len(mapped) pages pulled
            try:
                for j, (lp, _p) in enumerate(mapped):
                    self._with_retries(functools.partial(
                        self.backing.put, rid, lp, payloads[j]), rid)
                    pool.unmap_page(rid, lp)
            except BackingStoreError as e:
                # checkpoint failed persistently mid-sweep: the victim
                # cannot be parked, so demote it — _terminate releases the
                # still-mapped tail and discards the already-parked head
                self._fail(req, str(e))
                return
        req.swapped = [lp for lp, _ in mapped]
        pool.reserved.pop(rid, None)
        req.lane = -1
        req.preemptions += 1
        self.preemptions += 1
        self.lanes[i] = None
        self.active_dev = self.active_dev.at[i].set(0)
        self.len_dev = self.len_dev.at[i].set(0)
        self._h2d(1)
        pool.stats["swapped_out"] += len(mapped)
        self.tracer.record_host(EventType.SWAP_OUT, rid, len(mapped))
        self.tracer.record_host(EventType.REQUEST_PREEMPT, rid, len(mapped))
        self._delta(rid, event="preempt", data=len(mapped))
        self.queue.append(req)

    def preempt(self, rid: int) -> bool:
        """Forcibly preempt a running request (test/benchmark hook; pool
        pressure drives the same path through the scheduler)."""
        for r in self.lanes:
            if r is not None and r.rid == rid:
                self._preempt(r)
                return True
        return False

    def _swap_in(self, req: SeqState):
        """Restore a preempted request's swapped pages: fresh physical
        pages, one batched H2D payload upload, mappings re-established.

        Raises :class:`BackingStoreError` when a parked payload cannot be
        restored (persistent fault / checksum mismatch / retry budget
        exhausted); payloads are popped *before* any pool mutation and
        ``req.swapped`` stays set until all pops succeed, so the caller's
        demotion path (``_place``) releases a consistent request.

        With ``retry_backoff_s > 0`` a transient pop fault is NOT retried
        in place: already-popped payloads are re-parked (the store ends
        up exactly as before the attempt — the faulted page itself was
        never removed, the injector fires before removal) and the error
        propagates so ``_place`` can defer the whole resume on the engine
        clock instead of stalling the tick."""
        rid = req.rid
        pool = self._pool(req)
        lps = req.swapped
        if not lps:
            req.swapped = None
            return
        deferring = bool(self.retry_backoff_s)
        payloads: List[np.ndarray] = []
        try:
            for lp in lps:
                if deferring:
                    payloads.append(self.backing.pop(rid, lp))
                else:
                    payloads.append(self._with_retries(functools.partial(
                        self.backing.pop, rid, lp), rid))
        except BackingStoreError as e:
            if deferring and e.transient \
                    and req.retry_attempt < self.swap_retries:
                for lp, payload in zip(lps, payloads):
                    self.backing.repark(rid, lp, payload)
            raise
        req.swapped = None
        phys = [self._gpage(req, pool.alloc_page(rid, lp)) for lp in lps]
        # allocating may have evicted+spilled indexed pages: park their
        # payloads before this restore's upload can overwrite them
        self._drain_tier_ops()
        self._load_pages(phys, payloads)
        self._h2d(len(lps))
        pool.stats["swapped_in"] += len(lps)
        self.tracer.record_host(EventType.SWAP_IN, rid, len(lps))

    def _refresh_row(self, lane: int, req: SeqState):
        """Rebuild a lane's repeat-padded host block-table row from the
        pool (through the RAB translate path) and mark it for upload."""
        pool, rid = self._pool(req), req.rid
        n = pool.seq_len.get(rid, 0)
        n_pages = -(-n // self.page_size) if n else 0
        last = 0
        for lp in range(n_pages):
            last = pool.translate(rid, lp)
            self._bt_host[lane, lp] = last
        self._bt_host[lane, n_pages:] = last
        self._dirty.add(lane)

    def _register_prompt_pages(self, active: List[SeqState],
                               n_new: np.ndarray):
        """Publish prompt-prefix pages completed this iteration into the
        prefix index (full pages as they fill; the partial tail page once
        the whole prompt is pool-resident).  Decode-phase pages are never
        indexed — generated tokens are request-private."""
        if not self.enable_prefix_cache:
            return
        ps = self.page_size
        for r in active:
            if n_new[r.lane] == 0 or r.fed >= len(r.prompt):
                continue
            pool = self._pool(r)
            written = min(pool.seq_len.get(r.rid, 0), len(r.prompt))
            for lp in range(r.reg_pages, written // ps):
                pool.register_page(r.rid, lp, r.prompt)
            r.reg_pages = max(r.reg_pages, written // ps)
            if written == len(r.prompt) and written % ps:
                pool.register_page(r.rid, written // ps, r.prompt)

    # ------------------------------------------- hierarchical cache tiers --
    def _trace_store_moves(self, store: HostBackingStore):
        for eid, src, dst in store.drain_cache_moves():
            self.tracer.record_host(EventType.PAGE_DEMOTE, eid,
                                    src * 4 + dst)

    def _drain_tier_ops(self):
        """Service the pools' pending tier transitions: pull the payload
        of every just-demoted page D2H and park it in the tier store
        (MUST run before any device write that could recycle the page),
        drop entries a re-registration superseded, then trace the store's
        own cascade moves (host -> disk under pressure, drops)."""
        for c, pool in enumerate(self._all_pools()):
            if not (pool.pending_demote or pool.pending_spill_drop):
                continue
            store = self._cache_store_of(c)
            moves = pool.drain_demotions()
            # skip entries superseded between eviction and this drain
            live = [(p, key) for p, key in moves if key in pool.spilled]
            if live:
                payloads = self._snap_pages([self._gpage_c(c, p)
                                             for p, _ in live])
                self._d2h(len(live))
                for j, (_p, key) in enumerate(live):
                    eid = pool.key_ids[key]
                    store.park_cache(eid, payloads[j])
                    self.tracer.record_host(EventType.PAGE_DEMOTE, eid,
                                            TIER_DEVICE * 4 + TIER_HOST)
            for key in pool.drain_spill_drops():
                store.drop_cache(pool.key_ids[key])
            self._trace_store_moves(store)

    def _begin_promotion(self, req: SeqState, promo: List[tuple]):
        """Upload the fetched spilled payloads into their adopted device
        pages and schedule the promotion's *landing* on the engine clock.

        The payload write is issued immediately — the pages are never
        garbage, so sharers admitted off the restored index entries and
        preemption sweeps always read correct data — but the admitted
        lane stays gated (``active_dev`` 0, fed nothing) until the
        modeled H2D prefetch latency elapses: ``prefetch_depth`` pages
        move per latency quantum.  All timing binds through
        ``self.clock`` (never raw time.*), so a VirtualClock replays the
        whole overlap byte-identically."""
        cc = self.cache_cfg
        self._load_pages([g for g, _eid, _pl, _t in promo],
                         [pl for _g, _eid, pl, _t in promo])
        self._h2d(len(promo))
        quanta = -(-len(promo) // max(1, cc.prefetch_depth))
        due = self.clock.now() + cc.promote_latency_s * quanta
        req.promoting = True
        self._promotions.append({
            "rid": req.rid, "due": due,
            "pages": [(eid, t) for _g, eid, _pl, t in promo]})

    def _land_promotions(self, force_rid: Optional[int] = None):
        """Complete every promotion whose due time has passed (or whose
        owner ``force_rid`` is being preempted/terminated — the payload
        is already device-resident, so cancellation just closes the
        books): trace PAGE_PROMOTE per page and un-gate the lane."""
        if not self._promotions:
            return
        now = self.clock.now()
        rest = []
        for pr in self._promotions:
            if pr["due"] > now and pr["rid"] != force_rid:
                rest.append(pr)
                continue
            for eid, tier in pr["pages"]:
                self.tracer.record_host(EventType.PAGE_PROMOTE, eid,
                                        TIER_CODES[tier] * 4 + TIER_DEVICE)
            req = next((r for r in self.lanes
                        if r is not None and r.rid == pr["rid"]), None)
            if req is not None and req.promoting:
                req.promoting = False
                # the gated interval must not count against the lane's
                # progress watchdog
                req.progress_iter = self.iterations
                self.active_dev = self.active_dev.at[req.lane].set(1)
                self._h2d(1)
        self._promotions = rest

    def _runnable(self) -> List[SeqState]:
        """Lanes the iteration may feed: resident and not promotion-gated."""
        return [r for r in self.lanes if r is not None and not r.promoting]

    def cache_stats(self) -> CacheStats:
        """One frozen snapshot of the hierarchical prefix cache — tier
        residency, per-tier admission hits, promotion/demotion traffic —
        aggregated across clusters.  The supported way to observe the
        cache (benchmarks and tests poke no pool internals)."""
        pools = self._all_pools()
        stores = self._cache_stores()
        resident = {"host": 0, "disk": 0}
        bytes_dem = bytes_pro = dropped = 0
        for st in stores:
            r = st.cache_resident()
            resident["host"] += r.get("host", 0)
            resident["disk"] += r.get("disk", 0)
            bytes_dem += st.cache_bytes_demoted
            bytes_pro += st.cache_bytes_promoted
            dropped += st.cache_dropped
        cfg = self.cfg
        kv_hd = cfg.num_kv_heads * cfg.resolved_head_dim
        if self.quant_kv:
            # int8 page bytes plus the per-page scale slab amortized over
            # the page's token slots (4 bytes per (K/V, head) per page)
            bpt = cfg.num_layers * 2 * (
                kv_hd + 4.0 * cfg.num_kv_heads / self.page_size)
        else:
            bpt = cfg.num_layers * 2 * kv_hd * \
                jnp.dtype(cfg.param_dtype).itemsize
        return CacheStats(
            bytes_per_token=float(bpt),
            device_pages=sum(p.num_pages for p in pools),
            device_indexed=sum(len(p.prefix_index) for p in pools),
            device_cached_free=sum(len(p.cached_free) for p in pools),
            host_pages=resident["host"],
            disk_pages=resident["disk"],
            hits_device_pages=self.cache_hit_pages["device"],
            hits_host_pages=self.cache_hit_pages["host"],
            hits_disk_pages=self.cache_hit_pages["disk"],
            miss_pages=self.cache_miss_pages,
            prefix_hit_tokens=sum(p.stats["prefix_hit_tokens"]
                                  for p in pools),
            promotions_in_flight=len(self._promotions),
            demoted_pages=sum(p.stats["cache_demoted"] for p in pools),
            promoted_pages=sum(p.stats["cache_promoted"] for p in pools),
            dropped_entries=dropped,
            bytes_demoted=bytes_dem,
            bytes_promoted=bytes_pro,
            evictions=sum(p.stats["cache_evictions"] for p in pools))

    # ------------------------------------------------------------- finish --
    def _emit(self, req: SeqState, toks) -> tuple:
        """Append generated tokens to ``req``, honouring stop tokens and
        the token budget.  Returns (kept tokens, finish_reason or None);
        a stop token IS included in the output (like an EOS) and wins over
        the length bound when both trigger on the same token."""
        kept: List[int] = []
        reason = None
        stop = req.sampling.stop_tokens
        for t in toks:
            t = int(t)
            req.out.append(t)
            kept.append(t)
            if t in stop:
                reason = FINISH_STOP
                break
            if len(req.out) >= req.max_new:
                reason = FINISH_LENGTH
                break
        return kept, reason

    def _result(self, req: SeqState) -> GenerationResult:
        return GenerationResult(
            rid=req.rid, prompt=tuple(req.prompt), tokens=tuple(req.out),
            finish_reason=req.finish_reason or FINISH_LENGTH,
            prefix_hit_tokens=req.prefix_hit_tokens,
            preemptions=req.preemptions, cluster=req.cluster,
            spec_proposed=req.spec_proposed,
            spec_accepted=req.spec_accepted,
            spec_rejected=req.spec_rejected,
            spec_k_final=req.spec_k_cur,
            error=req.error)

    def _finish(self, req: SeqState, reason: str):
        req.done = True
        req.finish_reason = reason
        self.tracer.record_host(EventType.REQUEST_FINISH, req.rid,
                                len(req.out))
        self._pool(req).release(req.rid)
        self.tracer.record_host(EventType.PAGE_RELEASE, req.rid, 0)
        self.lanes[req.lane] = None
        self.active_dev = self.active_dev.at[req.lane].set(0)
        self.len_dev = self.len_dev.at[req.lane].set(0)
        self._h2d(1)
        self.finished.append(self._result(req))

    def _terminate(self, req: SeqState, reason: str, event: str,
                   diag: Optional[str] = None):
        """Single exceptional-finish path: abort / cancel / timeout /
        error-demotion / shed all release the request's resources through
        the same refcount/CoW/reservation-aware route preemption uses
        (``pool.release`` drops every mapping and reservation credit;
        shared pages merely lose this request's refcount) and surface a
        finished result + terminal delta instead of silently dropping
        work.  Works on queued, running and preempted-parked requests."""
        req.done = True
        req.finish_reason = reason
        req.error = diag
        if req.promoting:
            self._land_promotions(force_rid=req.rid)
        if req in self.queue:
            self.queue.remove(req)
        self._pool(req).release(req.rid)
        if req.lane >= 0:
            self.lanes[req.lane] = None
            self.active_dev = self.active_dev.at[req.lane].set(0)
            self.len_dev = self.len_dev.at[req.lane].set(0)
            req.lane = -1
            self._h2d(1)
        # parked payload (if any) is dropped, not restored — no swap-in
        # traffic; discard is a no-op when nothing of ``rid`` is parked
        self.backing.discard(req.rid)
        req.swapped = None
        self.tracer.record_host(EventType.REQUEST_FINISH, req.rid,
                                len(req.out))
        self.tracer.record_host(EventType.PAGE_RELEASE, req.rid, 0)
        self.finished.append(self._result(req))
        self._delta(req.rid, event=event, reason=reason)

    def _fail(self, req: SeqState, diag: str):
        """Per-request ``"error"`` demotion: a persistent (or
        retry-exhausted) fault takes down THIS request, never the
        engine."""
        self.errors += 1
        self._terminate(req, FINISH_ERROR, "error", diag=diag)

    def _abort(self, req: SeqState):
        """Release a still-queued/running request at the iteration cap and
        surface it as a finished-with-``aborted`` result instead of
        silently dropping it."""
        self._terminate(req, FINISH_ABORTED, "abort")

    def _abort_all(self):
        pending = [r for r in self.lanes if r is not None] + list(self.queue)
        for r in pending:
            self._abort(r)

    def cancel(self, rid: int) -> bool:
        """User-initiated cancellation, callable from the streaming
        consumer's loop body (like mid-stream ``submit``): the request —
        queued, running or preempted-parked — finishes with
        ``finish_reason="aborted"``, its pages released through the
        preemption-grade path, and its terminal delta reaches the stream
        on the current drain.  Returns False for unknown/finished rids."""
        for r in list(self.lanes) + list(self.queue):
            if r is not None and r.rid == rid and not r.done:
                self.cancelled += 1
                self._terminate(r, FINISH_ABORTED, "cancel")
                return True
        return False

    def _expired(self, req: SeqState, now: float) -> bool:
        return (req.deadline_iter is not None
                and self.iterations >= req.deadline_iter) or \
               (req.deadline_t is not None and now >= req.deadline_t)

    def _sweep_deadlines(self):
        """Finish every queued/running request whose deadline has passed
        with ``finish_reason="timeout"`` (tokens generated so far are
        kept).  Runs ahead of admission each step, so a timed-out waiter
        never consumes pool capacity it can no longer use."""
        pending = [r for r in self.lanes if r is not None] + list(self.queue)
        if not any(r.deadline_iter is not None or r.deadline_t is not None
                   for r in pending):
            return
        now = self.clock.now()
        for r in pending:
            if self._expired(r, now):
                self.timeouts += 1
                self.tracer.record_host(EventType.REQUEST_TIMEOUT, r.rid,
                                        self.iterations)
                self._terminate(
                    r, FINISH_TIMEOUT, "timeout",
                    diag=f"deadline exceeded at iteration {self.iterations}")

    def _with_retries(self, fn: Callable[[], object], rid: int):
        """Run one backing-store op under the engine's retry policy:
        transient faults retry immediately, up to ``swap_retries`` times;
        persistent faults (and exhausted budgets) re-raise for the caller
        to demote the request.  This loop NEVER sleeps — spacing retries
        out in time is the deferred-resume path (``_defer_resume``),
        which reschedules on the engine clock while other lanes run."""
        attempt = 0
        while True:
            try:
                out = fn()
                if attempt:
                    self.recovered_faults += 1
                return out
            except BackingStoreError as e:
                if not e.transient or attempt >= self.swap_retries:
                    raise
                attempt += 1
                self.fault_retries += 1

    # --------------------------------------------------------------- step --
    def _account_appends(self, active: List[SeqState], n_new: np.ndarray):
        """Host-side page accounting for this iteration's candidate writes:
        allocate (through the RAB translate path) every page the new tokens
        touch, apply any copy-on-write remaps, and push only the dirty
        repeat-padded block-table rows."""
        dirty, self._dirty = self._dirty, set()
        cow_src: List[int] = []
        cow_dst: List[int] = []
        fresh_pages: List[int] = []
        for r in active:
            i = r.lane
            pool = self._pool(r)
            for _ in range(int(n_new[i])):
                lpage, slot = pool.append_token(r.rid)
                if slot == 0:
                    phys = pool.translate(r.rid, lpage)
                    self.tracer.record_host(EventType.PAGE_ALLOC, r.rid, phys)
                    self._bt_host[i, lpage:] = phys
                    fresh_pages.append(self._gpage(r, phys))
                    dirty.add(i)
                for (s, lp, src, dst) in pool.drain_cow():
                    # the writer was remapped off a shared page: patch its
                    # row and queue the device-side payload copy (slab
                    # indices are global; the block table stays pool-local)
                    cow_src.append(self._gpage(r, src))
                    cow_dst.append(self._gpage(r, dst))
                    self._bt_host[i, lp:] = dst
                    dirty.add(i)
                    self.tracer.record_host(EventType.PAGE_COW, s, dst)
        # park payloads of pages the appends just evicted-and-spilled
        # BEFORE the CoW copy / K-V scatter can write into them
        self._drain_tier_ops()
        if self.quant_kv and fresh_pages:
            # a recycled page must not inherit its previous owner's scale:
            # the running-max would only ever grow across pool reuse and
            # quantization precision would decay with pool age
            self.kv_scales = self.kv_scales.at[
                :, jnp.asarray(fresh_pages)].set(0.0)
        if cow_src:
            # one batched on-device page copy, applied before this step's
            # K/V scatter so the write lands in the private copy
            self.kv_pages = self.kv_pages.at[:, jnp.asarray(cow_dst)].set(
                self.kv_pages[:, jnp.asarray(cow_src)])
            if self.quant_kv:
                # the private copy inherits the donor page's scales too
                self.kv_scales = self.kv_scales.at[
                    :, jnp.asarray(cow_dst)].set(
                    self.kv_scales[:, jnp.asarray(cow_src)])
        self._register_prompt_pages(active, n_new)
        # registration may supersede spilled entries; drop them down-tier
        self._drain_tier_ops()
        if dirty:
            rows = sorted(dirty)
            self.bt_dev = self.bt_dev.at[jnp.asarray(rows)].set(
                jnp.asarray(self._bt_host[rows]))
            self._h2d(len(rows))    # one dispatch, len(rows) rows uploaded

    def step(self) -> bool:
        """One engine iteration.  Returns False when fully idle.

        Deltas accumulate on ``self._deltas`` (drained by ``generate()``
        after every step) rather than being cleared here, so events
        recorded *between* iterations — a ``preempt()`` or ``submit()``
        from the caller's generate-loop body — still reach the stream.

        Each part is a tracer span nested in ``engine.step``: ``sweep``,
        ``admit`` (with ``place`` / ``preempt``), ``account``,
        ``dispatch``, ``pull`` and ``emit`` (with ``first_token``)."""
        with self.tracer.span("step"):
            with self.tracer.span("sweep"):
                self._sweep_deadlines()
                self._land_promotions()
            with self.tracer.span("admit"):
                self._admit()
                self._land_promotions()     # zero-latency promotions land
            active = self._runnable()
            if not active and (self.queue or self._promotions):
                # nothing runs and every waiter is deferred (backing off) or
                # gated on an in-flight promotion: park on the clock until the
                # earliest retry/landing comes due, then re-try — otherwise
                # run() would spin on an idle engine (and on a VirtualClock
                # nobody else ever moves time forward)
                waits = [pr["due"] for pr in self._promotions]
                if self.queue:
                    nb = min(r.not_before for r in self.queue)
                    if nb > self.clock.now():
                        waits.append(nb)
                if waits:
                    self.clock.hold_until(min(waits))
                    with self.tracer.span("admit"):
                        self._land_promotions()
                        self._admit()
                    active = self._runnable()
            if not active:
                return bool(self.queue) or bool(self._promotions)
            self.iterations += 1
            t0 = self.clock.now()

            if self._spec_wanted(active):
                drafts, n_spec = self._propose(active)
                if drafts is not None:
                    self._spec_iteration(active, drafts, n_spec, t0)
                    return True

            B, C = self.max_lanes, self.chunk
            n_new = np.zeros((B,), np.int32)
            feed = np.zeros((B, C), np.int32)
            use_last = np.zeros((B,), np.int32)
            prefill = [(r.lane, len(r.prompt) - r.fed) for r in active
                       if r.fed < len(r.prompt)]
            decode_only = not prefill
            alloc: dict = {}
            if prefill:
                # the interleave policy decides how many prompt tokens each
                # prefill-phase lane feeds; decode lanes always advance one
                alloc = dict(self.policy.plan(
                    tuple(prefill), len(active) - len(prefill), C))
                if len(prefill) == len(active) and \
                        not any(alloc.get(ln, rem) for ln, rem in prefill):
                    # a budget policy may starve every prefill lane in a mixed
                    # batch, but an all-prefill iteration that feeds nothing
                    # would never progress: force the oldest lane one chunk
                    alloc[prefill[0][0]] = min(C, prefill[0][1])
            for r in active:
                i = r.lane
                if r.fed < len(r.prompt):
                    n = min(C, len(r.prompt) - r.fed)
                    n = max(0, min(n, int(alloc.get(i, n))))
                    if n:
                        feed[i, :n] = r.prompt[r.fed:r.fed + n]
                        n_new[i] = n
                        self.prefill_tokens += n
                else:
                    n_new[i] = 1
                    use_last[i] = 1     # token is device-resident; no upload

            with self.tracer.span("account"):
                self._account_appends(active, n_new)

            smp = any(not r.sampling.greedy for r in active)
            # slots: the positions the step program computes, B or B x C
            with self.tracer.span(
                    "dispatch", kind="decode" if decode_only else "chunk",
                    tokens=int(n_new.sum()),
                    slots=B if decode_only else B * C):
                if decode_only:
                    # sync-free: every input already lives on device
                    self.last_tok, self.kv_pages, self.kv_scales, \
                        self.len_dev = self._decode_step[smp](
                            self.params, self.kv_pages, self.kv_scales,
                            self.bt_dev, self.len_dev, self.active_dev,
                            self.last_tok, self.seed_dev, self.temp_dev,
                            self.topk_dev, self.topp_dev)
                else:
                    self._h2d(1)            # the prompt-chunk feed bundle
                    self.last_tok, self.kv_pages, self.kv_scales, \
                        self.len_dev = self._chunk_step[smp](
                            self.params, self.kv_pages, self.kv_scales,
                            self.bt_dev, self.len_dev, jnp.asarray(n_new),
                            jnp.asarray(feed), self.last_tok,
                            jnp.asarray(use_last), self.seed_dev,
                            self.temp_dev, self.topk_dev, self.topp_dev)

            with self.tracer.span("pull"):
                tok = np.asarray(self.last_tok)     # one pull per iteration
                self._d2h(1)

            with self.tracer.span("emit"):
                for r in list(active):
                    i = r.lane
                    reason = None
                    kept: List[int] = []
                    if r.fed < len(r.prompt):
                        r.fed += int(n_new[i])
                        if r.fed == len(r.prompt):
                            with self.tracer.span("first_token", rid=r.rid):
                                kept, reason = self._emit(r, [int(tok[i])])
                    else:
                        kept, reason = self._emit(r, [int(tok[i])])
                    if kept or reason:
                        self._delta(r.rid, kept, reason=reason)
                    if reason:
                        self._finish(r, reason)
                self._post_iteration(self.clock.now() - t0)
            return True

    def _post_iteration(self, dt: float):
        """Scheduler watchdog, run after every engine iteration.

        * **EMA straggler flag** (ported from the trainer's elastic-mesh
          watchdog): an iteration slower than ``straggler_factor`` times
          the exponential moving average of recent iterations is flagged
          with a ``DEGRADE(iteration, 3)`` event — diagnostics, not
          termination, since a slow step is usually the store stalling.
        * **Stalled-lane abort**: a lane whose ``(fed, len(out))``
          progress marker has not advanced for ``watchdog_iters``
          iterations is aborted with ``finish_reason="error"`` plus a
          ``DEGRADE(rid, 2)`` event carrying diagnostics — a wedged lane
          must not pin pool pages forever."""
        if self.straggler_factor:
            ema = self._ema_step_s
            # warmup guard: the first iterations pay jit tracing costs
            if ema is not None and self.iterations > 3 and \
                    dt > self.straggler_factor * ema:
                self.straggler_steps += 1
                self.degrades += 1
                self.tracer.record_host(EventType.DEGRADE,
                                        self.iterations, 3)
            alpha = 0.2            # the trainer watchdog's ema_alpha
            self._ema_step_s = dt if ema is None else \
                alpha * dt + (1 - alpha) * ema
        if self.watchdog_iters:
            for r in [r for r in self.lanes if r is not None]:
                if r.promoting:
                    # gated on an in-flight promotion: not stalled, the
                    # landing path resets the marker clock
                    r.progress_iter = self.iterations
                    continue
                marker = (r.fed, len(r.out))
                if marker != r.progress_marker:
                    r.progress_marker = marker
                    r.progress_iter = self.iterations
                elif self.iterations - r.progress_iter >= \
                        self.watchdog_iters:
                    self.degrades += 1
                    self.tracer.record_host(EventType.DEGRADE, r.rid, 2)
                    self._fail(
                        r, f"watchdog: lane {r.lane} made no progress "
                           f"for {self.watchdog_iters} iterations "
                           f"(stuck at fed={r.fed}, out={len(r.out)})")

    # -------------------------------------------------------- speculation --
    def _spec_wanted(self, active: List[SeqState]) -> bool:
        """Draft this iteration?  Only when speculation is configured,
        every active lane is in the decode phase (mixed prefill iterations
        keep the plain chunk path), at least one lane decodes greedily
        (sampled lanes never draft — greedy verification could not accept
        their drafts — but they ride along in the verify step, whose
        bonus-token sampler matches the plain decode step exactly), and
        nothing is waiting for admission — a non-empty queue is preemption
        pressure: lanes should not widen their verify window while other
        work is starved."""
        return (self.spec_k > 0 and not self.queue
                and all(r.fed >= len(r.prompt) for r in active)
                and any(r.sampling.greedy for r in active))

    def _propose(self, active: List[SeqState]):
        """Collect per-lane draft proposals into a fixed-width (B, spec_k)
        matrix (fixed so the verify step compiles once).  Sampled lanes
        never propose; a greedy lane's draft depth is its adaptive
        ``spec_k_cur`` capped by the tokens it still owes
        (``accepted + 1 <= remaining`` must hold, so at most
        ``remaining - 1`` drafts).  Returns (None, None) when no lane
        proposed anything — the plain decode step is strictly cheaper."""
        drafts = np.zeros((self.max_lanes, self.spec_k), np.int32)
        n_spec = np.zeros((self.max_lanes,), np.int32)
        any_draft = False
        for r in active:
            if not r.sampling.greedy:
                continue
            rem = r.max_new - len(r.out)
            cap = min(r.spec_k_cur, rem - 1, self.spec_k)
            if cap <= 0:
                continue
            try:
                d = self.drafter.propose(r.prompt + r.out, cap)[:cap]
            except Exception:
                # a broken drafter is an accelerant, not a dependency:
                # disable speculation for this lane (it decodes plainly
                # from here on) and log the degradation instead of letting
                # the exception crash the engine step
                r.spec_k_cur = 0
                self.degrades += 1
                self.tracer.record_host(EventType.DEGRADE, r.rid, 1)
                continue
            if not d:
                continue
            drafts[r.lane, :len(d)] = d
            n_spec[r.lane] = len(d)
            any_draft = True
        return (drafts, n_spec) if any_draft else (None, None)

    def _spec_iteration(self, active: List[SeqState], drafts: np.ndarray,
                        n_spec: np.ndarray, t0: float):
        """One draft-verify-rollback engine iteration.

        The pool appends all K+1 candidate positions per lane (pages
        allocated, CoW applied — the ordinary append path), the verify
        step scores every position and counts the accepted prefix on
        device, and the host trims each lane back to ``accepted + 1``
        kept tokens: pages wholly beyond the kept length are unmapped and
        re-credited to the reservation.  Device lengths and the last
        sampled token are updated inside the jitted step from the
        acceptance itself, so the only pull is the one verdict array.
        Sampled lanes participate with zero drafts: they advance exactly
        one position-folded sampled token, unchanged from plain decode.
        ``t0`` is the iteration's start on the engine clock."""
        self.spec_iterations += 1
        lens0 = {r.rid: self._pool(r).seq_len[r.rid] for r in active}
        n_new = np.zeros((self.max_lanes,), np.int32)
        for r in active:
            k_i = int(n_spec[r.lane])
            n_new[r.lane] = k_i + 1
            if k_i:
                self.tracer.record_host(EventType.SPEC_PROPOSE, r.rid, k_i)
                self.spec_proposed += k_i
                r.spec_proposed += k_i
        with self.tracer.span("account"):
            self._account_appends(active, n_new)

        K = drafts.shape[1]
        smp = any(not r.sampling.greedy for r in active)
        with self.tracer.span("dispatch", kind="spec",
                              tokens=int(n_new.sum()),
                              slots=self.max_lanes * (K + 1)):
            self._h2d(1)                # the draft feed bundle
            verdict, self.kv_pages, self.kv_scales, self.last_tok, \
                self.len_dev = self._spec_step[smp](
                    self.params, self.kv_pages, self.kv_scales, self.bt_dev,
                    self.len_dev, self.active_dev, self.last_tok,
                    jnp.asarray(drafts), jnp.asarray(n_spec), self.seed_dev,
                    self.temp_dev, self.topk_dev, self.topp_dev)
        with self.tracer.span("pull"):
            v = np.asarray(verdict)     # one pull per iteration
            self._d2h(1)

        with self.tracer.span("emit"):
            for r in list(active):
                i = r.lane
                k_i = int(n_spec[i])
                a = int(v[i, K + 1])
                emitted = [int(t) for t in drafts[i, :a]] + [int(v[i, a])]
                freed = self._pool(r).trim(r.rid, lens0[r.rid] + a + 1)
                kept, reason = self._emit(r, emitted)
                self._delta(r.rid, kept, event="spec" if k_i else "token",
                            data=a, reason=reason)
                if k_i:
                    self.tracer.record_host(EventType.SPEC_ACCEPT, r.rid, a)
                    self.spec_accepted += a
                    r.spec_accepted += a
                    rej = k_i - a
                    if rej:
                        self.spec_rejected += rej
                        r.spec_rejected += rej
                        self.tracer.record_host(EventType.SPEC_ROLLBACK,
                                                r.rid, rej)
                    # adaptive depth: full acceptance earns a wider window,
                    # total rejection halves it (never below 1)
                    if a == k_i:
                        r.spec_k_cur = min(self.spec_k, r.spec_k_cur + 1)
                    elif a == 0:
                        r.spec_k_cur = max(1, r.spec_k_cur // 2)
                if freed:
                    self._refresh_row(i, r)
                if reason:
                    self._finish(r, reason)
            self._post_iteration(self.clock.now() - t0)

    # ---------------------------------------------------------- frontend --
    def poll_deltas(self) -> List[TokenDelta]:
        """Drain every delta accumulated since the last drain.  For
        callers that drive ``step()`` directly (the serving front door)
        instead of consuming the ``generate()`` stream; the two drains
        share one buffer, so use one or the other per engine."""
        out, self._deltas = self._deltas, []
        return out

    def generate(self, requests: Iterable[GenerationRequest] = (),
                 max_iters: Optional[int] = None) -> Iterator[TokenDelta]:
        """Submit ``requests`` and stream the engine: yields a
        :class:`TokenDelta` for every request-visible increment (new
        tokens, prefix-cache hits, preemptions, speculation verdicts) as
        each engine iteration completes, instead of making callers poll
        ``finished``.  The concatenation of a request's token deltas is
        exactly its final ``GenerationResult.tokens``.  When ``max_iters``
        is hit, still-queued/running requests are aborted (surfaced with
        ``finish_reason="aborted"``), never silently dropped.

        Exception-safe: ``break``-ing out of (or ``.close()``-ing) the
        stream leaves the pool consistent and the engine resumable —
        running lanes keep their pages, a later ``generate()``/``run()``
        picks up exactly where the stream stopped, and already-delivered
        deltas are never re-yielded.  ``engine.cancel(rid)`` and
        ``engine.submit(...)`` both work from the loop body."""
        for q in requests:
            self.submit(q)
        it = 0
        while True:
            busy = self.step()
            # drain one delta at a time from the live list: deltas the
            # caller's loop body triggers mid-yield (submit / preempt /
            # cancel) are picked up by the same drain, and a consumer
            # that ``break``s or ``.close()``es the generator mid-stream
            # leaves undelivered deltas queued (never re-yielded) with
            # the engine fully resumable — pool invariants hold between
            # iterations, so generate()/run() can simply be called again
            while self._deltas:
                yield self._deltas.pop(0)
            if not busy:
                return
            it += 1
            if max_iters is not None and it >= max_iters:
                self._abort_all()
                while self._deltas:
                    yield self._deltas.pop(0)
                return

    def run(self, max_iters: int = 10_000) -> List[GenerationResult]:
        """Drain the engine (``generate`` with nobody watching the stream)
        and return every result this engine has produced."""
        for _ in self.generate(max_iters=max_iters):
            pass
        return self.finished


# ===========================================================================
# jitted engine steps
# ===========================================================================

def _write_coords(bt, lens, n_new, C, page_size, trash):
    """Physical (page, slot) for the C candidate token writes of each lane.

    Invalid slots (beyond a lane's n_new) are routed to the trash page so a
    single unmasked scatter covers every lane."""
    n_pages = bt.shape[1]
    pos = lens[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]   # (B,C)
    lp = jnp.minimum(pos // page_size, n_pages - 1)
    sl = pos % page_size
    phys = jnp.take_along_axis(bt, lp, axis=1)
    valid = jnp.arange(C, dtype=jnp.int32)[None, :] < n_new[:, None]
    return jnp.where(valid, phys, trash), sl


def _layer_qkv(cfg, lp, x, pos):
    h = L.norm_forward(cfg, lp["ln1"], x)
    ap = lp["attn"]
    q = jnp.einsum("bsd,dhk->bshk", h, ap["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, ap["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, ap["wv"])
    if cfg.use_qk_norm:
        q = rms_head_norm(ap["q_norm"], q, cfg.norm_eps)
        k = rms_head_norm(ap["k_norm"], k, cfg.norm_eps)
    if cfg.use_rope:
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    return q, k, v


def _layer_mlp(cfg, lp, x):
    h = L.norm_forward(cfg, lp["ln2"], x)
    if "moe" in lp:
        from repro.models import moe as MOE
        return x + MOE.moe_forward(cfg, lp["moe"], h)
    return x + L.mlp_forward(cfg, lp["mlp"], h)


def _sample_tokens(logits, seeds, pos, temps, top_ks, top_ps):
    """Per-lane token selection from (B, V) logits.

    Temperature-0 lanes take exact greedy argmax (the historical engine
    path, byte-identical); sampled lanes divide by temperature, apply
    top-k then top-p truncation and draw categorically with
    ``fold_in(PRNGKey(seed), pos)`` — ``pos`` is the token's absolute
    sequence position, so a lane's draw is reproducible from (seed,
    position) alone no matter how the scheduler chunked, preempted or
    sharded the request."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def one(lg, seed, p, t, tk, tp):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), p)
        V = lg.shape[-1]
        lg = lg / jnp.maximum(t, 1e-6)
        desc = jnp.sort(lg)[::-1]
        kth = desc[jnp.clip(tk - 1, 0, V - 1)]
        lg = jnp.where((tk > 0) & (lg < kth), -jnp.inf, lg)
        probs = jax.nn.softmax(lg)
        sp = jnp.sort(probs)[::-1]
        # nucleus: keep the smallest prefix of descending probs whose mass
        # reaches tp (the mass of strictly-larger probs must be < tp)
        keep = (jnp.cumsum(sp) - sp) < tp
        thresh = jnp.min(jnp.where(keep, sp, jnp.inf))
        lg = jnp.where(probs >= thresh, lg, -jnp.inf)
        return jax.random.categorical(key, lg).astype(jnp.int32)

    sampled = jax.vmap(one)(logits, seeds, pos, temps, top_ks, top_ps)
    return jnp.where(temps > 0.0, sampled, greedy)


def _paged_forward(cfg: ArchConfig, use_kernel: bool,
                   pages_per_step: Optional[int], interpret: bool,
                   num_pages: int, params, kv_pages, kv_scales, bt, lens,
                   n_new, feed, last_tok, use_last, *, axis_name=None,
                   quant=False):
    """Shared forward for the chunk / decode / spec-verify steps: consume up
    to C tokens per lane (prompt chunks from ``feed``; lanes with
    ``use_last`` take the device-resident previous sample at position 0)
    and return the logits at EVERY fed position.

    kv_pages: (L, P+1, 2, page, kv, hd); kv_scales: (L, P+1, 2, kv) f32;
    bt: (B, n_pages) repeat-padded.  Returns (logits (B, C, V), kv_pages,
    kv_scales).

    ``quant`` (compile-time) marks the pool int8: the fused scatter
    quantizes each lane's new K/V under its page's running-max
    per-(page, K/V, head) scale — a grown scale re-packs the page's
    existing bytes under the new scale (untouched pages see factor 1.0
    exactly, so they round-trip losslessly) — and the attention fetch
    (kernel or oracle) dequantizes in-line.  In bf16 mode ``kv_scales``
    flows through untouched (jit DCEs it off the hot path).

    ``axis_name`` names the tensor-parallel head mesh axis when this runs
    as a ``shard_map`` body (sharded engine): q/k/v/o weights and the pool's
    kv-head dim arrive pre-sliced, so the only collective is one psum of the
    attention output per layer — everything else is replicated compute."""
    B, C = feed.shape
    page = kv_pages.shape[3]
    n_pages = bt.shape[1]
    tokens = feed.at[:, 0].set(jnp.where(use_last == 1, last_tok, feed[:, 0]))
    x = L.embed_tokens(cfg, params["embed"], tokens)        # (B,C,d)
    pos = lens[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    new_lens = lens + n_new
    counts = page_counts_for(new_lens, page)
    phys, sl = _write_coords(bt, lens, n_new, C, page, num_pages)
    if not use_kernel:      # the -1-marked table form the oracle expects
        idx = jnp.arange(n_pages, dtype=jnp.int32)[None, :]
        bt_masked = jnp.where(idx < counts[:, None], bt, -1)

    for i in range(cfg.num_layers):
        lp = M._sub(params["layers"], i)
        q, k, v = _layer_qkv(cfg, lp, x, pos)
        kv_new = jnp.stack([k, v], axis=2)          # (B,C,2,Kv,hd)
        if quant:
            # running-max page scales: scatter-max the new tokens' absmax
            # into the touched pages (duplicate-index safe), re-pack pages
            # whose scale grew, then quantize the new tokens in place
            sc_i = kv_scales[i]                     # (P+1,2,Kv)
            tok_scale = headwise_scales(kv_new)     # (B,C,2,Kv)
            new_sc = sc_i.at[phys].max(tok_scale)
            factor = jnp.where(
                new_sc > 0.0, sc_i / jnp.maximum(new_sc, SCALE_EPS), 0.0)
            repacked = jnp.clip(
                jnp.round(kv_pages[i].astype(jnp.float32)
                          * factor[:, :, None, :, None]),
                -127, 127).astype(jnp.int8)
            q_new = quantize_int8(kv_new, new_sc[phys][..., None])
            kv_pages = kv_pages.at[i].set(
                repacked.at[phys, :, sl].set(q_new))
            kv_scales = kv_scales.at[i].set(new_sc)
        else:
            # one fused scatter writes K AND V for all lanes' chunk tokens
            kv_pages = kv_pages.at[i, phys, :, sl].set(kv_new)
        if use_kernel:
            a = paged_prefill_fused(q, kv_pages[i], bt, counts, new_lens,
                                    lens, interpret=interpret,
                                    pages_per_step=pages_per_step,
                                    kv_scales=kv_scales[i] if quant
                                    else None)
        elif quant:
            a = paged_prefill_ref(q, kv_pages[i, :, 0], kv_pages[i, :, 1],
                                  bt_masked, new_lens, lens,
                                  k_scales=kv_scales[i, :, 0],
                                  v_scales=kv_scales[i, :, 1])
        else:
            a = paged_prefill_ref(q, kv_pages[i, :, 0], kv_pages[i, :, 1],
                                  bt_masked, new_lens, lens)
        attn_out = jnp.einsum("bshk,hkd->bsd", a, lp["attn"]["wo"])
        if axis_name is not None:
            # each head shard holds a partial sum over its heads
            attn_out = jax.lax.psum(attn_out, axis_name)
        x = x + attn_out
        x = _layer_mlp(cfg, lp, x)

    x = L.norm_forward(cfg, params["final_norm"], x)
    logits = L.logits_from_hidden(cfg, params["embed"], x)  # (B,C,V)
    return logits, kv_pages, kv_scales


def _paged_chunk_step(cfg: ArchConfig, use_kernel: bool,
                      pages_per_step: Optional[int],
                      interpret: bool, num_pages: int, params, kv_pages,
                      kv_scales, bt, lens, n_new, feed, last_tok, use_last,
                      seeds, temps, top_ks, top_ps, *, axis_name=None,
                      quant=False, sample=True):
    """Consume up to C tokens per lane: prompt chunks from ``feed``, decode
    lanes (``use_last``) from the device-resident previous sample; the next
    token is selected at the last fed position by the per-lane sampling
    policy (greedy argmax for temperature-0 lanes).  ``sample`` is a
    compile-time flag: the host dispatches the False variant when every
    active lane is greedy, so the historical hot path never traces the
    sampler at all.

    Returns (sampled_tokens (B,), kv_pages, kv_scales, new_lens)."""
    logits, kv_pages, kv_scales = _paged_forward(
        cfg, use_kernel, pages_per_step, interpret, num_pages, params,
        kv_pages, kv_scales, bt, lens, n_new, feed, last_tok, use_last,
        axis_name=axis_name, quant=quant)
    row = jnp.maximum(n_new - 1, 0)
    last_logits = jnp.take_along_axis(
        logits, row[:, None, None], axis=1)[:, 0]           # (B,V)
    if sample:
        # the sampled token's absolute position is new_lens: fold there so
        # the draw is chunking/scheduling-independent
        nxt = _sample_tokens(last_logits, seeds, lens + n_new, temps,
                             top_ks, top_ps)
    else:
        nxt = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    nxt = jnp.where(n_new > 0, nxt, last_tok)   # idle lanes keep their token
    return nxt, kv_pages, kv_scales, lens + n_new


def _paged_spec_step(cfg: ArchConfig, use_kernel: bool,
                     pages_per_step: Optional[int],
                     interpret: bool, num_pages: int, params, kv_pages,
                     kv_scales, bt, lens, active, last_tok, drafts, n_spec,
                     seeds, temps, top_ks, top_ps, *, axis_name=None,
                     quant=False, sample=True):
    """Speculative verify step: score all K+1 candidate positions of every
    lane in ONE chunked forward and count the accepted draft prefix.

    The feed is ``[x0, d_1 .. d_K]`` where x0 is the device-resident
    previous sample and d_j are host drafts; lane b uses ``n_spec[b]`` of
    them (the rest are dead weight routed to the trash page by the write
    coords).  Greedy verification: draft d_{j+1} is accepted iff every
    earlier draft was and d_{j+1} equals the greedy token after position j
    — so the accepted prefix plus the bonus token is exactly the plain
    greedy continuation (parity by construction).  The bonus token at
    position ``accepted`` goes through the same position-folded sampler
    the chunk step uses: for the greedy lanes that drafted it IS the
    greedy token, and for sampled lanes riding along with zero drafts it
    is the identical draw plain decode would have made.  Lengths advance
    by ``accepted + 1`` on device; the host applies the same trim to the
    pool.

    Returns (verdict (B, K+2), kv_pages, kv_scales, last_tok, new_lens)
    where ``verdict[:, :K+1]`` holds the per-position verify tokens (with
    the bonus token at column ``accepted``) and ``verdict[:, K+1]`` the
    accepted count."""
    B, K = drafts.shape
    feed = jnp.concatenate([jnp.zeros((B, 1), jnp.int32), drafts], axis=1)
    n_new = jnp.where(active == 1, n_spec + 1, 0)
    logits, kv_pages, kv_scales = _paged_forward(
        cfg, use_kernel, pages_per_step, interpret, num_pages, params,
        kv_pages, kv_scales, bt, lens, n_new, feed, last_tok, active,
        axis_name=axis_name, quant=quant)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    idx = jnp.arange(K, dtype=jnp.int32)[None, :]
    ok = (drafts == greedy[:, :K]) & (idx < n_spec[:, None])
    accepted = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
    new_lens = lens + jnp.where(active == 1, accepted + 1, 0)
    bonus_logits = jnp.take_along_axis(
        logits, accepted[:, None, None], axis=1)[:, 0]      # (B,V)
    if sample:
        bonus = _sample_tokens(bonus_logits, seeds, lens + accepted + 1,
                               temps, top_ks, top_ps)
    else:       # all-greedy batch: the bonus token IS the greedy token
        bonus = jnp.argmax(bonus_logits, axis=-1).astype(jnp.int32)
    last = jnp.where(active == 1, bonus, last_tok)
    toks = greedy.at[jnp.arange(B), accepted].set(last)
    verdict = jnp.concatenate([toks, accepted[:, None]], axis=1)
    return verdict, kv_pages, kv_scales, last, new_lens


def _paged_decode_step(cfg: ArchConfig, use_kernel: bool,
                       pages_per_step: Optional[int],
                       interpret: bool, num_pages: int, params, kv_pages,
                       kv_scales, bt, lens, active, last_tok, seeds, temps,
                       top_ks, top_ps, *, axis_name=None, quant=False,
                       sample=True):
    """One decode token for every active lane, entirely from device state —
    the C=1 case of the chunk step (mirroring paged_decode_fwd, which is the
    C=1 case of the prefill kernel), with every lane fed its device-resident
    previous sample.

    Returns (sampled_tokens (B,), kv_pages, kv_scales, new_lens)."""
    B = lens.shape[0]
    return _paged_chunk_step(
        cfg, use_kernel, pages_per_step, interpret, num_pages, params,
        kv_pages, kv_scales, bt, lens, active, jnp.zeros((B, 1), jnp.int32),
        last_tok, jnp.ones((B,), jnp.int32), seeds, temps, top_ks, top_ps,
        axis_name=axis_name, quant=quant, sample=sample)
