"""Event tracing (HERO §2.3.1), adapted to the JAX execution model.

HERO's tracers are hardware blocks that (1) never perturb execution, (2) are
cycle-accurate, (3) use buffers economically, and (4) need no application
changes.  The JAX adaptation has two parts, both cheap enough to stay on,
and each fact lives in one of them:

  * host events (offload begin/end, RAB activity, scheduler decisions) own
    *what* happened and in which order: which request arrived, was placed
    or preempted, which page was allocated, copied or demoted, each token
    pull.  They are rows of one schema, stamped with
    ``time.perf_counter_ns()`` kept strictly increasing, into a buffer
    that saturates at ``capacity`` and counts what it dropped (HERO's
    buffers freeze when full; the analyzer drains between steps, and
    ``drain`` warns with ``TruncatedTrace`` when the stream lost events);
  * spans (``TraceBuffer.span``) own *how long* each engine host phase
    took, against the device: with a ``jax.profiler`` session they land in
    its trace as ``engine.<name>``, on the same clock as the device's
    operations, so an idle gap on the device reads as the host phase that
    holds it; without one a span costs about a microsecond.  A span's
    args are only the request id, which joins a request's spans, and the
    work a dispatch hands the device (``kind``, ``tokens``, ``slots``);
    queue depths, lanes, prefix hits and pages stay with the events.  The
    events' own stamps time the stream where no profiler runs.

Event schema (int64 x 5): (timestamp_ns, tracer_id, event_type, arg0, arg1).
"""
from __future__ import annotations

import enum
import time
import warnings
from typing import List, Tuple

import jax
import numpy as np


class EventType(enum.IntEnum):
    # trainer
    SYNC = 5               # straggler step flagged: (step, 1)
    # RAB / VMM protocol (§3.4)
    TLB_L1_HIT = 10
    TLB_L2_HIT = 11
    TLB_MISS = 12
    MISS_HANDLED = 13
    CORE_SLEEP = 14
    CORE_WAKE = 15
    # offload runtime (§2.2)
    OFFLOAD_BEGIN = 20
    OFFLOAD_COPY_TO = 21
    OFFLOAD_KERNEL_BEGIN = 22
    OFFLOAD_KERNEL_END = 23
    OFFLOAD_COPY_FROM = 24
    OFFLOAD_END = 25
    # scheduler / serving
    PAGE_ALLOC = 30
    PAGE_RELEASE = 31
    REQUEST_ADMIT = 32
    REQUEST_FINISH = 33
    # shared-prefix KV cache + preemption (HERO §2.2/§3.4: SVM pages are
    # remapped, shared and reclaimed without touching the data path)
    PAGE_COW = 34          # copy-on-write: (seq, new physical page)
    PREFIX_HIT = 35        # admission prefix-cache hit: (rid, tokens reused)
    REQUEST_PREEMPT = 36   # (rid, private pages swapped out)
    SWAP_OUT = 37          # D2H page reclamation: (rid, pages)
    SWAP_IN = 38           # H2D page restoration: (rid, pages)
    # host<->device transfers on the serving hot path (the data-path cost
    # HERO's DMA double-buffering / zero-copy SVM exist to hide)
    H2D = 40
    D2H = 41
    # multi-cluster sharded serving (HERO §2.1: the PMCA scales by adding
    # clusters behind one SVM fabric; placement and the cross-cluster token
    # gather are the observable scheduling events)
    CLUSTER_DISPATCH = 42  # request placed on a cluster: (rid, cluster)
    ALL_GATHER = 43        # cross-cluster token gather: (iter, active clusters)
    # speculative decoding (HERO §2.2/§2.3: the lightweight host proposes,
    # the parallel accelerator verifies in bulk; every proposal, acceptance
    # and rollback is an observable scheduling event)
    SPEC_PROPOSE = 44      # drafter proposal: (rid, drafted tokens)
    SPEC_ACCEPT = 45       # verified acceptance: (rid, accepted tokens)
    SPEC_ROLLBACK = 46     # rejected drafts undone: (rid, rejected tokens)
    # fault tolerance (HERO's tracing-driven validation: faults are
    # injected, observed and re-tested through the same event stream the
    # healthy engine emits — no fault may vanish without a trace)
    FAULT_INJECT = 47      # injected fault: (rid, kind code | 8*persistent)
    REQUEST_TIMEOUT = 48   # deadline exceeded: (rid, engine iteration)
    REQUEST_SHED = 49      # admission-time load shed: (rid, queue depth)
    DEGRADE = 50           # graceful degradation: (subject, cause code
    #                        1=drafter disabled, 2=watchdog abort,
    #                        3=straggler iteration flagged)
    # live-traffic serving (the host front door feeding the engine a
    # continuous arrival stream instead of one closed batch)
    REQUEST_ARRIVE = 51    # request entered the queue: (rid, queue depth)
    # hierarchical prefix cache (HERO SVM: host DRAM reachable beyond
    # scratchpad capacity — evicted-but-indexed prefix pages demote to a
    # host tier and, under host pressure, to a disk tier; an admission hit
    # on a non-resident page promotes it back).  args: (entry_id,
    # src_tier * 4 + dst_tier) with tiers 0=device, 1=host, 2=disk,
    # 3=dropped — see core.analysis.layer2_tier_residency
    PAGE_DEMOTE = 52       # cache entry moved down-tier (or dropped)
    PAGE_PROMOTE = 53      # cache entry restored to the device pool


HOST_TRACER_ID = 255


class TruncatedTrace(RuntimeWarning):
    """A drained stream lacks the events recorded while the buffer was
    full: views that need every event (per-request latency, calibration,
    fault containment) would read it wrong."""


class TraceBuffer:
    """Host event buffer of at most ``capacity`` rows; ``dropped`` counts
    the events recorded while it was full."""

    def __init__(self, capacity: int = 4096):
        self.capacity = capacity
        self.host_events: List[Tuple[int, int, int, int, int]] = []
        self._last_ns = 0
        self.dropped = 0
        self._dropped_drained = 0

    def record_host(self, etype: EventType, a0: int = 0, a1: int = 0):
        if len(self.host_events) >= self.capacity:
            self.dropped += 1
            return
        self._last_ns = max(time.perf_counter_ns(), self._last_ns + 1)
        self.host_events.append(
            (self._last_ns, HOST_TRACER_ID, int(etype), int(a0), int(a1)))

    @staticmethod
    def span(name: str, **args) -> jax.profiler.TraceAnnotation:
        """A context manager naming a host phase ``engine.<name>`` in the
        profiler's trace, with ``args`` (ints or short constant strings)
        as its stats.  Build no strings per call and open none per page
        or token."""
        return jax.profiler.TraceAnnotation("engine." + name, **args)

    def drain(self) -> np.ndarray:
        """Freeze-and-drain: the host events as an (N,5) int64 array in
        time order, and an empty buffer.  Warns ``TruncatedTrace`` when
        events were dropped since the last drain."""
        lost = self.dropped - self._dropped_drained
        if lost:
            self._dropped_drained = self.dropped
            warnings.warn(
                f"trace truncated: {lost} host events dropped at capacity "
                f"{self.capacity}; pass a larger TraceBuffer(capacity=)",
                TruncatedTrace, stacklevel=2)
        if not self.host_events:
            return np.zeros((0, 5), np.int64)
        out = np.asarray(self.host_events, np.int64)
        self.host_events = []
        return out
