"""Tracer tests: the host buffer saturates at its capacity, counts what
it dropped and flags a truncated drain, its clock is strictly increasing
nanoseconds, spans land in the profiler's trace with their args, analysis
layers decode."""
import glob
import os
import time
import warnings

import jax
import pytest

from repro.core.tracing import TraceBuffer, EventType, HOST_TRACER_ID, \
    TruncatedTrace
from repro.core.analysis import layer1_decode, layer2_latency, \
    layer2_per_core, layer2_tlb_transactions, render_timeline


def test_host_buffer_saturates_and_counts_drops():
    tb = TraceBuffer(capacity=100)
    for i in range(10 ** 5):
        tb.record_host(EventType.PAGE_ALLOC, i, 0)
    assert len(tb.host_events) == 100
    assert tb.dropped == 10 ** 5 - 100
    with pytest.warns(TruncatedTrace):
        rows = tb.drain()
    assert rows.shape == (100, 5)
    assert rows[:, 3].tolist() == list(range(100))   # the first kept
    assert (rows[:, 1] == HOST_TRACER_ID).all()
    tb.record_host(EventType.PAGE_RELEASE, 1, 0)      # room again
    assert tb.drain().shape == (1, 5) and tb.dropped == 10 ** 5 - 100


def test_a_truncated_drain_is_flagged_once():
    tb = TraceBuffer(capacity=2)
    for i in range(5):
        tb.record_host(EventType.REQUEST_ARRIVE, i, 0)
    with pytest.warns(TruncatedTrace, match="3 host events dropped"):
        assert tb.drain().shape == (2, 5)
    tb.record_host(EventType.REQUEST_FINISH, 0, 1)
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # nothing lost since: silent
        assert tb.drain().shape == (1, 5)


def test_host_clock_is_strictly_increasing_nanoseconds():
    tb = TraceBuffer(capacity=10_000)
    t0 = time.perf_counter_ns()
    for _ in range(5_000):
        tb.record_host(EventType.H2D, 1, 0)
    first = tb.drain()[:, 0]
    tb.record_host(EventType.D2H, 1, 0)
    t1 = time.perf_counter_ns()
    second = tb.drain()[:, 0]
    ts = first.tolist() + second.tolist()
    assert all(b > a for a, b in zip(ts, ts[1:]))    # across drains too
    assert t0 <= ts[0] and ts[-1] <= t1 + len(ts)


def test_latency_view_reads_host_nanoseconds():
    tb = TraceBuffer()
    tb.record_host(EventType.REQUEST_ARRIVE, 4, 0)
    time.sleep(0.002)
    tb.record_host(EventType.REQUEST_ADMIT, 4, 0)
    tb.record_host(EventType.REQUEST_FINISH, 4, 3)
    r = layer2_latency(layer1_decode(tb.drain()))["requests"][4]
    assert 2_000_000 <= r["queue_delay"] < 2_000_000_000
    assert r["e2e"] == r["queue_delay"] + r["service"]


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("engine.")]


def test_spans_land_in_the_profiler_trace_with_their_args(tmp_path):
    tb = TraceBuffer()
    with tb.span("idle", rid=1):        # no profiler session: a no-op
        pass
    jax.profiler.start_trace(str(tmp_path))
    with tb.span("outer", rid=3):
        with tb.span("inner", kind="chunk", tokens=12):
            pass
    jax.profiler.stop_trace()
    ev = {n: (s, e, a) for n, s, e, a in _host_events(str(tmp_path))}
    assert set(ev) == {"engine.outer", "engine.inner"}
    assert ev["engine.outer"][2] == {"rid": 3}
    assert ev["engine.inner"][2] == {"kind": "chunk", "tokens": 12}
    (os_, oe, _), (is_, ie, _) = ev["engine.outer"], ev["engine.inner"]
    assert os_ <= is_ <= ie <= oe


def test_analysis_layers():
    tb = TraceBuffer()
    tb.record_host(EventType.TLB_MISS, 0, 7)
    tb.record_host(EventType.TLB_L1_HIT, 1, 3)
    tb.record_host(EventType.MISS_HANDLED, 0, 7)
    tb.record_host(EventType.CORE_WAKE, 0, 7)
    events = layer1_decode(tb.drain())
    per_core = layer2_per_core(events)
    assert set(per_core) == {0, 1}
    txs = layer2_tlb_transactions(events)
    kinds = {t["kind"] for t in txs}
    assert kinds == {"miss", "hit_l1"}
    miss = [t for t in txs if t["kind"] == "miss"][0]
    assert miss["latency"] > 0
    assert "core   0" in render_timeline(events)
