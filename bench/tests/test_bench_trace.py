"""Trace reduction: interval arithmetic on synthetic traces, and the
loader on a small trace recorded here."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.lib import trace as T  # noqa: E402
from bench.lib.harness import Harness  # noqa: E402
from bench.lib.record import RequestLog, Run, StepLog  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_busy_union_merges_overlaps_and_clips_to_the_window():
    ops = [("a", 0.0, 2.0), ("b", 1.0, 3.0), ("c", 5.0, 6.0),
           ("d", 9.0, 12.0)]
    assert T.busy_s(ops, 1.0, 10.0) == pytest.approx(2.0 + 1.0 + 1.0)
    assert T.idle_gaps(ops, 1.0, 10.0) == [(3.0, 5.0), (6.0, 9.0)]
    assert T.idle_gaps([], 0.0, 2.0) == [(0.0, 2.0)]


def test_idle_share_reader():
    tr = T.Reduced(lo=0.0, hi=10.0, ops=[("x", 1.0, 4.0), ("y", 3.0, 6.0)],
                   modules=[], spans=[])
    run = Run(start=0, end=10, requests=[], steps=[], setup_s=0,
              shapes={}, peaks=PEAKS, trace=tr)
    assert Harness().reader("device_idle_share").read(run) == \
        pytest.approx(50.0)


def test_gaps_are_attributed_to_the_host_spans_they_fell_in():
    gaps = [(1.0, 3.0), (5.0, 6.0)]
    spans = [(T.WINDOW_SPAN, 0.0, 10.0), ("bench.step", 0.5, 2.0),
             ("bench.submit", 2.0, 2.5), ("bench.generator", 5.0, 7.0)]
    got = dict(T.attribute(gaps, spans))
    assert got == pytest.approx({"step": 1.0, "submit": 0.5,
                                 "generator": 1.0, "none": 0.5})


DECODE_CALL = T.label(
    "%paged_prefill_fwd.3 = bf16[2,2,2,16]{3,2,1,0} custom-call()")
CHUNK_CALL = T.label(
    "%paged_prefill_fwd.7 = bf16[2,2,32,16]{3,2,1,0} custom-call()")


def test_kernel_calls_belong_to_their_enclosing_program():
    """Programs are told apart by their kernel calls: one query row per
    KV-head group is a decode step; a call crossing the program's end is
    not its own."""
    tr = T.Reduced(
        lo=0.0, hi=40.0,
        modules=[("jit__unknown(1)", 0.0, 10.0),
                 ("jit__unknown(2)", 12.0, 20.0),
                 ("jit_scatter(3)", 20.5, 20.8),
                 ("jit__unknown(1)", 21.0, 30.0),
                 ("jit__unknown(1)", 39.0, 41.0)],     # past the window
        ops=[(DECODE_CALL, 1.0, 2.0), ("fusion bf16[2]", 2, 3),
             (CHUNK_CALL, 13.0, 14.0), (CHUNK_CALL, 15.0, 15.5),
             (DECODE_CALL, 22.0, 24.0), (DECODE_CALL, 29.5, 30.5),
             (DECODE_CALL, 39.5, 40.0)],
        spans=[])
    runs = T.step_runs(tr, groups=2)
    assert [(r.start, r.chunk, r.kernel_s) for r in runs] == [
        (0.0, 1, 1.0), (12.0, 16, 1.5), (21.0, 1, 2.0)]
    assert T.kernel_rows(CHUNK_CALL) == 32
    assert T.kernel_rows(T.label("%sort.6 = (f32[32,64000]{1,0}) sort()")) \
        is None
    assert CHUNK_CALL == "paged_prefill_fwd bf16[2,2,32,16]"
    assert T.label("%copy.1376 = bf16[32,3624,2,8]{3,2,1,0} copy(x)") == \
        "copy bf16[32,3624,2,8]"


def test_top_ops_sums_by_name():
    ops = [("a", 0, 1), ("b", 1, 4), ("a", 4, 6)]
    assert T.top_ops(ops, 0, 10) == [("a", 3), ("b", 3)] or \
        T.top_ops(ops, 0, 10) == [("b", 3), ("a", 3)]


def test_roofline_and_decode_step_from_a_synthetic_trace():
    """Two traced steps: a decode step over contexts 100 and 300 whose
    kernel calls take 2 ms in all, and which runs on past its host span
    (the host returns once the sampled tokens are out), then a chunk
    step, which does not count.  The share is the least time over the
    kernel time."""
    shapes = dict(L=2, H=4, Kv=2, hd=16, itemsize=2)
    reqs = [RequestLog(rid=1, due=0, prompt_len=99, prefix_len=0,
                       max_new=9, greedy=True),
            RequestLog(rid=2, due=0, prompt_len=298, prefix_len=0,
                       max_new=9, greedy=True)]
    steps = [StepLog(1.0, 1.1, ((1, 1, 1), (2, 2, 1))),
             StepLog(1.2, 1.3, ((1, 2, 1),))]
    tr = T.Reduced(
        lo=100.0, hi=101.0,
        ops=[(DECODE_CALL, 100.02, 100.021),
             (DECODE_CALL, 100.03, 100.031),
             (CHUNK_CALL, 100.22, 100.29)],
        modules=[("jit__unknown(1)", 100.01, 100.05),
                 ("jit__unknown(2)", 100.21, 100.3)],
        spans=[(T.WINDOW_SPAN, 100.0, 101.0),
               ("bench.step", 100.0, 100.04),
               ("bench.step", 100.2, 100.31)])
    h = Harness()
    run = Run(start=0.5, end=2.0, requests=reqs, steps=steps, setup_s=0,
              shapes=shapes, peaks=PEAKS, trace=tr,
              model=h.model({"name": "tiny", "model": "llama"}))
    kv = 2 * 2 * 2 * 16 * 2                 # L * (K, V) * Kv * hd * bytes
    nbytes = kv * (100 + 300) + 2 * (2 * 2 * 4 * 16 * 2)
    flops = 4 * (100 + 300) * 4 * 16 * 2
    least = max(nbytes / PEAKS["hbm_bytes_per_s"],
                flops / PEAKS["bf16_flops_per_s"])
    assert h.reader("paged_attn_roofline").read(run) == \
        pytest.approx(100 * least / 0.002)
    assert h.reader("decode_step_ms").read(run) == pytest.approx(40.0)
    run.trace = None
    assert h.reader("paged_attn_roofline").read(run) is None
    assert h.reader("decode_step_ms").read(run) is None


def test_loader_reads_a_recorded_trace(tmp_path):
    """A real profiler trace, recorded on whatever device is here: the
    window span bounds it and the benchmark's host spans come back."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(str(tmp_path))
    steps = [s for s in tr.spans if s[0] == "bench.step"]
    assert len(steps) == 3
    assert all(tr.lo <= s <= e <= tr.hi for _, s, e in steps)
    assert tr.window_s > 0
