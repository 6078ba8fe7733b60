"""The benchmark beside a program that names its step programs and opens
``engine.*`` spans: ``chunk_step_ms`` reads the named chunk-step
programs, and the loader reads what it read before."""
import functools
import pathlib
import sys
from collections import Counter

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench.lib import trace as T  # noqa: E402
from bench.lib.harness import Harness  # noqa: E402
from bench.lib.record import Run  # noqa: E402

CELLS = ("yi6b-chat", "nemo12b-chat-unique")


def reduced(modules=(), spans=()):
    return T.Reduced(lo=0.0, hi=10.0, ops=[], modules=list(modules),
                     spans=[(T.WINDOW_SPAN, 0.0, 10.0)] + list(spans))


def read(tr):
    run = Run(start=100.0, end=110.0, requests=[], steps=[], setup_s=0,
              shapes={}, peaks={}, trace=tr)
    return Harness().reader("chunk_step_ms").read(run)


def test_chunk_step_time_reads_named_programs_inside_the_window():
    tr = reduced(modules=[
        ("jit_paged_chunk_step_greedy(1)", 1.1, 1.19),
        ("jit_paged_chunk_step_sampled(2)", 3.1, 3.13),
        ("jit_paged_decode_step_greedy(3)", 4.0, 4.5),
        ("jit_paged_chunk_step_greedy(1)", 9.9, 10.2)])    # past the close
    assert read(tr) == pytest.approx(60.0)


@pytest.mark.parametrize("tr", [
    reduced(modules=[("jit__unknown(1)", 1.0, 2.0)],
            spans=[("bench.step", 0.9, 2.1)]),
    reduced(),
    None], ids=["unnamed-programs", "empty-window", "untraced"])
def test_chunk_step_time_is_absent_without_named_programs(tr):
    """What a program whose step programs lower as ``jit__unknown`` gives,
    or a run with no trace: nothing, and no error."""
    assert read(tr) is None


@pytest.mark.parametrize("sample", [False, True])
def test_reader_prefix_matches_the_engines_chunk_step_name(sample):
    from repro.runtime.server import named_step
    prefix = Harness().reader("chunk_step_ms").PREFIX
    fn = named_step(functools.partial(lambda x: x), "chunk", sample)
    assert ("jit_" + fn.__name__).startswith(prefix)
    assert not ("jit_" + named_step(functools.partial(lambda x: x),
                                    "decode", sample).__name__
                ).startswith(prefix)


@pytest.mark.parametrize("cell", CELLS)
def test_chunk_step_time_applies_to_every_traced_cell(cell):
    names = [m["name"] for m in Harness().metrics(cell, True)]
    assert "chunk_step_ms" in names
    assert "chunk_step_ms" not in [m["name"]
                                   for m in Harness().metrics(cell, False)]


def _record(trace_dir, with_engine):
    import jax
    import jax.numpy as jnp
    from repro.core.tracing import TraceBuffer

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                if with_engine:
                    with TraceBuffer.span("place", rid=i + 1):
                        f(x).block_until_ready()
                else:
                    f(x).block_until_ready()
    jax.profiler.stop_trace()
    return T.load(trace_dir)


def test_loader_reads_the_same_with_engine_spans(tmp_path):
    """The same program traced with and without engine spans reads the
    same operations, programs and benchmark spans, so every metric and
    breakdown read before reads the same on a program that has them."""
    plain = _record(str(tmp_path / "plain"), False)
    spanned = _record(str(tmp_path / "spanned"), True)
    for field in ("ops", "modules", "spans"):
        assert Counter(n for n, _, _ in getattr(plain, field)) == \
            Counter(n for n, _, _ in getattr(spanned, field))
    assert not any(n.startswith("engine.") for n, _, _ in spanned.spans)
    attributed = dict(T.attribute([(spanned.lo, spanned.hi)],
                                  spanned.spans))
    assert set(attributed) <= {"step", "none"}
