"""The window arithmetic behind the end-to-end metrics."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.lib.harness import Harness  # noqa: E402
from bench.lib.record import (  # noqa: E402
    RequestLog, Run, StepLog, itl_s, percentile, tokens_in_window, ttft_s)


def req(rid, due, toks, **kw):
    return RequestLog(rid=rid, due=due, prompt_len=10, prefix_len=0,
                      max_new=len(toks) + 1, greedy=True,
                      tok_t=list(toks), tokens=[1] * len(toks), **kw)


def make_run(requests, steps=()):
    return Run(start=10.0, end=20.0, requests=requests, steps=list(steps),
               setup_s=3.5, shapes={}, peaks={})


def read(name, run):
    return Harness().reader(name).read(run)


@pytest.mark.parametrize("q,xs,want", [
    (95, list(range(1, 21)), 19),
    (95, list(range(1, 101)), 95),
    (50, [3, 1, 2, 4], 2),
    (100, [5, 1], 5),
    (95, [7.0], 7.0),
])
def test_nearest_rank_percentile(q, xs, want):
    assert percentile(xs, q) == want


def test_percentile_of_nothing_is_none():
    assert percentile([], 95) is None


def test_ttft_counts_requests_due_in_the_window_and_censors_stalls():
    run = make_run([
        req(0, 9.0, [9.5, 10.5]),       # due before the window: not counted
        req(1, 11.0, [11.25, 12.0]),    # 0.25 s
        req(2, 12.0, []),               # no token: counted as 20 - 12 = 8 s
        req(3, 19.0, [21.0]),           # first token after the close: 1 s
        req(4, 20.0, [20.5]),           # due at the close: outside
    ])
    assert sorted(ttft_s(run)) == [0.25, 1.0, 8.0]
    assert read("ttft_p95_ms", run) == 8000.0


def test_itl_takes_every_gap_of_tokens_emitted_in_the_window():
    run = make_run([
        req(0, 5.0, [9.0, 10.5, 10.6, 13.0, 20.5]),
        req(1, 11.0, [11.5, 11.7]),
    ])
    # 9.0 -> 10.5 ends in the window and counts; 13.0 -> 20.5 does not
    assert sorted(round(g, 6) for g in itl_s(run)) == [0.1, 0.2, 1.5, 2.4]
    assert read("itl_p95_ms", run) == pytest.approx(2400.0)


def test_tokens_per_second_over_the_window():
    run = make_run([req(0, 5.0, [9.0, 10.0, 15.0, 19.999, 20.0]),
                    req(1, 11.0, [12.0])])
    assert tokens_in_window(run) == 4
    assert tokens_in_window(run) / run.seconds == pytest.approx(0.4)
    assert read("setup_s", run) == 3.5


def test_iteration_and_submit_lag_readers():
    steps = [StepLog(9.0, 9.1, ()), StepLog(10.0, 10.03, ()),
             StepLog(11.0, 11.05, ()), StepLog(12.0, 12.001, (), ran=False)]
    run = make_run([req(0, 10.0, [], submit_t=10.002),
                    req(1, 12.0, [], submit_t=12.5),
                    req(2, 19.5, [])], steps)
    assert read("iteration_ms", run) == pytest.approx(40.0)
    assert read("submit_lag_p95_ms", run) == pytest.approx(500.0)


def test_prefix_hit_share_from_the_engine_counters():
    run = make_run([])
    zero = dict(hits_device_pages=0, hits_host_pages=0, hits_disk_pages=0,
                miss_pages=0)
    run.cache_start = dict(zero, hits_device_pages=10, miss_pages=5)
    run.cache_end = dict(zero, hits_device_pages=40, hits_host_pages=2,
                         miss_pages=37)
    assert read("prefix_hit_share", run) == pytest.approx(50.0)
    run.cache_end = dict(run.cache_start)
    assert read("prefix_hit_share", run) is None
