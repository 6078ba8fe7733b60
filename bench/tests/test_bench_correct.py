"""What decides ``correct``, end to end at CPU size: a sound run passes,
the control (the reference in float8) fails the same limit, and a run
whose served tokens are altered where the engine produces them fails."""
import json
import pathlib
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src"), str(REPO / "bench")]

from bench.lib.harness import Harness  # noqa: E402
from bench.tests.tiny import make_tree  # noqa: E402

PEAKS = json.loads((REPO / "bench" / "peaks.json").read_text())[
    "TPU v5 lite"]
SEEDS = [2 ** 40 + 3, 7]


@pytest.fixture(scope="module")
def harness(tmp_path_factory):
    return Harness(make_tree(tmp_path_factory.mktemp("tiny")))


def run(h, seed, **kw):
    from bench.lib.cell import run_cell
    return run_cell(h, "tiny-chat", seed=seed, seconds=2.0,
                    peaks=PEAKS, t_start=time.monotonic(), **kw)


def test_sound_runs_are_correct_and_the_control_is_not(harness):
    """The program's widest gap sits under the limit on every seed; the
    float8 control's sits above it (bench/control.py's reading)."""
    import control
    rows = control.readings(harness, "tiny-chat", SEEDS, 2.0, PEAKS,
                            log=lambda m: None)
    limit = harness.config("tiny")["check"]["max_logit_gap"]
    for r in rows:
        assert r["correct"] and r["program"] <= limit
        assert r["tokens"] >= 8
        assert r["control"] > limit, r


def _wrap_steps(engine, wrap):
    for table in (engine._decode_step, engine._chunk_step):
        for k in list(table):
            table[k] = wrap(table[k])


def token_altered(vocab):
    """The decode and chunk steps return their sampled token plus one:
    every greedy token served is then not the model's best."""
    def fault(engine):
        def wrap(step):
            def altered(*a):
                tok, *rest = step(*a)
                return ((tok + 1) % vocab, *rest)
            return altered
        _wrap_steps(engine, wrap)
    return fault


def state_unchanged(vocab):
    """The steps hand back the KV pool they were given: no key or value is
    ever written, so every later token attends to an empty cache."""
    def fault(engine):
        def wrap(step):
            def stale(*a):
                tok, _, *rest = step(*a)
                return (tok, a[1], *rest)
            return stale
        _wrap_steps(engine, wrap)
    return fault


@pytest.mark.parametrize("fault", [token_altered, state_unchanged])
def test_a_broken_timed_path_is_not_correct(harness, fault):
    vocab = harness.config("tiny")["vocab_size"]
    r = run(harness, SEEDS[0], trace=False, fault=fault(vocab))
    assert not r["correct"]
    assert r["check"]["max_logit_gap"]["value"] > \
        r["check"]["max_logit_gap"]["limit"]


def test_traced_run_reports_per_layer_metrics(harness, tmp_path):
    r = run(harness, 11, trace=True, trace_dir=str(tmp_path / "trace"))
    assert r["correct"]
    names = set(r["metrics"])
    assert {"submit_lag_p95_ms", "prefix_hit_share", "iteration_ms",
            "step_mfu"} <= names
    assert r["device"]["window_s"] > 0
    assert list(r)[-1] == "check"
    assert not (tmp_path / "trace").exists()


def test_untraced_run_reports_end_to_end_metrics(harness):
    r = run(harness, 12, trace=False)
    assert r["correct"]
    assert set(r["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["attempted"] > 0 and r["failed"] == 0
