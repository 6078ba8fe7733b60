"""The open-loop generator: seeded, byte-identical on replay, and true to
its traffic file's statistics."""
import pathlib
import pickle
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.generators import open_loop as G  # noqa: E402
from bench.lib.harness import Harness  # noqa: E402

MIXES = ["chat-shared-system", "chat-unique-sampled"]
SEED = 2 ** 40 + 17
VOCAB = 64000


@pytest.fixture(scope="module")
def harness():
    return Harness()


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_replays_byte_identically(harness, name):
    mix = harness.traffic(name)
    a = G.generate(mix, vocab=VOCAB, seed=SEED, seconds=20)
    b = G.generate(mix, vocab=VOCAB, seed=SEED, seconds=20)
    assert pickle.dumps(a) == pickle.dumps(b)
    c = G.generate(mix, vocab=VOCAB, seed=SEED + 1, seconds=20)
    assert pickle.dumps(a) != pickle.dumps(c)


def in_window(mix, plan, seconds):
    lo = mix["preroll_s"]
    return [p for p in plan if lo <= p.due < lo + seconds]


SCHEDULE = ("due", "max_new", "temperature", "top_p", "group", "prefix_len")


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work(harness, name, monkeypatch):
    """The seed draws token ids and sampling seeds only: every seed's
    requests have the same sizes and arrivals in the same order, and the
    window holds its own stratified set.  ``SCHEDULE_SEED`` sets the
    order."""
    mix = harness.traffic(name)
    runs = [G.generate(mix, vocab=VOCAB, seed=s, seconds=30)
            for s in (1, 2 ** 33 + 1, 99)]
    for field in SCHEDULE:
        cols = [[getattr(p, field) for p in r] for r in runs]
        assert cols[0] == cols[1] == cols[2], field
    lens = [[len(p.prompt) for p in r] for r in runs]
    assert lens[0] == lens[1] == lens[2]
    assert runs[0][0].prompt != runs[1][0].prompt
    assert len(in_window(mix, runs[0], 30)) == G.phase_counts(mix, 30)[1]
    monkeypatch.setattr(G, "SCHEDULE_SEED", G.SCHEDULE_SEED + 1)
    other = G.generate(mix, vocab=VOCAB, seed=1, seconds=30)
    assert [p.max_new for p in other] != [p.max_new for p in runs[0]]
    assert sorted(p.max_new for p in other) == \
        sorted(p.max_new for p in runs[0])


@pytest.mark.parametrize("name", MIXES)
def test_statistics_match_the_traffic_file(harness, name):
    mix = harness.traffic(name)
    seconds = 200
    plan = G.generate(mix, vocab=VOCAB, seed=SEED, seconds=seconds)
    n_pre, n_win = G.phase_counts(mix, seconds)
    n = n_pre + n_win
    assert len(plan) == n
    dues = np.array([p.due for p in plan])
    assert np.all(np.diff(dues) > 0)
    end = mix["preroll_s"] + seconds
    assert end - 2 / mix["rate_per_s"] < dues[-1] < end
    assert np.sum(dues < mix["preroll_s"]) == n_pre
    own = np.array([len(p.prompt) - p.prefix_len for p in plan])
    out = np.array([p.max_new for p in plan])
    for got, spec in ((own, mix["prompt"]), (out, mix["output"])):
        assert got.min() >= spec["min"] and got.max() <= spec["max"]
        assert np.median(got) == pytest.approx(spec["median"], rel=0.02)
        inside = got[(got > spec["min"]) & (got < spec["max"])]
        sigma = np.std(np.log(inside))
        assert sigma == pytest.approx(spec["sigma"], rel=0.15)
    smp = mix["sampling"]
    share = np.mean([p.temperature > 0 for p in plan])
    assert share == pytest.approx(smp["share"], abs=1.0 / n)
    assert {p.top_p for p in plan if p.temperature > 0} == {smp["top_p"]}
    assert all(0 < t < VOCAB for p in plan for t in p.prompt)
    pre = mix.get("prefix")
    if pre:
        counts = np.bincount([p.group for p in plan[n_pre:]],
                             minlength=pre["count"])
        w = 1.0 / np.arange(1, pre["count"] + 1) ** pre["zipf_s"]
        assert np.all(np.abs(counts - n_win * w / w.sum()) <= 1)
        heads = {p.group: p.prompt[:pre["tokens"]] for p in plan}
        assert all(p.prompt[:pre["tokens"]] == heads[p.group]
                   for p in plan)
        assert all(p.prefix_len == pre["tokens"] for p in plan)
    else:
        assert all(p.group == -1 and p.prefix_len == 0 for p in plan)
        assert len({p.prompt[:32] for p in plan}) == n
    assert max(len(p.prompt) + p.max_new for p in plan) <= G.max_len(mix)
