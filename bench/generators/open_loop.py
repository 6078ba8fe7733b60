"""Open-loop traffic: requests arrive on a fixed schedule, whatever the
server does.

Every size is drawn *stratified*: a phase of N requests takes the N
quantiles ``(i + 0.5) / N`` of each distribution, shuffled into one order
fixed by ``SCHEDULE_SEED``.  The pre-roll and the measured window are two
such phases, of ``round(rate * length)`` requests each, and a phase's
Poisson arrivals (exponential gaps) are scaled to fall inside it.  The
run's seed draws the token ids and the sampling seeds only.  So every run
offers the window the same gaps, prompt lengths, output lengths, prefix
choices and sampled requests, in the same order: the order of a few long
prompts sets the TTFT tail, and a seed that reshuffled it would change
the work measured.

Parameters (all lengths in tokens, times in seconds):

    rate_per_s     mean arrivals per second
    preroll_s      arrivals before the measured window (not counted)
    prefix         optional {count, tokens, zipf_s}: shared system prompts,
                   picked with Zipf weights 1/k^s
    prompt         {median, sigma, min, max}: the request's own prompt
                   tokens (after any prefix), lognormal, clipped
    output         {median, sigma, min, max}: tokens generated (max_new;
                   no stop tokens), lognormal, clipped
    sampling       {share, temperature, top_p}: the share of requests
                   sampled; the rest decode greedily

Other keys (the sources of the numbers, the knee sweep) are notes.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Tuple

import numpy as np

#: the one order of every mix's sizes and arrivals
SCHEDULE_SEED = 1


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request of the mix, due ``due`` seconds after traffic starts."""
    rid: int
    due: float
    prompt: Tuple[int, ...]
    max_new: int
    temperature: float
    top_p: float
    seed: int
    group: int          # shared-prefix index, -1 for none
    prefix_len: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_set(spec: dict, n: int) -> np.ndarray:
    """The n stratified draws of a clipped lognormal, in quantile order."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def gap_set(mix: dict, n: int) -> np.ndarray:
    """The n stratified exponential inter-arrival gaps, in quantile
    order."""
    return -np.log1p(-_quantiles(n)) / float(mix["rate_per_s"])


def zipf_counts(count: int, s: float, n: int) -> np.ndarray:
    """How many of n requests use each of ``count`` prefixes: Zipf weights
    1/k^s, rounded by largest remainder so the counts sum to n."""
    w = 1.0 / np.arange(1, count + 1) ** s
    exact = n * w / w.sum()
    base = np.floor(exact).astype(np.int64)
    rest = n - int(base.sum())
    base[np.argsort(-(exact - base), kind="stable")[:rest]] += 1
    return base


def phase_counts(mix: dict, seconds: float) -> Tuple[int, int]:
    """Requests due in the pre-roll and in the measured window."""
    rate = float(mix["rate_per_s"])
    return (int(round(rate * float(mix["preroll_s"]))),
            max(1, int(round(rate * seconds))))


def max_len(mix: dict) -> int:
    """The longest request the mix can send: prompt plus output tokens."""
    pre = mix["prefix"]["tokens"] if mix.get("prefix") else 0
    return pre + int(mix["prompt"]["max"]) + int(mix["output"]["max"])


def _phase(mix: dict, n: int, begin: float, length: float, vocab: int,
           prefixes, order, rng) -> List[tuple]:
    """One phase's n requests, due inside [begin, begin + length): the
    stratified sets of every size in the order ``order`` draws, with
    token ids and sampling seeds from ``rng``."""
    if n == 0:
        return []
    gaps = order.permutation(gap_set(mix, n))
    # the last arrival lands one mean gap before the phase ends
    due = begin + length * np.cumsum(gaps) / (gaps.sum() + gaps.mean())
    prompt_lens = order.permutation(lognormal_set(mix["prompt"], n))
    outputs = order.permutation(lognormal_set(mix["output"], n))
    pre = mix.get("prefix")
    if pre:
        groups = order.permutation(np.repeat(
            np.arange(pre["count"]),
            zipf_counts(pre["count"], float(pre["zipf_s"]), n)))
    else:
        groups = np.full(n, -1)
    smp = mix.get("sampling") or {"share": 0.0}
    sampled = order.permutation(
        np.arange(n) < int(round(float(smp["share"]) * n)))
    seeds = rng.integers(0, 2 ** 31, n)
    out = []
    for i in range(n):
        g = int(groups[i])
        head = prefixes[g] if g >= 0 else ()
        tail = tuple(int(t) for t in rng.integers(1, vocab,
                                                  int(prompt_lens[i])))
        out.append((float(due[i]), head + tail, int(outputs[i]),
                    float(smp["temperature"]) if sampled[i] else 0.0,
                    float(smp["top_p"]) if sampled[i] else 1.0,
                    int(seeds[i]), g, len(head)))
    return out


def generate(mix: dict, *, vocab: int, seed: int,
             seconds: float) -> List[Planned]:
    """The mix's requests for a run that measures ``seconds`` after the
    pre-roll, in order of arrival."""
    n_pre, n_win = phase_counts(mix, seconds)
    preroll = float(mix["preroll_s"])
    order = np.random.default_rng(SCHEDULE_SEED)
    rng = np.random.default_rng(seed)
    pre = mix.get("prefix")
    prefixes = [tuple(int(t) for t in rng.integers(1, vocab, pre["tokens"]))
                for _ in range(pre["count"])] if pre else []
    rows = _phase(mix, n_pre, 0.0, preroll, vocab, prefixes, order, rng) + \
        _phase(mix, n_win, preroll, seconds, vocab, prefixes, order, rng)
    return [Planned(i, *row) for i, row in enumerate(rows)]


def describe(mix: dict, seconds: float) -> str:
    n_pre, n_win = phase_counts(mix, seconds)
    return (f"{n_pre} requests in the pre-roll, {n_win} in the window "
            f"({math.fsum(lognormal_set(mix['prompt'], n_win)) / n_win:.1f}"
            " mean own prompt tokens, "
            f"{math.fsum(lognormal_set(mix['output'], n_win)) / n_win:.1f}"
            " mean output tokens)")
