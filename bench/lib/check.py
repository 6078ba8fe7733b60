"""What decides ``correct``: the served tokens against the plain reference.

The engine returns tokens, not logits.  So once the window has closed
and the program's state is freed, a sample of the greedy requests the
window finished (drawn from the seed, the longest always in it) runs
through the configuration's reference, prompt and served tokens
together, and each served token's float32 logit is compared with the
largest at its position.  A greedy engine that computes what the
configuration states serves a token within rounding of the best; the
compared number is the widest such gap over the sample.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench.lib.record import RequestLog

#: served tokens the sample aims at, and the most requests it takes
SAMPLE_TOKENS = 384
SAMPLE_REQUESTS = 8


def finished_greedy(requests: List[RequestLog], end: float):
    return [r for r in requests
            if r.greedy and r.finish in ("length", "stop")
            and r.finish_t is not None and r.finish_t < end and r.tokens]


def sample(requests: List[RequestLog], end: float, seed: int,
           tokens: int = SAMPLE_TOKENS,
           most: int = SAMPLE_REQUESTS) -> List[RequestLog]:
    """The longest finished greedy request, then others in an order drawn
    from ``seed`` until ``tokens`` served tokens or ``most`` requests."""
    cand = finished_greedy(requests, end)
    if not cand:
        return []
    cand.sort(key=lambda r: (-(r.prompt_len + len(r.tokens)), r.rid))
    out, rest = [cand[0]], cand[1:]
    order = np.random.default_rng([int(seed), 7]).permutation(len(rest))
    for i in order:
        if sum(len(r.tokens) for r in out) >= tokens or len(out) >= most:
            break
        out.append(rest[i])
    return out


def out_of_range(requests: List[RequestLog], vocab: int) -> int:
    """Served tokens outside the vocabulary, or past a request's budget."""
    bad = 0
    for r in requests:
        bad += sum(1 for t in r.tokens if not 0 <= t < vocab)
        bad += max(0, len(r.tokens) - r.max_new)
    return bad


def compare(reference, weights, conf: dict, picked: List[RequestLog],
            *, lower: bool = False):
    """Per picked request, the served tokens' gaps (and with ``lower`` the
    control's) under the reference."""
    served, low = [], []
    for r in picked:
        g, lg = reference.gaps(weights, conf, r.prompt, r.tokens,
                               lower=lower)
        served.append(g)
        if lower:
            low.append(lg)
    return served, low


def verdict(gaps, *, limit: float, bad: int, min_tokens: int) -> dict:
    """The numbers compared, each with its limit, and whether all hold."""
    n = int(sum(len(g) for g in gaps))
    widest = float(max((float(np.max(g)) for g in gaps if len(g)),
                       default=float("inf")))
    nums = {
        "max_logit_gap": {"value": widest, "limit": limit},
        "checked_tokens": {"value": n, "limit": min_tokens},
        "bad_tokens": {"value": bad, "limit": 0},
    }
    ok = widest <= limit and n >= min_tokens and bad == 0
    return {"correct": ok, "numbers": nums}
