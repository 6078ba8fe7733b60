"""What a run records, and the window arithmetic the metric readers share.

All times are seconds on the host's monotonic clock.  A run's window is
``[start, end)``; a request belongs to the window when it fell due in it,
a token when it was emitted in it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

#: finish reasons that count as failed requests
FAILED = ("error", "shed", "timeout", "aborted")


@dataclasses.dataclass
class RequestLog:
    rid: int
    due: float                  # absolute due time
    prompt_len: int
    prefix_len: int             # shared-prefix tokens the mix put in front
    max_new: int
    greedy: bool
    prompt: Tuple[int, ...] = ()
    submit_t: Optional[float] = None
    tok_t: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finish: Optional[str] = None
    finish_t: Optional[float] = None
    hit_tokens: int = 0         # prompt tokens the prefix cache served


@dataclasses.dataclass
class StepLog:
    """One ``engine.step()`` call, with the ``poll_deltas`` after it."""
    t0: float
    t1: float
    emitted: Tuple[Tuple[int, int, int], ...]   # (rid, tokens before, new)
    ran: bool = True            # the call ran an engine iteration


@dataclasses.dataclass
class Run:
    start: float                # window start
    end: float                  # window end
    requests: List[RequestLog]
    steps: List[StepLog]
    setup_s: float
    shapes: dict                # model sizes, see lib/flops.model_shapes
    peaks: dict                 # the device's row of peaks.json
    cache_start: dict = dataclasses.field(default_factory=dict)
    cache_end: dict = dataclasses.field(default_factory=dict)
    trace: Optional[object] = None      # lib/trace.Reduced, traced runs

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.start <= t < self.end

    def due_in_window(self) -> List[RequestLog]:
        return [r for r in self.requests if self.in_window(r.due)]

    def by_rid(self) -> Dict[int, RequestLog]:
        return {r.rid: r for r in self.requests}


def percentile(xs, q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least q% of the
    sample at or below it.  None for an empty sample."""
    if not xs:
        return None
    s = sorted(xs)
    rank = -(-int(round(q * len(s))) // 100)           # ceil(q * n / 100)
    return s[max(0, min(len(s), rank) - 1)]


def ttft_s(run: Run) -> List[float]:
    """Time to first token of every request due in the window, from its due
    time.  A request with no first token when the window closes counts
    with (window end - due), so a stall cannot hide."""
    out = []
    for r in run.due_in_window():
        first = r.tok_t[0] if r.tok_t else None
        out.append((first if first is not None and first < run.end
                    else run.end) - r.due)
    return out


def itl_s(run: Run) -> List[float]:
    """Every gap between consecutive output tokens of a request, for the
    tokens emitted in the window (the earlier token may precede it)."""
    out = []
    for r in run.requests:
        for a, b in zip(r.tok_t, r.tok_t[1:]):
            if run.in_window(b):
                out.append(b - a)
    return out


def tokens_in_window(run: Run) -> int:
    return sum(1 for r in run.requests for t in r.tok_t if run.in_window(t))


def window_steps(run: Run, ran: bool = True) -> List[StepLog]:
    """The step calls of the window: those that ran an engine iteration,
    or with ``ran=False`` every call, in the order the trace's
    ``bench.step`` spans have them."""
    return [s for s in run.steps
            if (s.ran or not ran) and run.in_window(s.t0)]
