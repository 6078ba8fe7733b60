"""95th percentile of every gap between consecutive output tokens of every
request, for the tokens emitted in the window: a stall shows in each gap
it lengthens, not averaged over a request."""
from bench.lib.record import itl_s, percentile


def read(run):
    p = percentile(itl_s(run), 95)
    return None if p is None else p * 1e3
