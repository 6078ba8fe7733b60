"""95th percentile time to first token, nearest rank, over every request
due in the window, timed from its due time; a request with no first token
when the window closes counts with (window end - due)."""
from bench.lib.record import percentile, ttft_s


def read(run):
    p = percentile(ttft_s(run), 95)
    return None if p is None else p * 1e3
