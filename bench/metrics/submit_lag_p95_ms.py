"""Front door: 95th percentile, nearest rank, of how late each request due
in the window reached ``engine.submit()`` after its due time (window end
for one never submitted).  The host loop is single-threaded, so this is
the wait behind the engine step in progress."""
from bench.lib.record import percentile


def read(run):
    lags = [((r.submit_t if r.submit_t is not None else run.end) - r.due)
            for r in run.due_in_window()]
    p = percentile(lags, 95)
    return None if p is None else p * 1e3
