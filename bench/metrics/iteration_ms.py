"""Scheduler: host time of one engine iteration, ``engine.step()`` and the
``poll_deltas()`` after it, averaged over the window's iterations (host
clock; the step ends with the pull of its sampled tokens, so it spans the
device's work too)."""
from bench.lib.record import window_steps


def read(run):
    steps = window_steps(run)
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
