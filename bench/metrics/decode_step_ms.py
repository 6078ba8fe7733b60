"""Model step: device time of one decode-step program execution, the mean
over the executions in the traced part of the window.  A decode step is
the program whose paged-kernel calls carry one query token per lane."""
from bench.lib.trace import step_runs


def read(run):
    if run.trace is None:
        return None
    s = run.shapes
    runs = [r for r in step_runs(run.trace, s["H"] // s["Kv"])
            if r.chunk == 1]
    if not runs:
        return None
    return 1e3 * sum(r.end - r.start for r in runs) / len(runs)
