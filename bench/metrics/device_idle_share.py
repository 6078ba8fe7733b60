"""Device: the share of the traced window in which no operation ran on the
device (1 - the union of the operations' intervals over the window)."""
from bench.lib import trace as T


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    busy = T.busy_s(tr.ops, tr.lo, tr.hi) / tr.devices
    return 100.0 * (1.0 - busy / tr.window_s)
