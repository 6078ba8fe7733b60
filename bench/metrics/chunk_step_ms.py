"""Model step: device time of one chunk-step program execution
(``jit_paged_chunk_step_greedy`` or ``_sampled``, as the engine names
it), the mean over the executions inside the traced window.  None for a
program whose step programs carry no such name."""
PREFIX = "jit_paged_chunk_step_"


def read(run):
    tr = run.trace
    if tr is None:
        return None
    runs = [e - s for n, s, e in tr.modules
            if n.startswith(PREFIX) and tr.lo <= s and e <= tr.hi]
    return 1e3 * sum(runs) / len(runs) if runs else None
