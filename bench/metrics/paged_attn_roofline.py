"""Kernel: the paged-attention kernel's share of its roofline in the
window's decode steps.  The least time is what the decode tokens'
attention needs on this chip (lib/flops: each token's whole context of K
and V read once, bound by HBM bandwidth or by compute, whichever is
slower); the time is the device time of the kernel's calls inside
decode-step programs.  Each decode program's lanes and contexts come from
the host step (``bench.step`` span) it started in."""
import bisect

from bench.lib.flops import attn_flops, decode_attn_bytes, least_time_s
from bench.lib.record import window_steps
from bench.lib.trace import step_runs


def read(run):
    tr = run.trace
    if tr is None:
        return None
    s = run.shapes
    reqs = run.by_rid()
    calls = window_steps(run, ran=False)
    spans = [sp for sp in tr.spans if sp[0] == "bench.step"
             and tr.lo <= sp[1] < tr.hi]
    if not spans or len(spans) != len(calls):
        return None
    starts = [sp[1] for sp in spans]
    least = busy = 0.0
    for r in step_runs(tr, s["H"] // s["Kv"]):
        i = bisect.bisect_right(starts, r.start) - 1
        if r.chunk != 1 or i < 0 or r.start > spans[i][2]:
            continue
        ctx = [reqs[rid].prompt_len + before
               for rid, before, _ in calls[i].emitted]
        if not ctx:
            continue
        busy += r.kernel_s
        least += least_time_s(sum(attn_flops(s, c) for c in ctx),
                              sum(decode_attn_bytes(s, c) for c in ctx),
                              run.peaks)
    if busy <= 0 or least <= 0:
        return None
    return 100.0 * least / busy
