"""Model step: the model FLOPs of the tokens the model ran in the window
over the seconds its iterations took (host clock, ``engine.step()`` and
the ``poll_deltas()`` after it) times the chip's peak (bf16).  So a step
that gets faster reads higher, even where the load is fixed and the
engine idles more.  Tokens run are a finished prompt's tokens the prefix
cache did not serve, counted when its first token comes, and every
decoded token; each costs 2 FLOPs per layer and head weight plus
attention over its context (lib/flops.token_flops)."""
from bench.lib.flops import attn_flops, token_flops
from bench.lib.record import window_steps


def prefill_flops(s, prompt_len, hit):
    """The prompt positions hit .. prompt_len-1, each over its causal
    context (position + 1)."""
    n = prompt_len - hit
    ctx_sum = (prompt_len * (prompt_len + 1) - hit * (hit + 1)) // 2
    return n * token_flops(s, 0) + attn_flops(s, 1) * ctx_sum


def read(run):
    s = run.shapes
    reqs = run.by_rid()
    total = 0
    seconds = 0.0
    for st in window_steps(run):
        seconds += st.t1 - st.t0
        for rid, before, new in st.emitted:
            r = reqs[rid]
            if before == 0:
                total += prefill_flops(s, r.prompt_len, r.hit_tokens)
                before, new = 1, new - 1
            for j in range(new):
                total += token_flops(s, r.prompt_len + before + j)
    if total == 0:
        return None
    return 100.0 * total / (seconds * run.peaks["bf16_flops_per_s"])
