"""Model step: the model FLOPs of the tokens the model ran in the window
over the seconds its iterations took (host clock, ``engine.step()`` and
the ``poll_deltas()`` after it) times the chip's peak (bf16).  So a step
that gets faster reads higher, even where the load is fixed and the
engine idles more.  Tokens run are a finished prompt's tokens the prefix
cache did not serve, counted when its first token comes, and every
decoded token; each costs 2 FLOPs per layer and head weight plus
attention over its context (the model module's ``token_flops``)."""
from bench.lib.record import window_steps


def prefill_flops(m, s, prompt_len, hit):
    """The prompt positions hit .. prompt_len-1, each over its causal
    context (position + 1), by model module ``m`` at shapes ``s``."""
    n = prompt_len - hit
    ctx_sum = (prompt_len * (prompt_len + 1) - hit * (hit + 1)) // 2
    return n * m.token_flops(s, 0) + m.attn_flops(s, 1) * ctx_sum


def read(run):
    m, s = run.model, run.shapes
    reqs = run.by_rid()
    total = 0
    seconds = 0.0
    for st in window_steps(run):
        seconds += st.t1 - st.t0
        for rid, before, new in st.emitted:
            r = reqs[rid]
            if before == 0:
                total += prefill_flops(m, s, r.prompt_len, r.hit_tokens)
                before, new = 1, new - 1
            for j in range(new):
                total += m.token_flops(s, r.prompt_len + before + j)
    if total == 0:
        return None
    return 100.0 * total / (seconds * run.peaks["bf16_flops_per_s"])
