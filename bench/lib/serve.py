"""The measured loop: timed arrivals into the engine's own serving entry.

This is the loop of the program's front door (submit the arrivals that
are due, run one ``engine.step()``, drain ``engine.poll_deltas()``),
bounded by the window and timed on the host's monotonic clock.  A token's
emission time is the time of the ``poll_deltas`` that returned it; a
request's latency counts from when it fell due, so a late generator or a
stalled engine shows in the numbers.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from typing import List, Optional

from bench.lib.record import RequestLog, StepLog

clock = time.monotonic


def to_request(p):
    from repro.runtime import GenerationRequest, SamplingParams
    return GenerationRequest(
        rid=p.rid, prompt=p.prompt,
        sampling=SamplingParams(temperature=p.temperature, top_p=p.top_p,
                                seed=p.seed, max_new=p.max_new))


def warm_up(engine, vocab: int, *, base_rid: int = -1_000_000):
    """Compile the four step programs a mix can reach: the chunk and the
    decode step, each with and without the sampler.  A greedy request runs
    alone (greedy chunk and decode), then a sampled one (sampled chunk
    and decode).  Nothing of it is measured.

    The engine also updates the rows of its device block table eagerly,
    one small program per number of rows changed at once; those are
    compiled here too, on a copy, for 1 to ``max_lanes`` rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.runtime import GenerationRequest, SamplingParams
    table = jnp.zeros(engine.bt_dev.shape, engine.bt_dev.dtype)
    for n in range(1, engine.max_lanes + 1):
        rows = list(range(n))
        jax.block_until_ready(table.at[jnp.asarray(rows)].set(jnp.asarray(
            np.zeros((n, table.shape[1]), np.int32))))
    prompt = tuple(1 + (i * 7919) % (vocab - 1)
                   for i in range(engine.chunk + 3))
    for i, temp in enumerate((0.0, 0.7)):
        req = GenerationRequest(
            rid=base_rid - i, prompt=prompt,
            sampling=SamplingParams(temperature=temp, top_p=0.95,
                                    seed=1 + i, max_new=3))
        for _ in engine.generate([req]):
            pass


class Tracing:
    """Host spans and the profiler.  The profiler starts before the
    pre-roll (starting it takes seconds, which must not fall in the
    window) and stops after the window; the ``bench.window`` span marks
    the window inside the trace.  Off, every span is a no-op."""

    def __init__(self, trace_dir: Optional[str]):
        self.dir = trace_dir
        self.window = None

    def span(self, name: str):
        if self.window is None:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def begin(self):
        if self.dir is None:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # the benchmark's spans only
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def open_window(self):
        if self.dir is None:
            return
        import jax
        from bench.lib.trace import WINDOW_SPAN
        self.window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self.window.__enter__()

    def end(self):
        if self.dir is None:
            return
        import jax
        if self.window is not None:
            self.window.__exit__(None, None, None)
            self.window = None
        jax.profiler.stop_trace()


def drive(engine, plan, *, preroll: float, seconds: float,
          tracing: Tracing, compiles=None):
    """Offer ``plan`` (the generator's requests, by due time) from now on;
    measure from ``preroll`` seconds later for ``seconds``.  Returns the
    request logs, the steps, the window, the cache counters at both ends
    of the window and the compiles inside it."""
    tracing.begin()
    t0 = clock()
    start, end = t0 + preroll, t0 + preroll + seconds
    logs = {p.rid: RequestLog(
        rid=p.rid, due=t0 + p.due, prompt_len=len(p.prompt),
        prefix_len=p.prefix_len, max_new=p.max_new,
        greedy=p.temperature == 0.0, prompt=p.prompt) for p in plan}
    pending = deque(sorted(plan, key=lambda p: (p.due, p.rid)))
    steps: List[StepLog] = []
    cache_start = None
    mark = None
    while True:
        now = clock()
        if cache_start is None and now >= start:
            cache_start = engine.cache_stats()
            mark = compiles.mark() if compiles is not None else None
            tracing.open_window()
        if now >= end:
            break
        with tracing.span("bench.submit"):
            while pending and logs[pending[0].rid].due <= now:
                p = pending.popleft()
                logs[p.rid].submit_t = clock()
                engine.submit(to_request(p))
        it = engine.iterations
        ta = clock()
        with tracing.span("bench.step"):
            busy = engine.step()
        with tracing.span("bench.poll_deltas"):
            deltas = engine.poll_deltas()
        tb = clock()
        emitted = []
        for d in deltas:
            r = logs.get(d.rid)
            if r is None:
                continue
            if d.event == "prefix_hit":
                r.hit_tokens = d.data
            if d.tokens:
                emitted.append((d.rid, len(r.tokens), len(d.tokens)))
                r.tokens.extend(d.tokens)
                r.tok_t.extend([tb] * len(d.tokens))
            if d.finish_reason is not None:
                r.finish, r.finish_t = d.finish_reason, tb
        steps.append(StepLog(ta, tb, tuple(emitted),
                             engine.iterations > it))
        if not busy:
            nxt = logs[pending[0].rid].due if pending else end
            if cache_start is None:
                nxt = min(nxt, start)
            with tracing.span("bench.generator"):
                time.sleep(max(0.0, min(nxt, end) - clock()))
    cache_end = engine.cache_stats()
    tracing.end()
    inside = compiles.since(mark) if compiles is not None and \
        mark is not None else []
    return dict(requests=list(logs.values()), steps=steps, start=start,
                end=end, cache_start=_counts(cache_start),
                cache_end=_counts(cache_end), compiles=inside,
                pending=len(pending))


def _counts(stats) -> dict:
    import dataclasses
    return dataclasses.asdict(stats) if stats is not None else {}
