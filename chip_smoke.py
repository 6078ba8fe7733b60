#!/usr/bin/env python3
"""Bring-up check: serve yi-6b at its published widths on a TPU through the
normal engine path, with the Pallas paged kernels compiled.

    python3 chip_smoke.py             # one chip: kernel check + engine run
    python3 chip_smoke.py --chips 4   # the sharded engine on four chips

One chip: the paged kernel against its jnp oracle at yi-6b widths (decode
and a 16-token chunk, bf16 and int8 pools), then ``launch.serve``'s
``build_engine`` with the default ``EngineConfig`` (kernel and prefix cache
on) at full depth, serving seeded requests that share a system prompt.
Four chips: the same requests through the one-chip engine, then through
the sharded engine at clusters x heads = 4x1 and 2x2; 4x1 must produce the
one-chip tokens exactly.

The weights are random, drawn from ``--seed``.  Compile seconds, wall
seconds and peak bytes are printed as bring-up facts, not as benchmark
results.  The last line of stdout is ``{"ok": true, "device": {...}}``; a
run without a TPU, or with any failed phase, exits non-zero before it.
"""
import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

LANES, CHUNK, PAGE, MAX_PAGES, POOL_PAGES = 8, 16, 8, 64, 1024
# 1024 pages of yi-6b KV are 0.54 GB: with 12.1 GB of parameters this leaves
# room on a 16 GB chip for the step's undonated second copy of the pool and
# for the engine's eager page copies (copy-on-write, swap-in)
SYSTEM_TOKENS, TAIL_TOKENS, MAX_NEW, REQUESTS = 64, (32, 256), 32, 8
# the four-chip phase checks mesh numerics, which do not depend on depth:
# it keeps every width and cuts the layers, since each of its seconds is
# charged four times
FOUR_CHIP_LAYERS = 8


def log(*a):
    print(*a, flush=True)


class CompileLog:
    """Backend compile durations, by function name, as JAX reports them."""

    def __init__(self):
        import jax.monitoring
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((kw.get("fun_name", "?"), duration))

    def report(self, since=0):
        """Compiles since event ``since``: their count and seconds, listing
        those of a second or more (the step programs and parameter draws;
        the rest are the engine's small eager updates)."""
        ev = self.events[since:]
        big = [(n, round(s, 2)) for n, s in ev if s >= 1.0]
        return (f"{len(ev)} compiles, {sum(s for _, s in ev):.2f} s; "
                f"of 1 s or more: {big}")


# ---------------------------------------------------------------- phases --

def kernel_check(cfg, seed):
    """The Pallas kernel, compiled, against the jnp oracle at ``cfg``'s
    attention widths: decode (C=1) and a 16-token chunk, bf16 and int8."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention import kernel as K
    from repro.kernels.paged_attention.ops import (
        page_counts_for, pad_block_table)
    from repro.kernels.paged_attention.ref import paged_prefill_ref
    from repro.optim.compress import quantize_int8

    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    pool = jax.random.normal(jax.random.fold_in(key, 1),
                             (POOL_PAGES, 2, PAGE, Kv, hd)
                             ).astype(jnp.bfloat16)
    scales = jnp.max(jnp.abs(pool.astype(jnp.float32)), axis=(2, 4)) / 127.0
    pool_i8 = quantize_int8(pool, scales[:, :, None, :, None])
    lengths = rng.integers(CHUNK, MAX_PAGES * PAGE + 1, LANES).astype(
        np.int32)
    counts = page_counts_for(jnp.asarray(lengths), PAGE)
    pages = rng.permutation(POOL_PAGES)[:LANES * MAX_PAGES].reshape(
        LANES, MAX_PAGES)
    marked = jnp.asarray(np.where(
        np.arange(MAX_PAGES)[None, :] < np.asarray(counts)[:, None],
        pages, -1).astype(np.int32))
    padded = pad_block_table(marked, counts)
    # Tolerance: both sides end in a cast to bf16 (8 mantissa bits, so one
    # rounding step is up to 2^-8 of the value) and the kernel may feed the
    # MXU the softmax weights at bf16 (2^-9 relative each); the oracle runs
    # at float32 ("highest").  2^-6 relative plus 2^-6 absolute allows a
    # couple of roundings; a layout or masking fault is off by O(1).
    rtol = atol = 2.0 ** -6
    worst = 0.0
    for C in (1, CHUNK):
        q = jax.random.normal(jax.random.fold_in(key, 2 + C),
                              (LANES, C, H, hd)).astype(jnp.bfloat16)
        start = jnp.asarray(lengths - C)
        for kv_dtype in ("bf16", "int8"):
            quant = kv_dtype == "int8"
            kv = pool_i8 if quant else pool
            out = K.paged_prefill_fwd(
                q, kv, padded, counts, jnp.asarray(lengths), start,
                interpret=False, kv_scales=scales if quant else None)
            with jax.default_matmul_precision("highest"):
                ref = paged_prefill_ref(
                    q, kv[:, 0], kv[:, 1], marked, jnp.asarray(lengths),
                    start, k_scales=scales[:, 0] if quant else None,
                    v_scales=scales[:, 1] if quant else None)
            o = np.asarray(out, np.float32)
            r = np.asarray(ref, np.float32)
            err = float(np.max(np.abs(o - r) / (atol + rtol * np.abs(r))))
            log(f"kernel C={C:2d} {kv_dtype}: shape {o.shape} finite "
                f"{bool(np.isfinite(o).all())} max|diff| "
                f"{float(np.max(np.abs(o - r))):.3e} (normalised {err:.3f})")
            if not np.isfinite(o).all() or err > 1.0:
                raise AssertionError(
                    f"kernel C={C} {kv_dtype} disagrees with the oracle")
            worst = max(worst, err)
    return worst


def make_requests(vocab, seed, wave):
    """``REQUESTS`` seeded requests: a shared system prompt, tails of
    ``TAIL_TOKENS`` tokens, one sampled lane (rid ``wave*REQUESTS + 1``)."""
    import numpy as np
    from repro.runtime import GenerationRequest, SamplingParams

    rng = np.random.default_rng(seed)
    system = rng.integers(1, vocab, SYSTEM_TOKENS).tolist()
    tails = np.random.default_rng([seed, wave])
    out = []
    for i in range(REQUESTS):
        rid = wave * REQUESTS + i
        tail = tails.integers(1, vocab, tails.integers(
            TAIL_TOKENS[0], TAIL_TOKENS[1] + 1)).tolist()
        sampling = SamplingParams(temperature=0.8, top_p=0.9, seed=seed + rid,
                                  max_new=MAX_NEW) if i == 1 else \
            SamplingParams(max_new=MAX_NEW)
        out.append(GenerationRequest(rid=rid, prompt=tuple(system + tail),
                                     sampling=sampling))
    return out


def serve(engine, requests, *, stagger):
    """Stream ``requests`` through ``engine`` and check the results; with
    ``stagger`` the first request runs alone until its first token, so the
    rest find the system prompt in the prefix cache.  Returns
    ``{rid: tokens}``."""
    import jax

    vocab = engine.cfg.vocab_size
    first, rest = (requests[:1], requests[1:]) if stagger else (requests, [])
    streamed = {}
    for d in engine.generate(first):
        streamed.setdefault(d.rid, []).extend(d.tokens)
        if rest and d.tokens:
            for r in rest:
                engine.submit(r)
            rest = []
    jax.block_until_ready((engine.kv_pages, engine.last_tok))
    done = {r.rid: r for r in engine.finished
            if r.rid in {q.rid for q in requests}}
    if len(done) != len(requests):
        raise AssertionError(f"{len(done)} of {len(requests)} finished")
    for rid, r in done.items():
        if r.finish_reason not in ("length", "stop"):
            raise AssertionError(f"req {rid} ended {r.finish_reason}: "
                                 f"{r.error}")
        if list(r.tokens) != streamed.get(rid, []):
            raise AssertionError(f"req {rid}: streamed deltas differ from "
                                 "the result")
        if not all(0 <= t < vocab for t in r.tokens):
            raise AssertionError(f"req {rid}: token outside the vocabulary")
    return {rid: list(r.tokens) for rid, r in done.items()}


def engine_config(**kw):
    from repro.runtime import CacheConfig, EngineConfig
    return EngineConfig(
        cache=CacheConfig(num_pages=POOL_PAGES, page_size=PAGE,
                          max_pages_per_seq=MAX_PAGES),
        max_lanes=LANES, chunk=CHUNK, **kw)


def check_kernel_in_step(engine):
    """The decode program the engine dispatches calls the compiled Pallas
    kernel (a ``tpu_custom_call``; the lowered text defines it once, in
    the kernel's own function, which every layer calls)."""
    lowered = engine._decode_step[False].lower(
        engine.params, engine.kv_pages, engine.kv_scales, engine.bt_dev,
        engine.len_dev, engine.active_dev, engine.last_tok, engine.seed_dev,
        engine.temp_dev, engine.topk_dev, engine.topp_dev)
    if "tpu_custom_call" not in lowered.as_text():
        raise AssertionError("the decode step does not run the Pallas "
                             "kernel")
    log(f"engine decode step ({engine.cfg.num_layers} layers, "
        f"{engine.max_lanes} lanes) lowers to the Pallas kernel "
        "(tpu_custom_call)")


def engine_run(cfg, seed, compiles):
    """``build_engine`` at ``cfg`` with the default ``EngineConfig``: a cold
    wave (compiles included), then waves of fresh requests, the last one
    timed warm."""
    import jax
    from repro.launch.serve import build_engine

    mark = len(compiles.events)
    t0 = time.perf_counter()
    engine = build_engine(cfg, engine_config(), seed=seed)
    jax.block_until_ready(engine.params)
    n_params = sum(x.size for x in jax.tree.leaves(engine.params))
    log(f"engine: {cfg.name} L={cfg.num_layers} d={cfg.d_model} "
        f"H={cfg.num_heads}/{cfg.num_kv_heads} hd={cfg.resolved_head_dim} "
        f"d_ff={cfg.d_ff} V={cfg.vocab_size}: {n_params} parameters, "
        f"use_kernel={engine.use_kernel}, built in "
        f"{time.perf_counter() - t0:.2f} s; {compiles.report(mark)}")
    check_kernel_in_step(engine)

    mark = len(compiles.events)
    t0 = time.perf_counter()
    cold = serve(engine, make_requests(cfg.vocab_size, seed, 0),
                 stagger=True)
    cold_s = time.perf_counter() - t0
    log(f"cold wave: {sum(map(len, cold.values()))} tokens in "
        f"{cold_s:.2f} s wall (compiles included); {compiles.report(mark)}")
    hits = engine.cache_stats().prefix_hit_tokens
    if hits <= 0:
        raise AssertionError("the prefix cache never hit")

    # an unstaggered wave compiles the step variants the staggered one did
    # not need (a decode step without the sampler, once the sampled lane
    # is done); the timed wave after it should then compile nothing
    serve(engine, make_requests(cfg.vocab_size, seed, 1), stagger=False)
    mark, iters = len(compiles.events), engine.iterations
    t0 = time.perf_counter()
    warm = serve(engine, make_requests(cfg.vocab_size, seed, 2),
                 stagger=False)
    warm_s = time.perf_counter() - t0
    tokens = sum(map(len, warm.values()))
    stats = jax.devices()[0].memory_stats() or {}
    log(f"warm wave: {tokens} tokens in {warm_s:.3f} s wall, ended by "
        f"block_until_ready; inside it {compiles.report(mark)}; "
        f"{engine.iterations - iters} iterations; prefix-hit tokens {hits} -> "
        f"{engine.cache_stats().prefix_hit_tokens}")
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
        f"bytes_limit {stats.get('bytes_limit')}")
    log("sample tokens: " + json.dumps(
        {rid: toks[:8] for rid, toks in sorted(cold.items())[:3]}))
    del engine
    gc.collect()


def mesh_check(cfg, seed, compiles):
    """The same requests through the one-chip engine, then the sharded
    engine at 4x1 (must match exactly) and 2x2 (reported)."""
    import jax
    from repro.launch.serve import build_engine

    requests = make_requests(cfg.vocab_size, seed, 0)
    results = {}
    for name, kw in (("1 chip", {}), ("4x1", dict(clusters=4, heads=1)),
                     ("2x2", dict(clusters=2, heads=2))):
        mark = len(compiles.events)
        t0 = time.perf_counter()
        engine = build_engine(cfg, engine_config(**kw), seed=seed)
        results[name] = serve(engine, requests, stagger=False)
        placed = sorted({r.cluster for r in engine.finished})
        log(f"{name}: {sum(map(len, results[name].values()))} tokens in "
            f"{time.perf_counter() - t0:.2f} s wall (build and compiles "
            f"included), clusters used {placed}; {compiles.report(mark)}")
        del engine
        gc.collect()
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]
    log(f"peak_bytes_in_use per chip: {peak}")
    ref = results["1 chip"]
    for name in ("4x1", "2x2"):
        diverged = [(rid, next(i for i, (a, b) in enumerate(
                    zip(ref[rid] + [None], toks + [None])) if a != b))
                    for rid, toks in sorted(results[name].items())
                    if toks != ref[rid]]
        if not diverged:
            log(f"{name}: tokens identical to the one-chip engine")
            continue
        rid, pos = diverged[0]
        log(f"{name}: {len(diverged)} requests diverge; first: req {rid} "
            f"at token {pos}: one chip {ref[rid][pos:pos + 4]} vs "
            f"{name} {results[name][rid][pos:pos + 4]}")
        if name == "4x1":
            raise AssertionError("4x1 tokens differ from the one-chip engine")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 1
    log(f"devices: {len(devices)} x {dev.device_kind}; jax {jax.__version__}"
        f"; compile cache {enable_compile_cache()}")
    compiles = CompileLog()
    cfg = get_config("yi-6b")
    t0 = time.perf_counter()
    if args.chips == 1:
        worst = kernel_check(cfg, args.seed)
        log(f"kernel check passed (worst normalised error {worst:.3f})")
        engine_run(cfg, args.seed, compiles)
    else:
        cfg = dataclasses.replace(cfg, num_layers=FOUR_CHIP_LAYERS)
        mesh_check(cfg, args.seed, compiles)
    log(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
