"""One run of one cell: set-up, the measured window, the check, the
metrics.  ``run.py`` calls :func:`run_cell` once the device is known to
be one the peaks table lists; the tests call it on the CPU."""
from __future__ import annotations

import gc
import os
import shutil
import sys
from collections import Counter
from typing import Optional

from bench.lib import check, flops, serve
from bench.lib.record import FAILED, Run


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _limits(conf: dict) -> dict:
    c = conf["check"]
    return dict(limit=float(c["max_logit_gap"]),
                min_tokens=int(c["min_checked_tokens"]))


def run_cell(h, cell_name: str, *, seed: int, seconds: float, trace: bool,
             peaks: dict, t_start: float, trace_dir: Optional[str] = None,
             fault=None, lower: bool = False) -> dict:
    """Set up, measure and check one run; return the result's dict.

    ``fault``, for the tests only, is called with the built engine and
    may break it underneath, to show that the check then fails.
    ``lower`` also reads the control (bench/control.py): the widest gap of
    the tokens the reference computed in float8 would serve, under the
    key ``control``."""
    import jax
    from repro.runtime import make_engine
    from bench.lib.compile_log import CompileLog
    from bench.lib.model import arch_config, draw_params, engine_config

    cell = h.cell(cell_name)
    conf = h.config(cell["config"])
    mix = h.traffic(cell["traffic"])
    gen = h.generator(mix)
    compiles = CompileLog()
    dev = jax.devices()[0]
    cfg = arch_config(conf)
    ecfg = engine_config(conf, gen.max_len(mix),
                         use_kernel=dev.platform == "tpu")
    plan = gen.generate(mix, vocab=cfg.vocab_size, seed=seed,
                        seconds=seconds)
    params = draw_params(cfg, seed)
    engine = make_engine(cfg, params, ecfg)
    if fault is not None:
        fault(engine)
    c = ecfg.cache
    log(f"settings: {cfg.name} L={cfg.num_layers} d={cfg.d_model} "
        f"H={cfg.num_heads}/{cfg.num_kv_heads} hd={cfg.resolved_head_dim} "
        f"ff={cfg.d_ff} V={cfg.vocab_size} {cfg.param_dtype}; lanes "
        f"{ecfg.max_lanes}, pool {c.num_pages} pages of {c.page_size} "
        f"tokens ({c.num_pages * c.page_size} tokens), max_pages_per_seq "
        f"{c.max_pages_per_seq}, chunk {ecfg.chunk}, pages_per_step "
        f"{ecfg.pages_per_step}, prefix cache {c.enable_prefix_cache}, "
        f"kernel {engine.use_kernel}")
    log(f"traffic {mix['name']}: {gen.describe(mix, seconds)}; rate "
        f"{mix['rate_per_s']}/s, pre-roll {mix['preroll_s']} s, window "
        f"{seconds} s")
    serve.warm_up(engine, cfg.vocab_size)
    log(f"warm-up done at {serve.clock() - t_start:.2f} s; "
        f"{compiles.report()}")
    tracing = serve.Tracing(trace_dir if trace else None)
    out = serve.drive(engine, plan, preroll=float(mix["preroll_s"]),
                      seconds=seconds, tracing=tracing, compiles=compiles)
    stats = [d.memory_stats() or {} for d in jax.devices()[:cell["chips"]]]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    limit = max(int(s.get("bytes_limit", 0)) for s in stats)
    due = [r for r in out["requests"] if out["start"] <= r.due < out["end"]]
    failed = sum(1 for r in due if r.finish in FAILED)
    late = [r.submit_t - r.due for r in due if r.submit_t is not None]
    log(f"window: {len(due)} requests due, {failed} failed, "
        f"{sum(1 for r in due if r.finish)} finished, "
        f"{sum(1 for r in due if r.submit_t is None)} never submitted; "
        f"generator lateness max {max(late, default=0.0) * 1e3:.3f} ms; "
        f"compiles inside the window: {len(out['compiles'])} "
        f"{sorted(Counter(n for n, _ in out['compiles']).items())}")
    log(f"peak_bytes_in_use {peak} of bytes_limit {limit}")

    # free the program's state before the reference runs
    del engine, params
    gc.collect()
    ref = h.reference(conf)
    picked = check.sample(out["requests"], out["end"], seed)
    weights = draw_params(cfg, seed) if picked else None
    t_ref = serve.clock()
    gaps, low = check.compare(ref, weights, conf, picked, lower=lower)
    del weights
    gc.collect()
    bad = check.out_of_range(out["requests"], cfg.vocab_size)
    v = check.verdict(gaps, bad=bad, **_limits(conf))
    log(f"reference: {len(picked)} requests, "
        f"{sum(len(r.tokens) for r in picked)} served tokens, "
        f"{serve.clock() - t_ref:.2f} s")

    run = Run(start=out["start"], end=out["end"], requests=out["requests"],
              steps=out["steps"], setup_s=out["start"] - t_start,
              shapes=flops.model_shapes(conf), peaks=peaks,
              cache_start=out["cache_start"], cache_end=out["cache_end"])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"], "memory_peak_bytes": peak}
    result = {"correct": v["correct"], "attempted": len(due),
              "failed": failed}
    breakdown = None
    if trace:
        from bench.lib import trace as T
        run.trace = T.load(trace_dir)
        tr = run.trace
        device["busy_s"] = T.busy_s(tr.ops, tr.lo, tr.hi) / tr.devices
        device["window_s"] = tr.window_s
        breakdown = {
            "device_ops": [[n, s] for n, s in T.top_ops(tr.ops, tr.lo,
                                                         tr.hi)],
            "idle_gaps": [[n, s] for n, s in T.attribute(
                T.idle_gaps(tr.ops, tr.lo, tr.hi), tr.spans)][:10]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in h.metrics(cell_name, trace):
        val = h.reader(m["name"]).read(run)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    if lower:
        result["control"] = check.verdict(
            low, bad=0, **_limits(conf))["numbers"]["max_logit_gap"]
    for name, n in v["numbers"].items():
        rule = ">=" if name == "checked_tokens" else "<="
        log(f"check {name}: {n['value']} (limit {rule} {n['limit']})")
    result["check"] = {k: dict(n, rule=">=" if k == "checked_tokens"
                               else "<=") for k, n in v["numbers"].items()}
    return result


def trace_dir_for(root, cell: str, seed: int) -> str:
    """A fixed place inside the checkout for one traced run's profile."""
    return os.path.join(str(root), "bench", ".out", "trace",
                        f"{cell}-{seed}")
