"""Fast dev loop: one train + prefill + decode step per smoke arch on CPU.

Failures are *aggregated*: every arch (and the serving benchmark) runs even
when an earlier step fails, a summary is printed at the end, and the exit
code is non-zero iff anything failed — so CI can run this script directly
and a single broken arch can't mask later ones (or sneak through a
reporting path that swallows the failure).
"""
import os
import sys
import traceback

# force virtual devices before the first jax import so the serving
# benchmark's multi-cluster sweep runs for real (single-device jit work is
# unaffected: it places on device 0)
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs, smoke_shape
from repro.models import model as M
from repro.models import steps as ST

ARCHS = sys.argv[1:] or list_archs()

failures = []

for name in ARCHS:
    cfg = get_config(name).smoke()
    rng = jax.random.PRNGKey(0)
    try:
        params = M.init_params(cfg, rng)
        n = sum(x.size for x in jax.tree.leaves(params))
        # train
        tshape = smoke_shape("train")
        batch = ST.make_batch(cfg, tshape, rng)
        state = ST.init_train_state(cfg, ST.default_opt_cfg(cfg), rng)
        step = jax.jit(ST.make_train_step(cfg))
        state, metrics = step(state, batch)
        loss = float(metrics["loss"])
        # prefill
        pshape = smoke_shape("prefill")
        pbatch = ST.make_batch(cfg, pshape, rng)
        logits = jax.jit(ST.make_prefill_step(cfg))(state["params"], pbatch)
        # decode
        dshape = smoke_shape("decode")
        T = max(cfg.cache_len(dshape), 1)
        cache = M.init_cache(cfg, dshape.global_batch, T)
        dbatch = ST.make_batch(cfg, dshape, rng)
        dlogits, cache = jax.jit(ST.make_decode_step(cfg))(
            state["params"], cache, dbatch)
        ok_nan = (jnp.isfinite(loss) and bool(jnp.isfinite(logits).all())
                  and bool(jnp.isfinite(dlogits).all()))
        print(f"OK   {name:20s} params={n:>9d} loss={loss:8.4f} "
              f"prefill={logits.shape} decode={dlogits.shape} finite={ok_nan}")
        assert ok_nan
    except Exception as e:
        print(f"FAIL {name}: {e}")
        traceback.print_exc()
        failures.append(name)

# the streaming serving example end-to-end: exercises the unified
# generation API (EngineConfig / SamplingParams / generate() deltas) the
# way a user would — it asserts internally that the streamed deltas
# concatenate to the final results.  It runs in this process: a child
# started after this process has touched JAX could not get the chip.
try:
    import contextlib
    import importlib.util
    import io
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "serve_paged", os.path.join(repo, "examples", "serve_paged.py"))
    serve_paged = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve_paged)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        serve_paged.main(["--requests", "4"])
    assert "served 5 requests" in out.getvalue(), out.getvalue()
    print("OK   examples/serve_paged.py (streaming API demo)")
except Exception as e:
    print(f"FAIL serve_paged example: {e}")
    traceback.print_exc()
    failures.append("serve_paged_example")

# serving hot path: chunked prefill vs token-by-token, the shared-prefix
# KV-cache workload (hit rate must be real), the preemption probe, and the
# sharded-engine cluster sweep (1-cluster parity is asserted inside main)
try:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import serve_throughput
    result = serve_throughput.main(["--smoke", "--clusters", "4"])
    sp = result["shared_prefix"]
    assert sp["prefix_hit_rate"] > 0, "no prefix-cache hits in smoke run"
    assert sp["prefix_cached"]["iterations"] < \
        sp["baseline_no_sharing"]["iterations"], \
        "prefix caching did not reduce engine iterations"
    assert result["preemption"]["swap_out_pages"] > 0, \
        "preemption probe swapped nothing"
    sweep = result["cluster_sweep"]
    assert sweep["one_cluster_outputs_match_unsharded"], \
        "sharded engine diverged at 1 cluster"
    sd = result["speculation"]
    assert sd["outputs_match"], "speculative decoding changed outputs"
    assert sd["iters_per_token_reduction"] > 1.0, \
        "speculation did not reduce engine iterations per token"
    sa = result["sampling"]
    assert sa["sampled_reproducible"], "seeded sampling not reproducible"
    assert sa["stop_token_early_exit"], "stop token did not end a request"
    print(f"OK   shared-prefix hit-rate="
          f"{sp['prefix_hit_rate']:.2f} pages_saved={sp['pages_saved']} "
          f"preemption swaps={result['preemption']['swap_out_pages']} "
          f"spec acceptance={sd['acceptance_rate']:.2f} "
          f"sampling reproducible={sa['sampled_reproducible']} "
          f"cluster configs={sorted(sweep['configs'])}")
except Exception as e:
    print(f"FAIL serve_throughput: {e}")
    traceback.print_exc()
    failures.append("serve_throughput")

# analytical capacity planner: replay the bench just produced above
# through the discrete-event simulator and assert the predictions land
# inside the accuracy gate (plan_accuracy sys.exits non-zero otherwise);
# the annotated copy goes to a scratch file so this script mutates
# nothing beyond what serve_throughput already wrote
try:
    import tempfile
    from benchmarks import plan_accuracy
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tf:
        scratch = tf.name
    section = plan_accuracy.main(["--bench", "BENCH_serve.json",
                                  "--out", scratch])
    assert section["capacity_demo"]["slo_met"], \
        "plan_capacity recommendation missed its own SLO"
    print(f"OK   planner accuracy: max gated |rel err| = "
          f"{section['max_gated_abs_rel_err']:.4f} over "
          f"{len(section['gated'])} metrics")
except (Exception, SystemExit) as e:
    print(f"FAIL plan_accuracy: {e}")
    traceback.print_exc()
    failures.append("plan_accuracy")

if failures:
    print(f"SMOKE FAILURES ({len(failures)}): " + ", ".join(failures))
    sys.exit(1)
print("ALL SMOKE OK")
