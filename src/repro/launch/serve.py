"""Serving launcher: the paged continuous-batching engine behind the
unified generation API.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
        --requests 8 --max-new 8 [--smoke] [--temperature 0.8 --top-p 0.9 \
        --top-k 40 --seed 7 --stop 13 --stop 17]

Serves the architecture at its published widths and depth with random
weights; ``--smoke`` selects the reduced same-family config instead.  On a
TPU the attention runs in the Pallas paged kernels; elsewhere it runs in
the jnp oracle (the kernels' reference).
"""
import argparse
import dataclasses
from typing import Optional

import jax

from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.core.analysis import layer1_decode, layer2_tlb_transactions
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.models import model as M
from repro.parallel.sharding import head_param_sharding
from repro.runtime import (
    CacheConfig, EngineConfig, GenerationRequest, SamplingParams,
    make_engine,
)


def build_engine(cfg: ArchConfig, engine_cfg: EngineConfig, *,
                 seed: int = 0, tracer=None):
    """The engine a serving entry point runs: random parameters drawn from
    ``seed`` straight into the placement the engine serves them from (the
    default device, or the sharded engine's mesh, which is built here and
    handed to the engine), then :func:`~repro.runtime.make_engine`."""
    sharding = None
    if engine_cfg.wants_sharded:
        mesh = engine_cfg.mesh if engine_cfg.mesh is not None else \
            make_serving_mesh(engine_cfg.clusters, engine_cfg.heads)
        engine_cfg = dataclasses.replace(engine_cfg, mesh=mesh)
        sharding = head_param_sharding(mesh.mesh)
    params = M.init_params(cfg, jax.random.PRNGKey(seed), sharding)
    return make_engine(cfg, params, engine_cfg, tracer=tracer)


def main(argv: Optional[list] = None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--pages", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: n-gram drafter proposes up "
                         "to K tokens per lane per iteration, verified in "
                         "one chunked step (0 disables; greedy lanes only)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy argmax; > 0 samples on device with a "
                         "per-request PRNG key folded by position")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest logits (0 disables)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus truncation mass (1.0 disables)")
    ap.add_argument("--seed", type=int, default=0,
                    help="per-request sampling seed (request i uses "
                         "seed + i so lanes differ but stay reproducible)")
    ap.add_argument("--stop", type=int, action="append", default=None,
                    help="stop token id; repeatable — any of them ends a "
                         "request with finish_reason='stop'")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    srv = build_engine(cfg, EngineConfig(
        cache=CacheConfig(num_pages=args.pages,
                          page_size=args.page_size,
                          max_pages_per_seq=16),
        max_lanes=args.lanes,
        use_kernel=jax.default_backend() == "tpu",
        spec_k=args.spec_k))
    requests = [
        GenerationRequest(
            rid=rid, prompt=(rid + 1, 3, 5),
            sampling=SamplingParams(
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p, seed=args.seed + rid,
                stop_tokens=tuple(args.stop or ()), max_new=args.max_new))
        for rid in range(args.requests)
    ]
    for _ in srv.generate(requests):
        pass                        # drain the stream; results accumulate
    for r in srv.finished:
        print(f"req {r.rid}: {list(r.prompt)} -> {list(r.tokens)} "
              f"[{r.finish_reason}]")
    print("RAB:", srv.rab.stats)
    if args.spec_k:
        gen = sum(len(r.tokens) for r in srv.finished)
        print(f"spec: proposed={srv.spec_proposed} "
              f"accepted={srv.spec_accepted} rejected={srv.spec_rejected} "
              f"iters/token={srv.iterations / max(gen, 1):.2f}")
    events = layer1_decode(srv.tracer.drain())
    print(f"{len(events)} trace events ({srv.tracer.dropped} dropped); "
          f"{len(layer2_tlb_transactions(events))} TLB transactions")


if __name__ == "__main__":
    main()
