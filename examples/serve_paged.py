"""Serve a small model through the paged engine's unified generation API:
continuous batching + RAB translation + shared-prefix KV caching +
priority preemption + per-request sampling + live token streaming.

Requests share a common system prompt, so later admissions hit the prefix
cache and skip most of their prefill; one request decodes with
temperature/top-p sampling (on device, seed-reproducible) while the rest
stay greedy; a late high-priority request lands in a deliberately tight
pool and preempts a running lane (its pages swap to the host backing
store and back).  Everything is observed LIVE through
``engine.generate()`` — the stream of ``TokenDelta``s (tokens, prefix
hits, preemptions) is printed as it happens, and its per-request
concatenation is asserted identical to the final ``GenerationResult``s.

    PYTHONPATH=src python examples/serve_paged.py [--requests 8] [--kernel]
"""
import argparse

import jax

from repro.configs import get_config
from repro.core.analysis import layer1_decode, layer2_tlb_transactions, \
    layer2_request_lifecycles, render_timeline
from repro.models import model as M
from repro.runtime import (
    CacheConfig, EngineConfig, GenerationRequest, SamplingParams,
    make_engine,
)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=4,
                    help="prompt tokens consumed per engine iteration "
                         "(chunked prefill)")
    ap.add_argument("--kernel", action="store_true",
                    help="use the Pallas paged-attention kernels "
                         "(interpret mode on CPU; slower but exercises them)")
    ap.add_argument("--no-prefix-cache", action="store_true")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch).smoke()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    srv = make_engine(cfg, params, EngineConfig(
        cache=CacheConfig(num_pages=24, page_size=4,
                          max_pages_per_seq=16,
                          enable_prefix_cache=not args.no_prefix_cache),
        max_lanes=2, chunk=args.chunk, use_kernel=args.kernel))
    system = [9, 9, 8, 2, 5, 5, 1, 3]          # the shared "system prompt"
    requests = []
    for rid in range(args.requests):
        # one sampled lane in the greedy crowd: rid 1 decodes at
        # temperature 0.7 with nucleus truncation, reproducible from seed
        sampling = SamplingParams(temperature=0.7, top_p=0.9, seed=11,
                                  max_new=6) if rid == 1 else \
            SamplingParams(max_new=6)
        requests.append(GenerationRequest(rid=rid, prompt=system + [20 + rid],
                                          sampling=sampling))

    streamed: dict = {}
    stream = srv.generate(requests)
    for i, delta in enumerate(stream):
        streamed.setdefault(delta.rid, []).extend(delta.tokens)
        tag = f" [{delta.event}]" if delta.event != "token" else ""
        fin = f" -> {delta.finish_reason}" if delta.finish_reason else ""
        print(f"delta {i:3d}: req {delta.rid} +{list(delta.tokens)}"
              f"{tag}{fin}")
        if i == 8:
            # a late VIP request into a busy pool: the scheduler preempts a
            # lane; submissions can land mid-stream
            srv.submit(GenerationRequest(
                rid=99, prompt=[4, 2] * 8, priority=5,
                sampling=SamplingParams(max_new=6)))
            print("delta   —: submitted VIP req 99 mid-stream")
    done = srv.finished

    # the streamed deltas ARE the results — token-for-token
    assert {r.rid: list(r.tokens) for r in done} == streamed, \
        "delta concatenation diverged from final results"

    print(f"\n# served {len(done)} requests (lanes=2, pages=24x4, "
          f"chunk={args.chunk}) in {srv.iterations} engine iterations "
          f"(h2d={srv.h2d_events}, d2h={srv.d2h_events}, "
          f"preemptions={srv.preemptions})")
    for r in sorted(done, key=lambda r: r.rid):
        tag = f" [prefix hit {r.prefix_hit_tokens} tok]" \
            if r.prefix_hit_tokens else ""
        tag += f" [preempted x{r.preemptions}]" if r.preemptions else ""
        print(f"req {r.rid}: prompt {list(r.prompt)} -> {list(r.tokens)} "
              f"[{r.finish_reason}]{tag}")
    print("\n# RAB:", srv.rab.stats)
    print("# pool:", srv.pool.stats)
    print(f"# backing store: {srv.backing.bytes_out} B out, "
          f"{srv.backing.bytes_in} B in")
    events = layer1_decode(srv.tracer.drain())
    print(f"\n# {len(events)} events ({srv.tracer.dropped} dropped); "
          "TLB transactions (first 10):")
    for tx in layer2_tlb_transactions(events)[:10]:
        print(tx)
    print("\n# request lifecycles (admit/preempt/swap_in/finish):")
    for rid, spans in sorted(layer2_request_lifecycles(events).items()):
        print(f"req {rid}: " + " -> ".join(s["kind"] for s in spans))
    print("\n# timeline (truncated)")
    print(render_timeline(events, max_rows=12)[:2000])


if __name__ == "__main__":
    main()
