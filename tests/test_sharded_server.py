"""Sharded serving engine: 1-cluster parity with the unsharded engine
(token-for-token, across page sizes), cluster dispatch tracing/balance,
GQA head-shard validation, and — in a subprocess with forced virtual
devices — multi-cluster + head-sharded parity with cluster-local pool
invariants checked every step.  All runs go through the unified
generation API (``EngineConfig`` + ``make_engine``)."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import get_config
from repro.core.analysis import layer1_decode, layer2_cluster_balance
from repro.core.tracing import EventType, TraceBuffer
from repro.kernels.paged_attention.ops import validate_head_sharding
from repro.models import model as M
from repro.runtime import (
    CacheConfig, EngineConfig, GenerationRequest, SamplingParams,
    ShardedPagedServer, make_engine,
)

PROMPTS = [[5, 6, 7, 8, 9, 10, 11], [3, 1, 4, 1, 5], [2, 7], [9, 9, 8]]


def _req(rid, prompt, max_new=4, **sampling):
    return GenerationRequest(rid=rid, prompt=tuple(prompt),
                             sampling=SamplingParams(max_new=max_new,
                                                     **sampling))


def _run(cfg, params, *, page_size, use_kernel, kv_dtype="bf16",
         tracer=None, sharded=False, **kw):
    srv = make_engine(cfg, params, EngineConfig(
        cache=CacheConfig(num_pages=32, page_size=page_size,
                          max_pages_per_seq=8, kv_dtype=kv_dtype),
        max_lanes=2, chunk=4, use_kernel=use_kernel, sharded=sharded,
        **kw),
        tracer=tracer)
    for rid, p in enumerate(PROMPTS):
        srv.submit(_req(rid, p, max_new=4))
    done = srv.run()
    assert len(done) == len(PROMPTS)
    return {r.rid: r.tokens for r in done}, srv


@pytest.mark.parametrize("page_size", [4, 8])
def test_one_cluster_parity_with_unsharded_engine(page_size,
                                                  matrix_use_kernel,
                                                  matrix_kv_dtype):
    """The 1-cluster sharded engine must be token-for-token identical to
    the unsharded engine — same scheduling, same kernels, the mesh
    collapsed to a single device."""
    cfg = get_config("yi-6b").smoke()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    base, _ = _run(cfg, params, page_size=page_size,
                   use_kernel=matrix_use_kernel, kv_dtype=matrix_kv_dtype)
    shard, srv = _run(cfg, params, page_size=page_size,
                      use_kernel=matrix_use_kernel, kv_dtype=matrix_kv_dtype,
                      sharded=True, clusters=1, heads=1)
    assert isinstance(srv, ShardedPagedServer)
    assert shard == base
    srv.cpool.check_invariants()
    assert srv.pool.free_pages() == 32


def test_matrix_engine_combination(matrix_page_size, matrix_use_kernel,
                                   matrix_kv_dtype):
    """The CI matrix's (page size, attention path, KV dtype) cell,
    exercised on the unsharded engine's hot path: chunked admission must
    match token-by-token admission exactly in this configuration."""
    cfg = get_config("yi-6b").smoke()
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    def run(chunk):
        srv = make_engine(cfg, params, EngineConfig(
            cache=CacheConfig(num_pages=32, page_size=matrix_page_size,
                              max_pages_per_seq=8,
                              kv_dtype=matrix_kv_dtype),
            max_lanes=2, chunk=chunk, use_kernel=matrix_use_kernel))
        for rid, p in enumerate(PROMPTS):
            srv.submit(_req(rid, p, max_new=3))
        return {r.rid: r.tokens for r in srv.run()}

    assert run(1) == run(4)


def test_cluster_dispatch_tracing_and_balance(matrix_page_size,
                                              matrix_use_kernel):
    cfg = get_config("yi-6b").smoke()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tracer = TraceBuffer(capacity=1 << 14)
    out, srv = _run(cfg, params, page_size=matrix_page_size,
                    use_kernel=matrix_use_kernel, tracer=tracer,
                    sharded=True, clusters=1)
    events = layer1_decode(tracer.drain())
    kinds = [e.etype for e in events]
    assert kinds.count(EventType.CLUSTER_DISPATCH) == len(PROMPTS)
    assert EventType.ALL_GATHER in kinds
    bal = layer2_cluster_balance(events)
    assert bal["clusters"][0]["dispatches"] == len(PROMPTS)
    assert sorted(bal["clusters"][0]["requests"]) == [0, 1, 2, 3]
    assert bal["all_gathers"] == srv.iterations
    assert bal["balance"] == 1.0
    rep = srv.cluster_report()
    assert rep["clusters"] == 1 and rep["peak_pages_per_cluster"][0] > 0


def test_validate_head_sharding_gqa():
    assert validate_head_sharding(8, 4, 2) == 2
    assert validate_head_sharding(8, 4, 4) == 1
    assert validate_head_sharding(4, 2, 1) == 2
    with pytest.raises(ValueError):
        validate_head_sharding(8, 4, 3)     # splits a GQA group
    with pytest.raises(ValueError):
        validate_head_sharding(8, 4, 8)     # more shards than kv heads
    with pytest.raises(ValueError):
        validate_head_sharding(7, 4, 1)     # H not a multiple of Kv


def test_head_axis_must_divide_kv_heads():
    cfg = get_config("yi-6b").smoke()       # Kv = 2
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        ShardedPagedServer(cfg, params, EngineConfig(
            clusters=1, heads=max(3, len(jax.devices())),
            cache=CacheConfig(num_pages=8, page_size=4,
                              max_pages_per_seq=4),
            max_lanes=1))


_MULTI_CLUSTER_SCRIPT = textwrap.dedent("""
    import os
    import jax
    jax.config.update("jax_platform_name", "cpu")
    assert len(jax.devices()) >= 8, jax.devices()
    from repro.configs import get_config
    from repro.models import model as M
    from repro.runtime import (CacheConfig, EngineConfig,
                               GenerationRequest, SamplingParams,
                               make_engine)

    KV_DTYPE = os.environ.get("REPRO_KV_DTYPE", "bf16")
    cfg = get_config("yi-6b").smoke()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [[5, 6, 7, 8, 9, 10, 11], [3, 1, 4, 1, 5], [2, 7], [9, 9, 8]]

    def run(preempt=False, sampled_rid=None, **kw):
        srv = make_engine(cfg, params, EngineConfig(
            cache=CacheConfig(num_pages=16, page_size=4,
                              max_pages_per_seq=8, kv_dtype=KV_DTYPE),
            max_lanes=2, chunk=4, use_kernel=False, **kw))
        for rid, p in enumerate(prompts):
            sp = SamplingParams(max_new=3) if rid != sampled_rid else \\
                SamplingParams(max_new=3, temperature=0.8, seed=13)
            srv.submit(GenerationRequest(rid=rid, prompt=tuple(p),
                                         sampling=sp))
        if preempt:
            srv.step()
            assert srv.preempt(0)      # forced mid-flight preemption
        it = 0
        while srv.step():
            it += 1
            assert it < 300
            if hasattr(srv, "cpool"):
                srv.cpool.check_invariants()
        return {r.rid: r.tokens for r in srv.finished}, srv

    base, _ = run()
    for C, H in [(2, 1), (4, 1), (2, 2)]:
        out, srv = run(sharded=True, clusters=C, heads=H)
        assert out == base, (C, H)
        used = {r.cluster for r in srv.finished}
        assert len(used) > 1, "workload never spread across clusters"
    out, srv = run(preempt=True, sharded=True, clusters=2)
    assert out == base and srv.preemptions >= 1
    # speculative decoding under shard_map: same token stream, fewer or
    # equal engine iterations, cluster invariants intact every step
    out, srv = run(sharded=True, clusters=2, spec_k=4)
    assert out == base, "2-cluster speculative run diverged"
    assert srv.spec_proposed >= srv.spec_accepted >= 0
    # a sampled lane on a 2-cluster mesh: greedy lanes unchanged, and the
    # sampled stream matches the unsharded engine (position-folded keys
    # never see the mesh)
    sbase, _ = run(sampled_rid=1)
    sout, _ = run(sampled_rid=1, sharded=True, clusters=2)
    assert sout == sbase, "sampled lane diverged across the mesh"
    assert all(sout[r] == base[r] for r in (0, 2, 3)), \\
        "a greedy lane changed because another lane sampled"
    # build_engine draws the parameters straight into the 2x2 mesh's
    # shardings, with the values of the plain draw from the same seed
    from jax.sharding import NamedSharding
    from repro.launch.serve import build_engine
    from repro.parallel.sharding import head_param_pspecs
    srv = build_engine(cfg, EngineConfig(
        cache=CacheConfig(num_pages=16, page_size=4, max_pages_per_seq=8),
        max_lanes=2, clusters=2, heads=2), seed=0)

    def drawn_in_place(a, b, spec):
        assert (a == b).all()
        assert b.sharding.is_equivalent_to(
            NamedSharding(srv.cmesh.mesh, spec), b.ndim)
    jax.tree.map(drawn_in_place, params, srv.params,
                 head_param_pspecs(params))
    wq = srv.params["layers"]["attn"]["wq"]
    assert wq.addressable_shards[0].data.shape[-2] == cfg.num_heads // 2
    print("MULTI_CLUSTER_OK")
""")


def test_multi_cluster_parity_subprocess():
    """2- and 4-cluster (and 2x2 head-sharded) engines match the unsharded
    engine token-for-token, including across a forced preemption and with
    a sampled lane in the mix, and ``build_engine`` draws parameters into
    the 2x2 mesh's shardings — run in a subprocess because the virtual
    device count must be fixed before the first jax import."""
    env = dict(os.environ,
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", _MULTI_CLUSTER_SCRIPT],
                       capture_output=True, text=True, env=env, cwd=".",
                       timeout=900)
    assert "MULTI_CLUSTER_OK" in r.stdout, r.stdout + r.stderr
