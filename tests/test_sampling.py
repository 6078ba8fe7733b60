"""The unified generation API: on-device sampling determinism (same seed
=> identical streams across the kernel/ref attention paths, page sizes
and the 1-cluster sharded engine), temperature-0 greedy byte-parity,
top-k/top-p semantics, finish reasons (stop / length / aborted),
streaming deltas whose concatenation equals the final results, and the
``make_engine`` factory + ``EngineConfig``/``SamplingParams``
validation."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.runtime import (
    CacheConfig, EngineConfig, GenerationRequest, GenerationResult,
    PagedServer, SamplingParams, ShardedPagedServer, TokenDelta,
    make_engine,
)

MAX_NEW = 8


@pytest.fixture(scope="module")
def cfg():
    return get_config("yi-6b").smoke()


@pytest.fixture(scope="module")
def params(cfg):
    return M.init_params(cfg, jax.random.PRNGKey(0))


def _prompts(vocab, n=3, seed=2):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=ln).tolist()
            for ln in rng.integers(3, 11, size=n)]


def _serve(cfg, params, prompts, sampling_for, *, page_size=4,
           use_kernel=False, sharded=False, chunk=4, **kw):
    srv = make_engine(cfg, params, EngineConfig(
        cache=CacheConfig(num_pages=32, page_size=page_size,
                          max_pages_per_seq=8),
        max_lanes=2, chunk=chunk, use_kernel=use_kernel, sharded=sharded,
        **kw))
    for rid, p in enumerate(prompts):
        srv.submit(GenerationRequest(rid=rid, prompt=tuple(p),
                                     sampling=sampling_for(rid)))
    done = srv.run()
    assert len(done) == len(prompts)
    return {r.rid: r.tokens for r in done}, srv


def _sampled(rid, seed_base=40, temperature=0.8, top_p=0.9, **kw):
    return SamplingParams(temperature=temperature, top_p=top_p,
                          seed=seed_base + rid, max_new=MAX_NEW, **kw)


# ------------------------------------------------------------ determinism --

@pytest.mark.parametrize("page_size", [4, 8])
def test_same_seed_identical_across_kernel_ref_and_sharded(cfg, params,
                                                           page_size):
    """Same seed => identical sampled streams on the ref path, the Pallas
    kernel path, and the 1-cluster sharded engine: the PRNG key folds by
    (seed, position) only, so neither the attention implementation nor
    the mesh may perturb a request's stream."""
    prompts = _prompts(cfg.vocab_size)
    ref, _ = _serve(cfg, params, prompts, _sampled, page_size=page_size)
    ref2, _ = _serve(cfg, params, prompts, _sampled, page_size=page_size)
    assert ref == ref2, "sampled decoding not reproducible"
    kern, _ = _serve(cfg, params, prompts, _sampled, page_size=page_size,
                     use_kernel=True)
    assert kern == ref, "kernel path diverged from ref under sampling"
    shard, srv = _serve(cfg, params, prompts, _sampled, page_size=page_size,
                        sharded=True, clusters=1, heads=1)
    assert isinstance(srv, ShardedPagedServer)
    assert shard == ref, "1-cluster sharded engine diverged under sampling"


def test_different_seed_changes_stream(cfg, params):
    prompts = _prompts(cfg.vocab_size, n=2)
    a, _ = _serve(cfg, params, prompts, _sampled)
    b, _ = _serve(cfg, params, prompts,
                  lambda rid: _sampled(rid, seed_base=900))
    assert a != b, "12+ sampled tokens identical across different seeds"


def test_sampling_independent_of_chunk_size(cfg, params):
    """The fold position is the token's absolute sequence position, so
    chunked-prefill granularity must not change sampled streams (the
    sampling analogue of the greedy chunk-parity test)."""
    prompts = _prompts(cfg.vocab_size)
    base, _ = _serve(cfg, params, prompts, _sampled, chunk=1)
    for chunk in (3, 16):
        out, _ = _serve(cfg, params, prompts, _sampled, chunk=chunk)
        assert out == base, chunk


# ---------------------------------------------------------- greedy parity --

def test_temperature_zero_is_greedy_regardless_of_seed(cfg, params):
    """temperature=0 must ride the exact argmax path the engine always
    had: the seed (and top-k/top-p) must be inert, and the default
    SamplingParams() must match — byte-identical greedy."""
    prompts = _prompts(cfg.vocab_size)
    base, _ = _serve(cfg, params, prompts,
                     lambda rid: SamplingParams(max_new=MAX_NEW))
    for seed in (0, 7, 123456789):
        out, _ = _serve(cfg, params, prompts,
                        lambda rid: SamplingParams(temperature=0.0,
                                                   seed=seed, top_p=0.5,
                                                   top_k=3,
                                                   max_new=MAX_NEW))
        assert out == base, f"temperature=0 not greedy (seed={seed})"


class _ScriptedDrafter:
    """Proposes the recorded greedy continuation of whichever stream the
    context extends, with the last draft token made wrong: acceptance and
    rollback then both happen by construction, whatever the weights."""

    def __init__(self, streams, vocab):
        self.streams, self.vocab = streams, vocab

    def propose(self, ctx, k):
        for s in self.streams:
            if s[:len(ctx)] == list(ctx):
                drafts = s[len(ctx):len(ctx) + k]
                if len(drafts) > 1:
                    drafts[-1] = (drafts[-1] + 1) % self.vocab
                return drafts
        return []


def test_temperature_zero_greedy_with_speculation_active(cfg, params):
    """Acceptance criterion: temperature=0 output is byte-identical to
    the pre-redesign greedy decode with speculation still engaged."""
    prompts = _prompts(cfg.vocab_size, n=2)
    base, _ = _serve(cfg, params, prompts,
                     lambda rid: SamplingParams(max_new=12))
    drafter = _ScriptedDrafter(
        [list(p) + list(base[rid]) for rid, p in enumerate(prompts)],
        cfg.vocab_size)
    out, srv = _serve(cfg, params, prompts,
                      lambda rid: SamplingParams(temperature=0.0, seed=3,
                                                 max_new=12), spec_k=4,
                      drafter=drafter)
    assert out == base
    assert srv.spec_accepted > 0, "speculation never engaged"
    assert srv.spec_rejected > 0, "no draft was ever rolled back"


def test_top_k_one_is_greedy_at_any_temperature(cfg, params):
    """top_k=1 collapses the candidate set to the argmax token, so even a
    hot temperature must reproduce the greedy stream — exercises the
    truncation masks end-to-end."""
    prompts = _prompts(cfg.vocab_size)
    base, _ = _serve(cfg, params, prompts,
                     lambda rid: SamplingParams(max_new=MAX_NEW))
    out, _ = _serve(cfg, params, prompts,
                    lambda rid: SamplingParams(temperature=2.0, top_k=1,
                                               seed=rid, max_new=MAX_NEW))
    assert out == base


# --------------------------------------------------------- finish reasons --

def test_finish_reason_length_and_stop(cfg, params):
    prompts = _prompts(cfg.vocab_size, n=1)
    base, srv = _serve(cfg, params, prompts,
                       lambda rid: SamplingParams(max_new=MAX_NEW))
    assert srv.finished[0].finish_reason == "length"
    toks = base[0]
    # stop on the token whose FIRST occurrence is latest, so the expected
    # truncation point is well-defined for any stream shape
    first_occ = {t: toks.index(t) for t in toks}
    stop_tok = max(first_occ, key=lambda t: first_occ[t])
    cut = first_occ[stop_tok]
    out, srv = _serve(
        cfg, params, prompts,
        lambda rid: SamplingParams(max_new=MAX_NEW,
                                   stop_tokens=(stop_tok,)))
    r = srv.finished[0]
    assert r.finish_reason == "stop"
    assert r.tokens == toks[:cut + 1]   # stop token included, then cut
    assert srv.pool.free_pages() == 32  # early exit released everything


def test_stop_token_on_first_generated_token(cfg, params):
    """The very first sampled token being a stop token is the edge case:
    one token out, reason 'stop'."""
    prompts = _prompts(cfg.vocab_size, n=1)
    base, _ = _serve(cfg, params, prompts,
                     lambda rid: SamplingParams(max_new=MAX_NEW))
    first = base[0][0]
    out, srv = _serve(cfg, params, prompts,
                      lambda rid: SamplingParams(max_new=MAX_NEW,
                                                 stop_tokens=(first,)))
    assert out[0] == (first,)
    assert srv.finished[0].finish_reason == "stop"


def test_generate_max_iters_streams_abort_deltas(cfg, params):
    """The streaming front-end surfaces the iteration-cap abort: every
    pending request yields an 'abort' delta and a finished result with
    finish_reason='aborted' (the run(max_iters) regression, observed
    through generate())."""
    srv = make_engine(cfg, params, EngineConfig(
        cache=CacheConfig(num_pages=32, page_size=4, max_pages_per_seq=8),
        max_lanes=2, chunk=4, use_kernel=False))
    reqs = [GenerationRequest(rid=rid, prompt=(rid + 1, 2, 3, 4),
                              sampling=SamplingParams(max_new=8))
            for rid in range(4)]
    deltas = list(srv.generate(reqs, max_iters=2))
    aborted = {d.rid for d in deltas if d.event == "abort"}
    assert aborted == {0, 1, 2, 3}
    assert {r.rid: r.finish_reason for r in srv.finished} == \
        {rid: "aborted" for rid in range(4)}
    assert srv.pool.free_pages() == 32 and len(srv.backing) == 0


# -------------------------------------------------------------- streaming --

def test_stream_concatenation_equals_results(cfg, params):
    """Acceptance criterion: for every request — greedy, sampled, and
    preempted mid-flight — the concatenation of its token deltas equals
    the final GenerationResult tokens, and scheduler events (prefix hits,
    preemptions) surface as token-free deltas."""
    sys_p = [9, 9, 8, 2, 5, 5, 1, 3]
    prompts = [sys_p + [20 + i] for i in range(4)] + [[4, 2] * 6]

    def sampling_for(rid):
        if rid == 1:
            return SamplingParams(temperature=0.7, top_p=0.9, seed=5,
                                  max_new=5)
        return SamplingParams(max_new=5)

    srv = make_engine(cfg, params, EngineConfig(
        cache=CacheConfig(num_pages=16, page_size=4, max_pages_per_seq=8),
        max_lanes=2, chunk=4, use_kernel=False))
    reqs = [GenerationRequest(rid=rid, prompt=tuple(p),
                              sampling=sampling_for(rid),
                              priority=5 if rid == 4 else 0)
            for rid, p in enumerate(prompts)]
    streamed: dict = {}
    events: list = []
    for d in srv.generate(reqs):
        assert isinstance(d, TokenDelta)
        streamed.setdefault(d.rid, []).extend(d.tokens)
        if d.event != "token":
            events.append(d.event)
            if d.event in ("prefix_hit", "preempt"):
                assert d.tokens == ()   # scheduler events carry no tokens
    final = {r.rid: list(r.tokens) for r in srv.finished}
    assert streamed == final
    assert "prefix_hit" in events, "shared prompts never hit the cache"
    assert all(isinstance(r, GenerationResult) for r in srv.finished)


def test_preempt_between_iterations_surfaces_in_stream(cfg, params):
    """Regression: events recorded BETWEEN engine iterations — a caller
    invoking preempt() from the generate-loop body — must still reach the
    stream (step() used to clear the delta buffer on entry, silently
    dropping them), and the delta/result token contract must survive the
    preemption round-trip."""
    srv = make_engine(cfg, params, EngineConfig(
        cache=CacheConfig(num_pages=32, page_size=4, max_pages_per_seq=8),
        max_lanes=2, chunk=8, use_kernel=False))
    reqs = [GenerationRequest(rid=rid, prompt=(rid + 1, 2, 3, 4, 5),
                              sampling=SamplingParams(max_new=6))
            for rid in range(2)]
    streamed: dict = {}
    events = []
    preempted = False
    for i, d in enumerate(srv.generate(reqs)):
        streamed.setdefault(d.rid, []).extend(d.tokens)
        events.append(d.event)
        if i == 2 and not preempted:
            preempted = srv.preempt(0)      # from the loop body
            assert preempted
    assert "preempt" in events, "between-iteration preempt delta was lost"
    assert streamed == {r.rid: list(r.tokens) for r in srv.finished}
    assert srv.preemptions >= 1


def test_stream_spec_deltas_concatenate(cfg, params):
    """Speculative iterations emit multi-token 'spec' deltas; their
    concatenation must still equal the final stream."""
    rng = np.random.default_rng(9)
    pat = rng.integers(1, cfg.vocab_size, size=4).tolist()
    srv = make_engine(cfg, params, EngineConfig(
        cache=CacheConfig(num_pages=32, page_size=4, max_pages_per_seq=8),
        max_lanes=2, chunk=8, use_kernel=False, spec_k=4))
    reqs = [GenerationRequest(rid=0, prompt=tuple(pat * 3),
                              sampling=SamplingParams(max_new=10))]
    streamed: list = []
    saw_spec = False
    for d in srv.generate(reqs):
        streamed.extend(d.tokens)
        saw_spec |= (d.event == "spec" and len(d.tokens) > 1)
    assert tuple(streamed) == srv.finished[0].tokens
    assert saw_spec, "no multi-token speculative delta observed"


# ---------------------------------------------------------------- factory --

def test_make_engine_selects_engine_class(cfg, params):
    ec = EngineConfig(cache=CacheConfig(num_pages=8, page_size=4,
                                        max_pages_per_seq=4),
                      max_lanes=1, use_kernel=False)
    assert type(make_engine(cfg, params, ec)) is PagedServer
    assert isinstance(
        make_engine(cfg, params, dataclasses.replace(ec, sharded=True)),
        ShardedPagedServer)
    assert make_engine(cfg, params, ec).engine_cfg == ec


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(max_new=0)
    sp = SamplingParams(stop_tokens=[1, 2])
    assert sp.stop_tokens == (1, 2) and sp.greedy


def test_generation_request_is_frozen(cfg, params):
    req = GenerationRequest(rid=0, prompt=[1, 2, 3])
    assert req.prompt == (1, 2, 3)      # normalized to a tuple
    with pytest.raises(Exception):
        req.prompt = (9,)
    srv = make_engine(cfg, params, EngineConfig(
        cache=CacheConfig(num_pages=8, page_size=4, max_pages_per_seq=4),
        max_lanes=1, use_kernel=False))
    srv.submit(GenerationRequest(rid=0, prompt=(1, 2, 3),
                                 sampling=SamplingParams(max_new=2)))
    srv.run()
    assert req.prompt == (1, 2, 3)      # engine never mutates the request


def test_submit_validation_errors(cfg, params):
    srv = make_engine(cfg, params, EngineConfig(
        cache=CacheConfig(num_pages=4, page_size=4, max_pages_per_seq=4),
        max_lanes=1, use_kernel=False))
    with pytest.raises(ValueError):
        srv.submit(GenerationRequest(rid=0, prompt=()))
    with pytest.raises(ValueError):     # 4 pages * 4 slots < 13 + 8 - 1
        srv.submit(GenerationRequest(rid=1, prompt=tuple(range(1, 14)),
                                     sampling=SamplingParams(max_new=8)))
