"""The engine's spans in a profiler trace, and the names its step programs
lower to: a tiny engine runs under ``jax.profiler`` on the CPU."""
import glob
import os
from collections import defaultdict

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.runtime import (
    CacheConfig, EngineConfig, GenerationRequest, SamplingParams,
    make_engine,
)

PROMPTS = [[5, 6, 7, 8, 9, 10, 11], [3, 1, 4, 1, 5], [2, 7]]
#: the spans every iteration that dispatches a step program holds
STEP_PARTS = {"sweep", "admit", "account", "dispatch", "pull", "emit"}


@pytest.fixture(scope="module")
def model():
    cfg = get_config("yi-6b").smoke()
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _engine(model, **kw):
    cfg, params = model
    kw.setdefault("max_lanes", 2)
    return make_engine(cfg, params, EngineConfig(
        cache=CacheConfig(num_pages=32, page_size=4, max_pages_per_seq=8),
        chunk=4, use_kernel=False, **kw))


def _req(rid, prompt, max_new=3, **kw):
    return GenerationRequest(rid=rid, prompt=tuple(prompt),
                             sampling=SamplingParams(max_new=max_new), **kw)


def _traced(trace_dir, fn):
    """Run ``fn`` under the profiler; its ``engine.*`` spans by name, each
    ``(start_ns, end_ns, args)``, in order of start."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(trace_dir)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    by = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    by[ev.name[len("engine."):]].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return {k: sorted(v, key=lambda s: s[0]) for k, v in by.items()}


def _inside(child, parent):
    return parent[0] <= child[0] and child[1] <= parent[1]


def _held_by(child, parents):
    return any(_inside(child, p) for p in parents)


def test_step_spans_nest_and_name_the_requests(model, tmp_path):
    srv = _engine(model)

    def go():
        for rid, p in enumerate(PROMPTS):
            srv.submit(_req(rid, p))
        srv.run()
    by = _traced(str(tmp_path), go)
    assert [a["rid"] for *_, a in by["submit"]] == [0, 1, 2]
    # a step() that finds the engine idle opens a span with no dispatch
    steps = [st for st in by["step"]
             if any(_inside(d, st) for d in by["dispatch"])]
    assert len(steps) == srv.iterations
    for st in steps:
        held = {n for n, spans in by.items() if n != "step"
                for s in spans if _inside(s, st)}
        assert STEP_PARTS <= held
    for part in STEP_PARTS:
        assert all(_held_by(s, by["step"]) for s in by[part])
    assert all(_held_by(s, by["admit"]) for s in by["place"])
    assert all(_held_by(s, by["emit"]) for s in by["first_token"])
    assert sorted(a["rid"] for *_, a in by["place"]) == [0, 1, 2]
    placed = {a["rid"]: s for s, _, a in by["place"]}
    assert sorted(a["rid"] for *_, a in by["first_token"]) == [0, 1, 2]
    assert all(s > placed[a["rid"]] for s, _, a in by["first_token"])
    # every token the engine writes K/V for passes one dispatch: prompt
    # plus max_new - 1, on pages of 4 tokens
    assert {a["kind"] for *_, a in by["dispatch"]} == {"chunk", "decode"}
    assert sum(a["tokens"] for *_, a in by["dispatch"]) == \
        sum(len(p) + 2 for p in PROMPTS)
    for *_, a in by["dispatch"]:
        assert a["slots"] == (2 * 4 if a["kind"] == "chunk" else 2)
        assert 0 < a["tokens"] <= a["slots"]
    assert len(by["account"]) == srv.iterations


def test_preemption_is_a_span_inside_admission(model, tmp_path):
    srv = _engine(model, max_lanes=1)

    def go():
        srv.submit(_req(0, PROMPTS[0], max_new=4))
        srv.step()
        srv.submit(_req(1, PROMPTS[1], priority=1))
        srv.run()
    by = _traced(str(tmp_path), go)
    (s, e, a), = by["preempt"]
    assert a == {"rid": 0}
    assert _held_by((s, e), by["admit"])
    assert [a["rid"] for *_, a in by["place"]] == [0, 1, 0]
    assert srv.preemptions == 1


def test_speculative_iterations_dispatch_the_verify_step(model, tmp_path):
    srv = _engine(model, spec_k=2)

    def go():
        srv.submit(_req(0, [1, 2, 3] * 4, max_new=8))
        srv.run()
    by = _traced(str(tmp_path), go)
    spec = [a for *_, a in by["dispatch"] if a["kind"] == "spec"]
    assert spec and srv.spec_iterations == len(spec)
    assert all(a["slots"] == 2 * 3 and a["tokens"] >= 1 for a in spec)
    assert len(by["emit"]) == len(by["pull"]) == srv.iterations


def _step_args(srv, kind):
    B, C = srv.max_lanes, srv.chunk
    lane = (srv.seed_dev, srv.temp_dev, srv.topk_dev, srv.topp_dev)
    head = (srv.params, srv.kv_pages, srv.kv_scales, srv.bt_dev,
            srv.len_dev)
    i32 = jnp.int32
    if kind == "chunk":
        return head + (jnp.zeros((B,), i32), jnp.zeros((B, C), i32),
                       srv.last_tok, jnp.zeros((B,), i32)) + lane
    if kind == "decode":
        return head + (srv.active_dev, srv.last_tok) + lane
    return head + (srv.active_dev, srv.last_tok,
                   jnp.zeros((B, srv.spec_k), i32),
                   jnp.zeros((B,), i32)) + lane


@pytest.mark.parametrize("sharded", [False, True], ids=["base", "sharded"])
@pytest.mark.parametrize("kind", ["chunk", "decode", "spec"])
def test_step_programs_lower_to_stable_names(model, sharded, kind):
    """The device trace names a program by its module: each step lowers as
    ``jit_paged_<kind>_step_<greedy|sampled>``, in both engines."""
    kw = dict(sharded=True, clusters=1, heads=1) if sharded else {}
    srv = _engine(model, spec_k=2, **kw)
    table = getattr(srv, f"_{kind}_step")
    for sample, tag in ((False, "greedy"), (True, "sampled")):
        text = table[sample].lower(*_step_args(srv, kind)).as_text()
        assert text.startswith(f"module @jit_paged_{kind}_step_{tag} ")
