"""Numerical invariants of the model zoo: chunked-vs-reference mLSTM,
decode-vs-fullseq consistency per arch family, MoE routing properties."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.models import ssm as S
from repro.models import moe as MOE


def test_mlstm_chunkwise_matches_recurrent(rng):
    cfg = get_config("xlstm-350m").smoke()
    p = S.build_mlstm(__import__("repro.parallel.sharding",
                                 fromlist=["ParamFactory"]).ParamFactory(
        "init", jnp.float32, rng), cfg)
    x = jax.random.normal(jax.random.fold_in(rng, 9), (2, 64, cfg.d_model),
                          jnp.float32) * 0.5
    out_chunk = S.mlstm_fullseq(cfg, p, x, chunk=16)
    out_ref = S.mlstm_reference(cfg, p, x)
    np.testing.assert_allclose(np.asarray(out_chunk), np.asarray(out_ref),
                               rtol=2e-3, atol=2e-3)


def test_mamba_decode_matches_fullseq(rng):
    cfg = get_config("hymba-1.5b").smoke()
    from repro.parallel.sharding import ParamFactory
    p = S.build_mamba(ParamFactory("init", jnp.float32, rng), cfg)
    B, T = 2, 12
    x = jax.random.normal(jax.random.fold_in(rng, 3), (B, T, cfg.d_model),
                          jnp.float32) * 0.5
    full = S.mamba_fullseq(cfg, p, x)
    state = {k: jnp.zeros(s, dt) for k, (s, dt, _)
             in S.mamba_state_specs(cfg, B).items()}
    outs = []
    for t in range(T):
        o, state = S.mamba_decode(cfg, p, x[:, t:t + 1], state)
        outs.append(o)
    step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(step),
                               rtol=2e-3, atol=2e-3)


def test_slstm_decode_matches_fullseq(rng):
    cfg = get_config("xlstm-350m").smoke()
    from repro.parallel.sharding import ParamFactory
    p = S.build_slstm(ParamFactory("init", jnp.float32, rng), cfg)
    B, T = 2, 10
    x = jax.random.normal(jax.random.fold_in(rng, 4), (B, T, cfg.d_model),
                          jnp.float32) * 0.5
    full = S.slstm_fullseq(cfg, p, x)
    state = {k: jnp.zeros(s, dt) for k, (s, dt, _)
             in S.slstm_state_specs(cfg, B).items()}
    outs = []
    for t in range(T):
        o, state = S.slstm_decode(cfg, p, x[:, t:t + 1], state)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(jnp.concatenate(outs, 1)),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# decode-vs-prefill consistency (the cache correctness test), all families
# ---------------------------------------------------------------------------

DECODE_ARCHS = ["yi-6b", "qwen3-32b", "gemma2-2b", "olmoe-1b-7b",
                "deepseek-v2-236b", "xlstm-350m", "hymba-1.5b",
                "whisper-medium", "pixtral-12b", "minitron-8b"]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_matches_fullseq_logits(arch, rng):
    cfg = get_config(arch).smoke()
    # hymba SWA ring needs window >= T for exact equivalence at this length
    T = 12
    if cfg.sliding_window:
        cfg = dataclasses.replace(cfg, sliding_window=max(cfg.sliding_window,
                                                          T))
    if cfg.moe_num_experts:
        # joint-prefill routing must not drop tokens for exact equivalence
        # with the (dropless) decode path
        cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)
    params = M.init_params(cfg, rng)
    B = 2
    tokens = jax.random.randint(jax.random.fold_in(rng, 7), (B, T), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    frontend = None
    if cfg.frontend:
        frontend = (jax.random.normal(
            jax.random.fold_in(rng, 8), (B, cfg.frontend_seq, cfg.d_model),
            jnp.float32) * 0.1).astype(jnp.bfloat16)

    hidden = M.forward_fullseq(cfg, params, tokens, frontend=frontend)
    from repro.models.layers import logits_from_hidden
    want = logits_from_hidden(cfg, params["embed"], hidden[:, -1:, :])

    cache = M.init_cache(cfg, B, T)
    if cfg.block_kind == "encdec":
        xk, xv = M.encdec_cross_cache(cfg, params, frontend)
        cache["xk"], cache["xv"] = xk, xv
    got = None
    for t in range(T):
        pos = jnp.full((B,), t, jnp.int32)
        embeds = None
        if cfg.frontend == "patch" and t < cfg.frontend_seq:
            # fullseq replaces the first Fs positions with patch embeddings;
            # the decode path consumes them as inputs_embeds
            embeds = frontend[:, t:t + 1]
        got, cache = M.decode_forward(cfg, params, cache, tokens[:, t:t + 1],
                                      pos, inputs_embeds=embeds)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def test_moe_matches_dense_loop(rng):
    """Capacity-based dispatch == per-token dense loop when capacity ample."""
    cfg = dataclasses.replace(get_config("olmoe-1b-7b").smoke(),
                              moe_capacity_factor=8.0)
    from repro.parallel.sharding import ParamFactory
    p = MOE.build_moe(ParamFactory("init", jnp.float32, rng), cfg)
    B, T = 2, 8
    x = jax.random.normal(jax.random.fold_in(rng, 5), (B, T, cfg.d_model),
                          jnp.float32) * 0.5
    got = MOE.moe_forward(cfg, p, x)

    # reference: explicit per-token top-k loop
    xf = x.reshape(-1, cfg.d_model)
    logits = xf @ p["router"]
    k = cfg.moe_top_k
    vals, idx = jax.lax.top_k(logits, k)
    probs = jax.nn.softmax(vals, -1)
    ref = np.zeros_like(np.asarray(xf))
    for t in range(xf.shape[0]):
        for j in range(k):
            e = int(idx[t, j])
            h = np.asarray(jax.nn.silu(xf[t] @ p["w_gate"][e]) *
                           (xf[t] @ p["w_up"][e]))
            ref[t] += float(probs[t, j]) * (h @ np.asarray(p["w_down"][e]))
    np.testing.assert_allclose(np.asarray(got.reshape(-1, cfg.d_model)),
                               ref, rtol=3e-3, atol=3e-3)


def test_moe_capacity_drops_overflow(rng):
    cfg = dataclasses.replace(get_config("olmoe-1b-7b").smoke(),
                              moe_capacity_factor=0.05)
    from repro.parallel.sharding import ParamFactory
    p = MOE.build_moe(ParamFactory("init", jnp.float32, rng), cfg)
    x = jax.random.normal(rng, (2, 16, cfg.d_model), jnp.float32)
    out = MOE.moe_forward(cfg, p, x)
    assert bool(jnp.isfinite(out).all())
    # with tiny capacity, most tokens are dropped -> smaller magnitude
    big = MOE.moe_forward(dataclasses.replace(cfg, moe_capacity_factor=8.0),
                          p, x)
    assert float(jnp.abs(out).mean()) <= float(jnp.abs(big).mean()) + 1e-6


def test_router_load_counts(rng):
    cfg = get_config("olmoe-1b-7b").smoke()
    from repro.parallel.sharding import ParamFactory
    p = MOE.build_moe(ParamFactory("init", jnp.float32, rng), cfg)
    x = jax.random.normal(rng, (2, 16, cfg.d_model), jnp.float32)
    load = MOE.router_load(cfg, p, x)
    assert int(load.sum()) == 2 * 16 * cfg.moe_top_k


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_init_params_bit_identical_to_eager_draw(dtype, monkeypatch):
    """The jitted per-leaf draw (which keeps the f32 draw off the device)
    gives the values of the eager ``normal(...) * std`` cast, bit for bit,
    so recorded outputs do not move."""
    from repro.parallel import sharding as SH
    cfg = dataclasses.replace(get_config("yi-6b").smoke(), param_dtype=dtype)
    key = jax.random.PRNGKey(3)
    jitted = M.init_params(cfg, key)
    monkeypatch.setattr(
        SH, "_normal_leaf", lambda k, shape, std, dt, where: (
            jax.random.normal(k, shape, jnp.float32) * std).astype(dt))
    eager = M.init_params(cfg, key)

    def same(a, b):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    jax.tree.map(same, jitted, eager)
    assert jitted["layers"]["attn"]["wq"].dtype == jnp.dtype(dtype)
