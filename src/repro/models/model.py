"""Unified model assembly for all assigned architectures.

One parameter-building function (interpreted for init / shape-spec / axes by
``ParamFactory``), one full-sequence forward (train / prefill), and one
single-token decode forward (with per-family caches).

Layer loops are unrolled in Python (each layer indexes a stacked parameter
tree).  This keeps `compiled.cost_analysis()` and collective-byte parsing
exact — while-loop bodies would be counted once — at the cost of larger HLO,
which is acceptable at <=64 layers.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.parallel.sharding import ParamFactory, shard, tree_pspecs
from repro.models import layers as L
from repro.models import attention as A
from repro.models import moe as MOE
from repro.models import ssm as S

# "slot not written" marker for position caches.  Must be a large POSITIVE
# value: masks keep slots with kpos <= current position, so an empty slot
# must compare greater than any real position (a negative sentinel would
# silently attend to zero-valued K/V rows).
EMPTY_POS = 2 ** 30


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


# ===========================================================================
# Parameter building
# ===========================================================================

def _build_tf_layer(f: ParamFactory, cfg: ArchConfig, use_moe: bool):
    d = cfg.d_model
    lp: Dict[str, Any] = {"ln1": L.build_norm(f, cfg, "ln1", d)}
    if cfg.attention_kind == "mla":
        lp["attn"] = A.build_mla(f, cfg)
    else:
        lp["attn"] = A.build_gqa(f, cfg)
    lp["ln2"] = L.build_norm(f, cfg, "ln2", d)
    if use_moe:
        lp["moe"] = MOE.build_moe(f, cfg)
    else:
        lp["mlp"] = L.build_mlp(f, cfg, "mlp", d, cfg.d_ff)
    if cfg.post_block_norm:
        lp["pln1"] = L.build_norm(f, cfg, "pln1", d)
        lp["pln2"] = L.build_norm(f, cfg, "pln2", d)
    return lp


def _build_hymba_layer(f: ParamFactory, cfg: ArchConfig):
    d = cfg.d_model
    return {
        "ln1": L.build_norm(f, cfg, "ln1", d),
        "attn": A.build_gqa(f, cfg),
        "mamba": S.build_mamba(f, cfg),
        "bn_attn": L.build_norm(f, cfg, "bn_attn", d),
        "bn_ssm": L.build_norm(f, cfg, "bn_ssm", d),
        "ln2": L.build_norm(f, cfg, "ln2", d),
        "mlp": L.build_mlp(f, cfg, "mlp", d, cfg.d_ff),
    }


def _build_encdec(f: ParamFactory, cfg: ArchConfig):
    d = cfg.d_model
    p: Dict[str, Any] = {}
    with f.scope("enc"):
        with f.stacked(cfg.encoder_layers):
            p["enc_layers"] = {
                "ln1": L.build_norm(f, cfg, "ln1", d),
                "attn": A.build_gqa(f, cfg),
                "ln2": L.build_norm(f, cfg, "ln2", d),
                "mlp": L.build_mlp(f, cfg, "mlp", d, cfg.d_ff),
            }
        p["enc_norm"] = L.build_norm(f, cfg, "enc_norm", d)
        p["enc_pos"] = f("enc_pos", (cfg.frontend_seq, d), (None, "fsdp"))
    with f.scope("dec"):
        with f.stacked(cfg.num_layers):
            p["dec_layers"] = {
                "ln1": L.build_norm(f, cfg, "ln1", d),
                "attn": A.build_gqa(f, cfg),
                "lnx": L.build_norm(f, cfg, "lnx", d),
                "xattn": A.build_cross_attn(f, cfg),
                "ln2": L.build_norm(f, cfg, "ln2", d),
                "mlp": L.build_mlp(f, cfg, "mlp", d, cfg.d_ff),
            }
        p["dec_pos"] = f("dec_pos", (cfg.max_positions, d), (None, "fsdp"))
    return p


def build_params(f: ParamFactory, cfg: ArchConfig):
    p: Dict[str, Any] = {"embed": L.build_embedding(f, cfg)}
    if cfg.block_kind == "encdec":
        p.update(_build_encdec(f, cfg))
    elif cfg.block_kind == "mlstm":
        n_s = -(-cfg.num_layers // cfg.slstm_every) if cfg.slstm_every else 0
        n_m = cfg.num_layers - n_s
        with f.scope("mlstm"):
            with f.stacked(n_m):
                p["mlstm_layers"] = {
                    "ln1": L.build_norm(f, cfg, "ln1", cfg.d_model),
                    "cell": S.build_mlstm(f, cfg),
                }
        if n_s:
            with f.scope("slstm"):
                with f.stacked(n_s):
                    p["slstm_layers"] = {
                        "ln1": L.build_norm(f, cfg, "ln1", cfg.d_model),
                        "cell": S.build_slstm(f, cfg),
                    }
    elif cfg.block_kind == "hymba":
        with f.scope("layers"):
            with f.stacked(cfg.num_layers):
                p["layers"] = _build_hymba_layer(f, cfg)
    else:  # transformer (dense / moe / vlm)
        n_dense_first = cfg.moe_first_dense_layers if cfg.moe_num_experts else 0
        n_main = cfg.num_layers - n_dense_first
        use_moe = bool(cfg.moe_num_experts)
        if n_dense_first:
            with f.scope("first_layers"):
                with f.stacked(n_dense_first):
                    p["first_layers"] = _build_tf_layer(f, cfg, use_moe=False)
        with f.scope("layers"):
            with f.stacked(n_main):
                p["layers"] = _build_tf_layer(f, cfg, use_moe=use_moe)
    p["final_norm"] = L.build_norm(f, cfg, "final_norm", cfg.d_model)
    return p


def init_params(cfg: ArchConfig, rng: jax.Array, sharding=None):
    """Random parameters from ``rng``; ``sharding`` (a ``ParamFactory``
    placement, e.g. ``parallel.sharding.head_param_sharding``) draws each
    leaf directly into its target devices."""
    f = ParamFactory("init", _dtype(cfg), rng, sharding)
    return build_params(f, cfg)


def param_specs(cfg: ArchConfig):
    return build_params(ParamFactory("spec", _dtype(cfg)), cfg)


def param_axes(cfg: ArchConfig):
    return build_params(ParamFactory("axes", _dtype(cfg)), cfg)


def param_pspecs(cfg: ArchConfig, mesh):
    return tree_pspecs(param_specs(cfg), param_axes(cfg), mesh)


# ===========================================================================
# Decode caches
# ===========================================================================

def build_cache(f: ParamFactory, cfg: ArchConfig, B: int, T: int):
    """Cache tree for one-token decode with context length T."""
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    c: Dict[str, Any] = {}
    if cfg.block_kind == "mlstm":
        n_s = -(-cfg.num_layers // cfg.slstm_every) if cfg.slstm_every else 0
        n_m = cfg.num_layers - n_s
        with f.scope("mlstm_state"):
            with f.stacked(n_m):
                c["mlstm"] = {
                    k: f(k, shape, ax, init="zeros", dtype=dtype)
                    for k, (shape, dtype, ax) in S.mlstm_state_specs(cfg, B).items()
                }
        if n_s:
            with f.scope("slstm_state"):
                with f.stacked(n_s):
                    c["slstm"] = {
                        k: f(k, shape, ax, init="zeros", dtype=dtype)
                        for k, (shape, dtype, ax) in S.slstm_state_specs(cfg, B).items()
                    }
        return c

    if cfg.block_kind == "hymba":
        W = min(cfg.sliding_window, T) if cfg.sliding_window else T
        with f.scope("attn_cache"):
            with f.stacked(cfg.num_layers):
                c["k"] = f("k", (B, W, kv, hd), ("dp", None, None, None), init="zeros")
                c["v"] = f("v", (B, W, kv, hd), ("dp", None, None, None), init="zeros")
        c["kpos"] = f("kpos", (B, W), ("dp", None), init="fill", fill=EMPTY_POS,
                      dtype=jnp.int32)
        with f.scope("mamba_state"):
            with f.stacked(cfg.num_layers):
                c["mamba"] = {
                    k: f(k, shape, ax, init="zeros", dtype=dtype)
                    for k, (shape, dtype, ax) in S.mamba_state_specs(cfg, B).items()
                }
        return c

    if cfg.block_kind == "encdec":
        F = cfg.frontend_seq
        with f.scope("self_cache"):
            with f.stacked(cfg.num_layers):
                c["k"] = f("k", (B, T, kv, hd), ("dp", None, "tp", None), init="zeros")
                c["v"] = f("v", (B, T, kv, hd), ("dp", None, "tp", None), init="zeros")
        with f.scope("cross_cache"):
            with f.stacked(cfg.num_layers):
                c["xk"] = f("xk", (B, F, kv, hd), ("dp", None, "tp", None), init="zeros")
                c["xv"] = f("xv", (B, F, kv, hd), ("dp", None, "tp", None), init="zeros")
        c["kpos"] = f("kpos", (B, T), ("dp", None), init="fill", fill=EMPTY_POS,
                      dtype=jnp.int32)
        return c

    if cfg.attention_kind == "mla":
        r, dr = cfg.mla_kv_lora_rank, cfg.mla_qk_rope_dim
        with f.scope("mla_cache"):
            with f.stacked(cfg.num_layers):
                c["c"] = f("c", (B, T, r), ("dp", "sp", None), init="zeros")
                c["rope"] = f("rope", (B, T, dr), ("dp", "sp", None), init="zeros")
        c["kpos"] = f("kpos", (B, T), ("dp", None), init="fill", fill=EMPTY_POS,
                      dtype=jnp.int32)
        return c

    # plain GQA transformer; gemma2 splits local(ring W) / global(linear T)
    if cfg.local_global_period:
        n_local = (cfg.num_layers + 1) // cfg.local_global_period
        n_global = cfg.num_layers - n_local
        W = min(cfg.sliding_window, T)
        with f.scope("local_cache"):
            with f.stacked(n_local):
                c["k_local"] = f("k", (B, W, kv, hd), A.kv_cache_axes(cfg), init="zeros")
                c["v_local"] = f("v", (B, W, kv, hd), A.kv_cache_axes(cfg), init="zeros")
        with f.scope("global_cache"):
            with f.stacked(n_global):
                c["k_global"] = f("k", (B, T, kv, hd), A.kv_cache_axes(cfg), init="zeros")
                c["v_global"] = f("v", (B, T, kv, hd), A.kv_cache_axes(cfg), init="zeros")
        c["kpos_local"] = f("kpos_local", (B, W), ("dp", None), init="fill",
                            fill=EMPTY_POS, dtype=jnp.int32)
        c["kpos"] = f("kpos", (B, T), ("dp", None), init="fill",
                      fill=EMPTY_POS, dtype=jnp.int32)
        return c

    with f.scope("kv_cache"):
        with f.stacked(cfg.num_layers):
            c["k"] = f("k", (B, T, kv, hd), A.kv_cache_axes(cfg), init="zeros")
            c["v"] = f("v", (B, T, kv, hd), A.kv_cache_axes(cfg), init="zeros")
    c["kpos"] = f("kpos", (B, T), ("dp", None), init="fill", fill=EMPTY_POS,
                  dtype=jnp.int32)
    return c


def init_cache(cfg: ArchConfig, B: int, T: int):
    return build_cache(ParamFactory("init", _dtype(cfg)), cfg, B, T)


def cache_specs(cfg: ArchConfig, B: int, T: int):
    return build_cache(ParamFactory("spec", _dtype(cfg)), cfg, B, T)


def cache_axes(cfg: ArchConfig, B: int, T: int):
    return build_cache(ParamFactory("axes", _dtype(cfg)), cfg, B, T)


# ===========================================================================
# Full-sequence forward (train / prefill)
# ===========================================================================

def _sub(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


def _remat(cfg: ArchConfig, fn):
    """Per-layer activation checkpointing (policy from config)."""
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def _stack_apply(cfg: ArchConfig, stacked, x, body, n: int):
    """Apply `body(layer_params, x) -> x` over a homogeneous layer stack.

    cfg.scan_layers=True uses lax.scan (one HLO body; compile time ~n x
    smaller — the HLO cost accounting multiplies loop bodies by their trip
    count, see launch/hlo_stats.py).  Otherwise a Python unrolled loop.
    """
    body = _remat(cfg, body)
    if n == 0:
        return x
    if not cfg.scan_layers or n == 1:
        for i in range(n):
            x = body(_sub(stacked, i), x)
        return x

    def scan_body(carry, lp):
        return body(lp, carry), None

    x, _ = jax.lax.scan(scan_body, x, stacked)
    return x


def _tf_block(cfg: ArchConfig, lp, x, positions, *, window: int,
              use_moe: bool):
    # block entry: one explicit seq all-gather (Megatron-SP pattern); the
    # residual itself stays sequence-sharded between blocks
    h = shard(L.norm_forward(cfg, lp["ln1"], x), "dp", None, None)
    if cfg.attention_kind == "mla":
        a = A.mla_fullseq(cfg, lp["attn"], h, positions)
    else:
        a = A.gqa_fullseq(cfg, lp["attn"], h, positions, window=window)
    if cfg.post_block_norm:
        a = L.norm_forward(cfg, lp["pln1"], a)
    x = x + a
    h = shard(L.norm_forward(cfg, lp["ln2"], x), "dp", None, None)
    if use_moe:
        m = MOE.moe_forward(cfg, lp["moe"], h)
    else:
        m = L.mlp_forward(cfg, lp["mlp"], h)
    if cfg.post_block_norm:
        m = L.norm_forward(cfg, lp["pln2"], m)
    x = x + m
    return shard(x, "dp", "sp", None)


def _layer_window(cfg: ArchConfig, i: int) -> int:
    if cfg.local_global_period:
        return cfg.sliding_window if i % cfg.local_global_period == 0 else 0
    if cfg.block_kind == "hymba":
        return cfg.sliding_window
    return cfg.sliding_window or 0


def _hymba_block(cfg: ArchConfig, lp, x, positions):
    h = shard(L.norm_forward(cfg, lp["ln1"], x), "dp", None, None)
    a = A.gqa_fullseq(cfg, lp["attn"], h, positions,
                      window=cfg.sliding_window)
    m = S.mamba_fullseq(cfg, lp["mamba"], h)
    fused = 0.5 * (L.norm_forward(cfg, lp["bn_attn"], a) +
                   L.norm_forward(cfg, lp["bn_ssm"], m))
    x = x + fused
    h = shard(L.norm_forward(cfg, lp["ln2"], x), "dp", None, None)
    x = x + L.mlp_forward(cfg, lp["mlp"], h)
    return shard(x, "dp", "sp", None)


def forward_fullseq(cfg: ArchConfig, params, tokens: jax.Array,
                    frontend: Optional[jax.Array] = None) -> jax.Array:
    """Returns final hidden states (B,S,d)."""
    B, Sq = tokens.shape
    positions = jnp.arange(Sq, dtype=jnp.int32)
    x = L.embed_tokens(cfg, params["embed"], tokens)

    if cfg.frontend == "patch" and frontend is not None:
        Fs = frontend.shape[1]
        x = jnp.concatenate([frontend.astype(x.dtype), x[:, Fs:, :]], axis=1)

    if cfg.block_kind == "encdec":
        enc = frontend.astype(x.dtype) + params["enc_pos"][None]
        enc_pos = jnp.arange(cfg.frontend_seq, dtype=jnp.int32)

        def enc_block(lp, enc):
            h = shard(L.norm_forward(cfg, lp["ln1"], enc), "dp", None, None)
            a = A.gqa_fullseq(cfg, lp["attn"], h, enc_pos, causal=False)
            enc = enc + a
            h = L.norm_forward(cfg, lp["ln2"], enc)
            return enc + L.mlp_forward(cfg, lp["mlp"], h)

        def dec_block(lp, x, enc):
            h = shard(L.norm_forward(cfg, lp["ln1"], x), "dp", None, None)
            x = x + A.gqa_fullseq(cfg, lp["attn"], h, positions)
            h = shard(L.norm_forward(cfg, lp["lnx"], x), "dp", None, None)
            xk, xv = A.gqa_make_kv(cfg, lp["xattn"], enc, enc_pos)
            x = x + A.gqa_fullseq(cfg, lp["xattn"], h, positions, causal=False,
                                  kv_override=(xk, xv), kv_positions=enc_pos)
            h = shard(L.norm_forward(cfg, lp["ln2"], x), "dp", None, None)
            x = x + L.mlp_forward(cfg, lp["mlp"], h)
            return shard(x, "dp", "sp", None)

        enc = _stack_apply(cfg, params["enc_layers"], enc, enc_block,
                           cfg.encoder_layers)
        enc = L.norm_forward(cfg, params["enc_norm"], enc)
        x = x + params["dec_pos"][None, :Sq, :]
        x = _stack_apply(cfg, params["dec_layers"], x,
                         lambda lp, x: dec_block(lp, x, enc), cfg.num_layers)
    elif cfg.block_kind == "mlstm":
        def m_block(lp, x):
            h = shard(L.norm_forward(cfg, lp["ln1"], x), "dp", None, None)
            return shard(x + S.mlstm_fullseq(cfg, lp["cell"], h),
                         "dp", "sp", None)

        def s_block(lp, x):
            h = shard(L.norm_forward(cfg, lp["ln1"], x), "dp", None, None)
            return shard(x + S.slstm_fullseq(cfg, lp["cell"], h),
                         "dp", "sp", None)

        # grouped stacks: one sLSTM heads each group of (slstm_every) layers
        n_s = -(-cfg.num_layers // cfg.slstm_every) if cfg.slstm_every else 0
        if n_s == 0:
            x = _stack_apply(cfg, params["mlstm_layers"], x, m_block,
                             cfg.num_layers)
        else:
            per = cfg.slstm_every - 1
            s_block_r = _remat(cfg, s_block)
            for g in range(n_s):
                x = s_block_r(_sub(params["slstm_layers"], g), x)
                lo = g * per
                hi = min(lo + per, cfg.num_layers - n_s)
                grp = jax.tree.map(lambda t: t[lo:hi], params["mlstm_layers"])
                x = _stack_apply(cfg, grp, x, m_block, hi - lo)
    elif cfg.block_kind == "hymba":
        x = _stack_apply(cfg, params["layers"], x,
                         lambda lp, x: _hymba_block(cfg, lp, x, positions),
                         cfg.num_layers)
    else:
        n_first = cfg.moe_first_dense_layers if cfg.moe_num_experts else 0
        n_main = cfg.num_layers - n_first
        use_moe = bool(cfg.moe_num_experts)

        def mk_block(window, moe):
            return lambda lp, x: _tf_block(
                cfg, lp, x, positions, window=window, use_moe=moe)

        if n_first:
            x = _stack_apply(cfg, params["first_layers"], x,
                             mk_block(_layer_window(cfg, 0), False), n_first)
        if cfg.local_global_period:
            # scan over [local, global] pairs: reshape stacks (L,..)->(L/p,p,..)
            p_ = cfg.local_global_period
            pairs = jax.tree.map(
                lambda t: t.reshape((n_main // p_, p_) + t.shape[1:]),
                params["layers"])

            def pair_block(lp, x):
                for j in range(p_):
                    x = _tf_block(cfg, _sub(lp, j), x, positions,
                                  window=_layer_window(cfg, j), use_moe=use_moe)
                return x

            x = _stack_apply(cfg, pairs, x, pair_block, n_main // p_)
        else:
            x = _stack_apply(cfg, params["layers"], x,
                             mk_block(_layer_window(cfg, n_first), use_moe),
                             n_main)

    return L.norm_forward(cfg, params["final_norm"], x)


def loss_fn(cfg: ArchConfig, params, batch: Dict[str, jax.Array]) -> jax.Array:
    hidden = forward_fullseq(cfg, params, batch["tokens"],
                             frontend=batch.get("frontend"))
    # gather the sequence-parallel residual once; the chunked loss then keeps
    # only (B, chunk, V/tp) logits alive
    hidden = shard(hidden, "dp", None, None)
    return L.chunked_xent(cfg, params["embed"], hidden, batch["labels"])


def encode_frontend(cfg: ArchConfig, params, frontend: jax.Array) -> jax.Array:
    """Run the (stub-fed) encoder once; returns encoder hidden states."""
    assert cfg.block_kind == "encdec"
    enc = frontend.astype(_dtype(cfg)) + params["enc_pos"][None]
    enc_pos = jnp.arange(cfg.frontend_seq, dtype=jnp.int32)

    def enc_block(lp, enc):
        h = shard(L.norm_forward(cfg, lp["ln1"], enc), "dp", None, None)
        a = A.gqa_fullseq(cfg, lp["attn"], h, enc_pos, causal=False)
        enc = enc + a
        h = L.norm_forward(cfg, lp["ln2"], enc)
        return enc + L.mlp_forward(cfg, lp["mlp"], h)

    enc = _stack_apply(cfg, params["enc_layers"], enc, enc_block,
                       cfg.encoder_layers)
    return L.norm_forward(cfg, params["enc_norm"], enc)


def encdec_cross_cache(cfg: ArchConfig, params, frontend: jax.Array):
    """(xk, xv) stacked (L,B,F,kv,hd) for the decode cache, from one encode."""
    enc = encode_frontend(cfg, params, frontend)
    enc_pos = jnp.arange(cfg.frontend_seq, dtype=jnp.int32)
    xks, xvs = [], []
    for i in range(cfg.num_layers):
        lp = _sub(params["dec_layers"], i)
        xk, xv = A.gqa_make_kv(cfg, lp["xattn"], enc, enc_pos)
        xks.append(xk)
        xvs.append(xv)
    return jnp.stack(xks), jnp.stack(xvs)


def prefill_logits(cfg: ArchConfig, params, batch) -> jax.Array:
    hidden = forward_fullseq(cfg, params, batch["tokens"],
                             frontend=batch.get("frontend"))
    return L.logits_from_hidden(cfg, params["embed"], hidden[:, -1:, :])


# ===========================================================================
# Decode forward
# ===========================================================================

def decode_forward(cfg: ArchConfig, params, cache, tokens: jax.Array,
                   pos: jax.Array,
                   inputs_embeds: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, Any]:
    """One decode step.  tokens: (B,1) int32; pos: (B,) positions of the new
    token.  ``inputs_embeds`` (B,1,d) overrides the token embedding (VLM
    patch positions during prefill-by-decode).  Returns (logits, cache)."""
    B = tokens.shape[0]
    if inputs_embeds is not None:
        x = inputs_embeds.astype(_dtype(cfg))
    else:
        x = L.embed_tokens(cfg, params["embed"], tokens)
    cache = dict(cache)

    def upd_pos(kp, slot):
        return jax.vmap(lambda kpb, s, pv: jax.lax.dynamic_update_slice(
            kpb, pv[None], (s,)))(kp, slot, pos)

    if cfg.block_kind == "mlstm":
        im, isl = 0, 0
        m_state = dict(cache["mlstm"])
        s_state = dict(cache.get("slstm", {}))
        for i in range(cfg.num_layers):
            if cfg.slstm_every and i % cfg.slstm_every == 0:
                lp = _sub(params["slstm_layers"], isl)
                h = L.norm_forward(cfg, lp["ln1"], x)
                out, new = S.slstm_decode(cfg, lp["cell"], h, _sub(s_state, isl))
                s_state = {k: s_state[k].at[isl].set(new[k]) for k in s_state}
                x = x + out
                isl += 1
            else:
                lp = _sub(params["mlstm_layers"], im)
                h = L.norm_forward(cfg, lp["ln1"], x)
                out, new = S.mlstm_decode(cfg, lp["cell"], h, _sub(m_state, im))
                m_state = {k: m_state[k].at[im].set(new[k]) for k in m_state}
                x = x + out
                im += 1
        cache["mlstm"] = m_state
        if s_state:
            cache["slstm"] = s_state

    elif cfg.block_kind == "hymba":
        W = cache["k"].shape[2]
        slot = pos % W
        kpos = upd_pos(cache["kpos"], slot)
        cache["kpos"] = kpos
        k_all, v_all = cache["k"], cache["v"]
        mamba_state = dict(cache["mamba"])
        for i in range(cfg.num_layers):
            lp = _sub(params["layers"], i)
            h = L.norm_forward(cfg, lp["ln1"], x)
            a, k_new, v_new = A.gqa_decode(
                cfg, lp["attn"], h, pos, k_all[i], v_all[i], slot, kpos,
                window=cfg.sliding_window)
            k_all = k_all.at[i].set(k_new)
            v_all = v_all.at[i].set(v_new)
            m_out, new_ms = S.mamba_decode(cfg, lp["mamba"], h,
                                           _sub(mamba_state, i))
            mamba_state = {k: mamba_state[k].at[i].set(new_ms[k])
                           for k in mamba_state}
            fused = 0.5 * (L.norm_forward(cfg, lp["bn_attn"], a) +
                           L.norm_forward(cfg, lp["bn_ssm"], m_out))
            x = x + fused
            h = L.norm_forward(cfg, lp["ln2"], x)
            x = x + L.mlp_forward(cfg, lp["mlp"], h)
        cache["k"], cache["v"], cache["mamba"] = k_all, v_all, mamba_state

    elif cfg.block_kind == "encdec":
        slot = pos
        kpos = upd_pos(cache["kpos"], slot)
        cache["kpos"] = kpos
        x = x + jnp.take(params["dec_pos"], pos, axis=0)[:, None, :]
        F = cache["xk"].shape[2]
        xpos = jnp.arange(F, dtype=jnp.int32)
        xk_positions = jnp.broadcast_to(xpos[None], (B, F))
        full_len = jnp.full((B,), F - 1, jnp.int32)
        k_all, v_all = cache["k"], cache["v"]
        for i in range(cfg.num_layers):
            lp = _sub(params["dec_layers"], i)
            h = L.norm_forward(cfg, lp["ln1"], x)
            a, k_new, v_new = A.gqa_decode(cfg, lp["attn"], h, pos,
                                           k_all[i], v_all[i], slot, kpos)
            k_all = k_all.at[i].set(k_new)
            v_all = v_all.at[i].set(v_new)
            x = x + a
            h = L.norm_forward(cfg, lp["lnx"], x)
            q = jnp.einsum("bsd,dhk->bshk", h, lp["xattn"]["wq"])
            xa = A.attend_decode(q, cache["xk"][i], cache["xv"][i],
                                 lengths=full_len, k_positions=xk_positions)
            x = x + jnp.einsum("bshk,hkd->bsd", xa, lp["xattn"]["wo"])
            h = L.norm_forward(cfg, lp["ln2"], x)
            x = x + L.mlp_forward(cfg, lp["mlp"], h)
        cache["k"], cache["v"] = k_all, v_all

    elif cfg.attention_kind == "mla":
        slot = pos
        kpos = upd_pos(cache["kpos"], slot)
        cache["kpos"] = kpos
        c_all, r_all = cache["c"], cache["rope"]
        n_first = cfg.moe_first_dense_layers
        for i in range(cfg.num_layers):
            lp = (_sub(params["first_layers"], i) if i < n_first
                  else _sub(params["layers"], i - n_first))
            h = L.norm_forward(cfg, lp["ln1"], x)
            a, c_new, r_new = A.mla_decode(cfg, lp["attn"], h, pos,
                                           c_all[i], r_all[i], slot, kpos)
            c_all = c_all.at[i].set(c_new)
            r_all = r_all.at[i].set(r_new)
            if cfg.post_block_norm:
                a = L.norm_forward(cfg, lp["pln1"], a)
            x = x + a
            h = L.norm_forward(cfg, lp["ln2"], x)
            if "moe" in lp:
                m = MOE.moe_forward(cfg, lp["moe"], h, dropless=True)
            else:
                m = L.mlp_forward(cfg, lp["mlp"], h)
            x = x + m
        cache["c"], cache["rope"] = c_all, r_all

    elif cfg.local_global_period:
        W = cache["k_local"].shape[2]
        slot_local = pos % W
        slot_global = pos
        cache["kpos_local"] = upd_pos(cache["kpos_local"], slot_local)
        cache["kpos"] = upd_pos(cache["kpos"], slot_global)
        kl, vl = cache["k_local"], cache["v_local"]
        kg, vg = cache["k_global"], cache["v_global"]
        il = ig = 0
        for i in range(cfg.num_layers):
            lp = _sub(params["layers"], i)
            h = L.norm_forward(cfg, lp["ln1"], x)
            local = i % cfg.local_global_period == 0
            if local:
                a, k_new, v_new = A.gqa_decode(
                    cfg, lp["attn"], h, pos, kl[il], vl[il], slot_local,
                    cache["kpos_local"], window=cfg.sliding_window)
                kl, vl = kl.at[il].set(k_new), vl.at[il].set(v_new)
                il += 1
            else:
                a, k_new, v_new = A.gqa_decode(
                    cfg, lp["attn"], h, pos, kg[ig], vg[ig], slot_global,
                    cache["kpos"])
                kg, vg = kg.at[ig].set(k_new), vg.at[ig].set(v_new)
                ig += 1
            if cfg.post_block_norm:
                a = L.norm_forward(cfg, lp["pln1"], a)
            x = x + a
            h = L.norm_forward(cfg, lp["ln2"], x)
            m = L.mlp_forward(cfg, lp["mlp"], h)
            if cfg.post_block_norm:
                m = L.norm_forward(cfg, lp["pln2"], m)
            x = x + m
        cache["k_local"], cache["v_local"] = kl, vl
        cache["k_global"], cache["v_global"] = kg, vg

    else:  # plain GQA transformer (incl. MoE without MLA: olmoe)
        slot = pos
        kpos = upd_pos(cache["kpos"], slot)
        cache["kpos"] = kpos
        k_all, v_all = cache["k"], cache["v"]
        for i in range(cfg.num_layers):
            lp = _sub(params["layers"], i)
            h = L.norm_forward(cfg, lp["ln1"], x)
            a, k_new, v_new = A.gqa_decode(cfg, lp["attn"], h, pos,
                                           k_all[i], v_all[i], slot, kpos,
                                           window=_layer_window(cfg, i))
            k_all = k_all.at[i].set(k_new)
            v_all = v_all.at[i].set(v_new)
            x = x + a
            h = L.norm_forward(cfg, lp["ln2"], x)
            if "moe" in lp:
                m = MOE.moe_forward(cfg, lp["moe"], h, dropless=True)
            else:
                m = L.mlp_forward(cfg, lp["mlp"], h)
            x = x + m
        cache["k"], cache["v"] = k_all, v_all

    x = L.norm_forward(cfg, params["final_norm"], x)
    logits = L.logits_from_hidden(cfg, params["embed"], x)
    return logits, cache
