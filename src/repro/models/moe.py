"""Capacity-based routed MoE, expert-parallel over `model`, with
*group-local dispatch* (perf iteration 3, EXPERIMENTS.md §Perf).

Dispatch is sort-based, but the sort/scatter bookkeeping runs independently
per data-parallel shard group: tokens are reshaped (T,) -> (G, T/G) with G =
the mesh's dp degree, so the argsort, run-start search and position
computation stay *local* to each shard (GSPMD keeps per-group ops on the
shard that owns the group).  The only cross-device movement left is the
token payload exchange into the expert-sharded (G, E, C, d) buffer — the
canonical MoE all-to-all — instead of a distributed global sort (the
baseline's dominant collective cost: a global argsort over T*k elements plus
repeated (T*k, d) resharding).

FLOPs scale with E*C ~= T*top_k*capacity_factor — the routed compute —
keeping MODEL_FLOPS/HLO_FLOPs honest.  Overflow tokens (per-expert,
per-group load > C) drop, the standard capacity trade-off; ``dropless=True``
(decode) sizes C for the worst case instead.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.parallel.sharding import (
    ParamFactory, current_mesh, current_profile, PROFILES, shard,
)
from repro.models.layers import build_mlp, mlp_forward


def _capacity(cfg: ArchConfig, tokens: int) -> int:
    c = int(tokens * cfg.moe_top_k * cfg.moe_capacity_factor /
            cfg.moe_num_experts)
    c = max(c, 8)
    return -(-c // 8) * 8  # round up to 8


def _dp_groups(T: int) -> int:
    """Dispatch group count = the mesh's data-parallel degree.

    Grouping only pays off at prefill/train token counts; at decode scale
    the (G, E*C, d) scatter buffer costs more than a tiny global sort
    (measured: ds-v2 decode 196 GiB grouped vs 23 GiB simple)."""
    mesh = current_mesh()
    if mesh is None or T < 4096:
        return 1
    prof = PROFILES[current_profile()]
    g = 1
    for a in prof["dp"]:
        g *= mesh.shape.get(a, 1)
    if g <= 1 or T % g or (T // g) < 8:
        return 1
    return g


def build_moe(f: ParamFactory, cfg: ArchConfig, name: str = "moe"):
    d, E, ff = cfg.d_model, cfg.moe_num_experts, cfg.moe_d_ff
    with f.scope(name):
        p = {
            "router": f("router", (d, E), (None, None), dtype=jnp.float32),
            "w_gate": f("w_gate", (E, d, ff), ("ep", "fsdp", None)),
            "w_up": f("w_up", (E, d, ff), ("ep", "fsdp", None)),
            "w_down": f("w_down", (E, ff, d), ("ep", None, "fsdp"), fan_in=ff),
        }
        if cfg.moe_shared_experts:
            with f.scope("shared"):
                p["shared"] = build_mlp(
                    f, cfg, "mlp", d, ff * cfg.moe_shared_experts)
        return p


def moe_forward(cfg: ArchConfig, p, x: jax.Array,
                capacity: Optional[int] = None,
                dropless: bool = False) -> jax.Array:
    """x: (B,S,d) -> (B,S,d).

    dropless=True sizes capacity for the worst case (every token on one
    expert) — the decode path; training/prefill use the capacity factor.

    With a mesh whose expert-parallel degree divides E, dispatch runs under
    ``shard_map``: routing/sort/scatter are shard-local by construction and
    the only cross-device traffic is one explicit all-to-all pair (perf
    iteration 3b — GSPMD-level constraints could not stop the partitioner
    from distributing the sort; see EXPERIMENTS.md §Perf)."""
    out = _moe_shardmap(cfg, p, x, capacity, dropless)
    if out is not None:
        if cfg.moe_shared_experts:
            out = out + mlp_forward(cfg, p["shared"], x)
        return out
    return _moe_gspmd(cfg, p, x, capacity, dropless)


def _dispatch_local(cfg, router, xf, C, dropless):
    """Sort-based local dispatch.  xf: (T,d) -> buf (E,C,d) + combine meta."""
    T, d = xf.shape
    E, k = cfg.moe_num_experts, cfg.moe_top_k
    N = T * k
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router)
    gate_vals, expert_idx = jax.lax.top_k(logits, k)
    probs = jax.nn.softmax(gate_vals, axis=-1)

    flat_expert = expert_idx.reshape(N)
    order = jnp.argsort(flat_expert)
    sorted_expert = flat_expert[order]
    starts = jnp.searchsorted(sorted_expert, jnp.arange(E), side="left")
    pos_in_e = jnp.arange(N) - starts[sorted_expert]
    valid = pos_in_e < C
    dest = jnp.where(valid, sorted_expert * C + pos_in_e, E * C)
    gathered = xf[order // k]
    buf = jnp.zeros((E * C + 1, d), xf.dtype).at[dest].set(
        gathered, mode="drop", unique_indices=True)[:E * C]
    return buf.reshape(E, C, d), (order, dest, valid, probs)


def _combine_local(y, meta, T, k, d):
    """Inverse of _dispatch_local.  y: (E,C,d) -> (T,d)."""
    order, dest, valid, probs = meta
    E_C = y.shape[0] * y.shape[1]
    y_flat = jnp.concatenate([y.reshape(E_C, d),
                              jnp.zeros((1, d), y.dtype)], axis=0)
    y_slots = y_flat[jnp.minimum(dest, E_C)] * valid[:, None].astype(y.dtype)
    unsorted = jnp.zeros((T * k, d), y.dtype).at[order].set(y_slots)
    return jnp.einsum("tkd,tk->td", unsorted.reshape(T, k, d),
                      probs.astype(y.dtype))


def _moe_shardmap(cfg: ArchConfig, p, x: jax.Array, capacity, dropless
                  ) -> Optional[jax.Array]:
    from jax.sharding import PartitionSpec as P

    mesh = current_mesh()
    if mesh is None:
        return None
    prof = PROFILES[current_profile()]
    ep_axes = tuple(a for a in prof["ep"] if mesh.shape.get(a, 1) > 1)
    if len(ep_axes) != 1:
        return None
    ep = ep_axes[0]
    ntp = mesh.shape[ep]
    E, k = cfg.moe_num_experts, cfg.moe_top_k
    B, S, d = x.shape
    if E % ntp or ntp <= 1:
        return None
    dp_axes = tuple(a for a in prof["dp"]
                    if mesh.shape.get(a, 1) > 1 and a != ep)
    ndp = 1
    for a in dp_axes:
        ndp *= mesh.shape[a]
    if B % ndp:
        return None
    T_loc = (B // ndp) * S
    if T_loc < E:
        # decode-sized token counts: a2a capacity padding (E*C slots for
        # T_loc*k assignments) would dominate the wire — the local/GSPMD
        # path is strictly cheaper (perf iteration 3c, refuted-then-guarded)
        return None
    if dropless:
        C = -(-T_loc * k // 8) * 8
    else:
        C = capacity or _capacity(cfg, T_loc)

    def body(xl, router, wg, wu, wd):
        # xl: (B_loc, S, d); wg/wu/wd: (E_loc, d, f)/(E_loc, f, d) — E sharded
        Bl = xl.shape[0]
        xf = xl.reshape(Bl * S, d)
        buf, meta = _dispatch_local(cfg, router, xf, C, dropless)
        # token payload exchange: (E,C,d) -> (E/ntp, C*ntp, d)
        buf = jax.lax.all_to_all(buf, ep, split_axis=0, concat_axis=1,
                                 tiled=True)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
            jnp.einsum("ecd,edf->ecf", buf, wu)
        y = jnp.einsum("ecf,efd->ecd", h, wd)
        # reverse exchange back to the token owners
        y = jax.lax.all_to_all(y, ep, split_axis=1, concat_axis=0, tiled=True)
        out = _combine_local(y, meta, Bl * S, k, d)
        return out.reshape(Bl, S, d)

    dp_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_spec, None, None), P(None, None),
                  P(ep, None, None), P(ep, None, None), P(ep, None, None)),
        out_specs=P(dp_spec, None, None),
        check_vma=False)
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def _moe_gspmd(cfg: ArchConfig, p, x: jax.Array,
               capacity: Optional[int] = None,
               dropless: bool = False) -> jax.Array:
    """GSPMD fallback (no usable ep axis): group-local dispatch."""
    B, S, d = x.shape
    T = B * S
    E, k = cfg.moe_num_experts, cfg.moe_top_k
    G = _dp_groups(T)
    Tg = T // G
    if dropless:
        C = -(-Tg * k // 8) * 8
    else:
        C = capacity or _capacity(cfg, Tg)
    N = Tg * k

    xg = shard(x.reshape(G, Tg, d), "dp", None, None)
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), p["router"])
    gate_vals, expert_idx = jax.lax.top_k(logits, k)            # (G,Tg,k)
    probs = jax.nn.softmax(gate_vals, axis=-1)

    flat_expert = expert_idx.reshape(G, N)
    order = jnp.argsort(flat_expert, axis=1)                    # group-local
    sorted_expert = jnp.take_along_axis(flat_expert, order, axis=1)
    starts = jax.vmap(lambda s: jnp.searchsorted(s, jnp.arange(E),
                                                 side="left"))(sorted_expert)
    pos_in_e = jnp.arange(N)[None, :] - \
        jnp.take_along_axis(starts, sorted_expert, axis=1)
    valid = pos_in_e < C
    # overflow -> out-of-bounds destination, dropped by the scatter
    dest = jnp.where(valid, sorted_expert * C + pos_in_e, E * C)

    tok_of_slot = order // k                                    # (G,N)
    gathered = jnp.take_along_axis(
        xg, tok_of_slot[..., None], axis=1)                     # (G,N,d)

    g_off = (jnp.arange(G) * (E * C + 1))[:, None]
    buf_flat = jnp.zeros((G * (E * C + 1), d), xg.dtype).at[
        (dest + g_off).reshape(-1)].set(
        gathered.reshape(-1, d), mode="drop", unique_indices=True)
    buf = buf_flat.reshape(G, E * C + 1, d)[:, :E * C, :].reshape(G, E, C, d)
    buf = shard(buf, "dp", "ep", None, None)                    # the MoE a2a

    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])) * \
        jnp.einsum("gecd,edf->gecf", buf, p["w_up"])
    h = shard(h, "dp", "ep", None, None)
    y = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    y = shard(y, "dp", "ep", None, None)

    y_flat = jnp.concatenate(
        [y.reshape(G, E * C, d),
         jnp.zeros((G, 1, d), y.dtype)], axis=1)                # OOB row
    y_slots = jnp.take_along_axis(
        y_flat, jnp.minimum(dest, E * C)[..., None], axis=1)    # (G,N,d)
    y_slots = y_slots * valid[..., None].astype(y.dtype)

    unsorted = jnp.zeros((G, N, d), y.dtype).at[
        jnp.arange(G)[:, None], order].set(y_slots)
    combined = jnp.einsum("gtkd,gtk->gtd",
                          unsorted.reshape(G, Tg, k, d),
                          probs.astype(y.dtype))
    out = shard(combined, "dp", None, None).reshape(B, S, d)

    if cfg.moe_shared_experts:
        out = out + mlp_forward(cfg, p["shared"], x)
    return out


def router_load(cfg: ArchConfig, p, x: jax.Array) -> jax.Array:
    """Expert load histogram (for balance metrics / tests)."""
    T = x.shape[0] * x.shape[1]
    logits = jnp.einsum("td,de->te",
                        x.reshape(T, -1).astype(jnp.float32), p["router"])
    _, idx = jax.lax.top_k(logits, cfg.moe_top_k)
    return jnp.bincount(idx.reshape(-1), length=cfg.moe_num_experts)
