"""Paged attention kernels with in-kernel RAB translation.

The RAB insight (HERO C2): a tiny software-managed table suffices to let an
accelerator translate virtual addresses at run time.  Here the table is the
block table maintained by ``core/rab.py``; the kernels *themselves* perform
the translation on their fast path — the block table is scalar-prefetched
(SMEM) and indexes physical KV pages pulled into VMEM per grid step.  The
slow path (allocation) is handled by the host-side RAB miss handler before
launch.

One kernel body serves two entry points:

``paged_prefill_fwd``
    A whole prompt chunk (``C`` tokens) per request against the paged pool,
    flash-style.  Grid ``(B, ceil(n_pages / G))``: each step attends ``G``
    KV pages (``pages_per_step``) with a single online-softmax rescale plus
    the causal in-chunk mask (the chunk's own K/V are pool-resident by the
    time the kernel runs, so one mask covers both history and in-chunk
    causality).  q enters laid out per KV head, ``(B, Kv, C*G, hd)``, and
    the step loops over the KV heads, so every product is a 2-D dot.

``paged_decode_fwd``
    One query token per request — the C=1 special case of the above (with
    ``q_start = lengths - 1`` the masks coincide), kept as its own entry
    point for the engine's decode path.

Both take a *fused* KV pool of shape ``(P, 2, page, Kv, hd)`` — K and V for
a page live in one block and are fetched through one combined index map,
halving the address-translation work of the old separate-K/V layout.

Both require a *repeat-padded* block table: entries past a request's last
mapped page hold the last mapped physical page (never -1).  Trailing grid
steps therefore map to the same block as their predecessor, which lets the
Pallas pipeline elide the DMA entirely, and a scalar-prefetched per-request
page count (``page_counts``) gates the compute, so fully-unmapped trailing
steps cost neither fetch nor FLOPs.  ``ops.pad_block_table`` produces the
padded form from a -1-marked table.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


# ===========================================================================
# decode: one query token, G pages per grid step
# ===========================================================================

@functools.partial(jax.jit, static_argnames=("pages_per_step", "interpret",
                                             "scale"))
def paged_decode_fwd(q: jax.Array, kv_pages: jax.Array,
                     block_table: jax.Array, page_counts: jax.Array,
                     lengths: jax.Array, *, pages_per_step: int = 2,
                     scale: float | None = None,
                     interpret: bool = False,
                     kv_scales: jax.Array | None = None) -> jax.Array:
    """q: (B,H,hd); kv_pages: (P, 2, page, Kv, hd) fused K/V pool;
    block_table: (B, max_pages) int32 physical page ids, repeat-padded (no
    -1; see module docstring); page_counts: (B,) mapped logical pages per
    request; lengths: (B,) tokens per request; kv_scales: optional
    (P, 2, Kv) float32 per-page dequantization scales for an int8 pool
    (see ``paged_prefill_fwd``).

    Decode is exactly the C=1 case of chunked prefill: with
    ``q_start = lengths - 1`` the prefill mask ``tok < len & tok <= qpos``
    collapses to the decode mask ``tok < len``, so one kernel serves both
    paths (and empty lanes, qpos = -1, stay fully masked).

    Returns (B,H,hd)."""
    return paged_prefill_fwd(q[:, None], kv_pages, block_table, page_counts,
                             lengths, lengths - 1,
                             pages_per_step=pages_per_step, scale=scale,
                             interpret=interpret,
                             kv_scales=kv_scales)[:, 0]


# ===========================================================================
# chunked prefill: C query tokens, G pages per grid step
# ===========================================================================

def _prefill_kernel(bt_ref, cnt_ref, len_ref, start_ref, q_ref, *refs,
                    scale: float, page_size: int, g_pages: int, groups: int,
                    kv_heads: int, quant: bool):
    kv_refs = refs[:g_pages]
    rest = refs[g_pages:]
    sc_refs = rest[:g_pages] if quant else ()
    rest = rest[g_pages:] if quant else rest
    o_ref = rest[0]
    m_ref, l_ref, acc_ref = rest[1:]
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    npages = cnt_ref[b]

    @pl.when(j * g_pages < npages)
    def _accumulate():
        # q rows are (chunk token c, group member g) pairs of one kv head,
        # row r = c * groups + g, so the query position is start + r // G;
        # the causal test tok <= start + r // G is rewritten without the
        # division as (tok - start) * G <= r
        R = q_ref.shape[2]
        T = g_pages * page_size
        row = jax.lax.broadcasted_iota(jnp.int32, (R, T), 0)
        tok = j * T + jax.lax.broadcasted_iota(jnp.int32, (R, T), 1)
        # one mask covers pool history AND in-chunk causality: the chunk's
        # own K/V are already pool-resident at positions start..start+C-1
        mask = (tok < len_ref[b]) & ((tok - start_ref[b]) * groups <= row)

        def page_rows(kv: int, h: int):
            # (T, hd) rows of kv head h: one strided read per fetched page;
            # an int8 page is dequantized by its (page, K/V, head) scale
            parts = []
            for gi, r in enumerate(kv_refs):
                x = r[0, kv, :, h, :].astype(jnp.float32)
                if quant:
                    x = x * sc_refs[gi][0, kv, h]
                parts.append(x)
            return jnp.concatenate(parts, axis=0)

        # a static loop of 2-D dots per kv head: Mosaic lowers these where
        # a 4-D (C, Kv, G, hd) reshape of q has no supported layout
        for h in range(kv_heads):
            q = q_ref[0, h].astype(jnp.float32)            # (R, hd)
            s = jax.lax.dot_general(
                q, page_rows(0, h), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (R, T)
            s = jnp.where(mask, s, NEG_INF)
            m_prev, l_prev = m_ref[h], l_ref[h]             # (R, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, page_rows(1, h), preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    last_step = (jnp.maximum(npages, 1) + g_pages - 1) // g_pages - 1

    @pl.when(j == last_step)
    def _flush():
        lsum = l_ref[...]
        safe = jnp.where(lsum == 0.0, 1.0, lsum)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)  # (Kv, R, hd)


@functools.partial(jax.jit, static_argnames=("pages_per_step", "interpret",
                                             "scale"))
def paged_prefill_fwd(q: jax.Array, kv_pages: jax.Array,
                      block_table: jax.Array, page_counts: jax.Array,
                      lengths: jax.Array, q_start: jax.Array, *,
                      pages_per_step: int = 2, scale: float | None = None,
                      interpret: bool = False,
                      kv_scales: jax.Array | None = None) -> jax.Array:
    """q: (B,C,H,hd) — a chunk of C query tokens per request, occupying
    positions ``q_start[b] .. q_start[b]+C-1``; their K/V must already be
    written into the pool (``lengths`` includes them).  Other args as
    ``paged_decode_fwd``.  Rows past a request's real chunk length attend
    to the full resident sequence (callers ignore them).

    When ``kv_scales`` is given — (P, 2, Kv) float32, one scale per (page,
    K/V, kv-head) — the pool is int8 and each fetched page block is
    dequantized in-kernel before the attention math; the scale blocks ride
    the same page-indexed DMA as their K/V pages, so the extra traffic is
    4 bytes per (K/V, head) per page.

    Returns (B,C,H,hd)."""
    B, C, H, hd = q.shape
    P, _, page, Kv, _ = kv_pages.shape
    n_pages = block_table.shape[1]
    g = max(1, min(pages_per_step, n_pages))
    n_steps = _cdiv(n_pages, g)
    groups = H // Kv
    R = C * groups
    sc = scale if scale is not None else 1.0 / math.sqrt(hd)
    quant = kv_scales is not None
    # q per kv head: (B, Kv, C*G, hd), row c*G + g holds query head k*G + g
    # of chunk token c (the kv-major GQA head layout)
    qh = q.reshape(B, C, Kv, groups, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Kv, R, hd)

    def page_imap(gi):
        def imap(b, j, bt, cnt, ln, st):
            idx = jnp.minimum(j * g + gi, n_pages - 1)
            return bt[b, idx]
        return imap

    def kv_spec(gi):
        im = page_imap(gi)
        return pl.BlockSpec((1, 2, page, Kv, hd),
                            lambda b, j, *a, _im=im: (_im(b, j, *a), 0, 0, 0, 0))

    def sc_spec(gi):
        im = page_imap(gi)
        return pl.BlockSpec((1, 2, Kv),
                            lambda b, j, *a, _im=im: (_im(b, j, *a), 0, 0))

    q_spec = pl.BlockSpec((1, Kv, R, hd), lambda b, j, *_: (b, 0, 0, 0))
    in_specs = [q_spec] + [kv_spec(gi) for gi in range(g)]
    operands = [qh] + [kv_pages] * g
    if quant:
        in_specs += [sc_spec(gi) for gi in range(g)]
        operands += [kv_scales] * g

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, n_steps),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Kv, R, 1), jnp.float32),
            pltpu.VMEM((Kv, R, 1), jnp.float32),
            pltpu.VMEM((Kv, R, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=sc, page_size=page,
                          g_pages=g, groups=groups, kv_heads=Kv,
                          quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, R, hd), q.dtype),
        interpret=interpret,
    )(block_table, page_counts, lengths, q_start, *operands)
    return out.reshape(B, Kv, C, groups, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, C, H, hd)
