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
    KV pages with a single online-softmax rescale plus the causal in-chunk
    mask (the chunk's own K/V are pool-resident by the time the kernel
    runs, so one mask covers both history and in-chunk causality).  q
    enters laid out per KV head, ``(B, Kv, C*G, hd)``, and the step loops
    over the KV heads, so every product is a 2-D dot.

``paged_decode_fwd``
    One query token per request — the C=1 special case of the above (with
    ``q_start = lengths - 1`` the masks coincide), kept as its own entry
    point for the engine's decode path.

Both take a *fused* KV pool of shape ``(P, 2, page, Kv, hd)`` — K and V for
a page live in one block and are fetched together — and a *repeat-padded*
block table: entries past a request's last mapped page hold the last
mapped physical page (never -1).  A scalar-prefetched per-request page
count (``page_counts``) gates the compute.  ``ops.pad_block_table``
produces the padded form from a -1-marked table.

Pages per grid step.  ``G`` follows the shapes: ``kv_pages_per_step(page,
n_pages)``, ``KV_TOKENS_PER_STEP`` tokens' worth (one 128-lane row of
scores per query row), capped at the table's width; ``pages_per_step``
overrides it.  The grid walks every step of every lane, and the table is
as wide as the longest request, so most steps hold no page of their lane;
what such a step costs is the grid's per-step overhead, so the kernel's
time is counted in grid steps, and ``G`` sets how many there are.

Fetching.  Where a DMA can take one page out of the pool's HBM tiling
(``_page_dma_aligned``: bf16 pools of 2, 4, 8 or 16 KV heads among them),
the pool stays in HBM and the kernel
copies a step's pages itself into a double buffer, the next step's pages
in flight while this one computes: only the pages the lane has, so a step
past them costs neither fetch nor FLOPs, nor any work per page.  Otherwise
the ``G`` pages come as ``G`` pipelined block operands, whose index maps
repeat the last page past a lane's end (the pipeline then elides the
DMA), at a fixed bookkeeping cost per operand per grid step.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: KV tokens one grid step attends: one 128-lane row of scores per query
#: row (16 pages of 8 tokens)
KV_TOKENS_PER_STEP = 128


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _page_dma_aligned(dtype, kv_heads: int) -> bool:
    """Whether a DMA can take one page, ``(2, page, Kv, hd)``, out of the
    pool in HBM: XLA tiles its ``(Kv, hd)`` rows in groups of a power of
    two of at least the dtype's packing (f32 1, bf16 2, int8 4 rows per
    32-bit sublane), up to 8 sublanes, and Mosaic slices only whole tiles."""
    pack = max(1, 4 // jnp.dtype(dtype).itemsize)
    pow2 = kv_heads & (kv_heads - 1) == 0
    return (pow2 and kv_heads >= pack) or kv_heads % (8 * pack) == 0


def kv_pages_per_step(page_size: int, n_pages: int) -> int:
    """The pages one grid step fetches: ``KV_TOKENS_PER_STEP`` tokens'
    worth, at least one page and at most the block table's width."""
    return min(n_pages, max(1, KV_TOKENS_PER_STEP // page_size))


# ===========================================================================
# decode: one query token, G pages per grid step
# ===========================================================================

@functools.partial(jax.jit, static_argnames=("pages_per_step", "interpret",
                                             "scale"))
def paged_decode_fwd(q: jax.Array, kv_pages: jax.Array,
                     block_table: jax.Array, page_counts: jax.Array,
                     lengths: jax.Array, *,
                     pages_per_step: int | None = None,
                     scale: float | None = None,
                     interpret: bool = False,
                     kv_scales: jax.Array | None = None) -> jax.Array:
    """q: (B,H,hd); kv_pages: (P, 2, page, Kv, hd) fused K/V pool;
    block_table: (B, max_pages) int32 physical page ids, repeat-padded (no
    -1; see module docstring); page_counts: (B,) mapped logical pages per
    request; lengths: (B,) tokens per request; kv_scales: optional
    (P, 2, Kv) float32 per-page dequantization scales for an int8 pool
    (see ``paged_prefill_fwd``); pages_per_step: pages per grid step,
    None for ``kv_pages_per_step`` of the shapes.

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
                    kv_heads: int, quant: bool, manual: bool):
    # manual: the pool (and its scale rows) in HBM, a step's pages copied
    # by the kernel into a double buffer; else g pipelined page operands
    n_src = 1 if manual else g_pages
    kv_src, refs = refs[:n_src], refs[n_src:]
    sc_src, refs = (refs[:n_src], refs[n_src:]) if quant else ((), refs)
    o_ref, *scratch = refs
    m_ref, l_ref, acc_ref = scratch[-3:]
    b, j = pl.program_id(0), pl.program_id(1)
    npages = cnt_ref[b]

    if manual:
        kv_buf, sem = scratch[0], scratch[-4]
        sc_buf = scratch[1] if quant else None

        def page_copies(step, slot):
            # (live, copies) per page of a step: logical page step*g + gi
            # of the lane into buffer slot ``slot``, live below the count
            out = []
            for gi in range(g_pages):
                lp = step * g_pages + gi
                page = bt_ref[b, jnp.minimum(lp, npages - 1)]
                cps = [pltpu.make_async_copy(
                    kv_src[0].at[page], kv_buf.at[slot, gi], sem.at[slot])]
                if quant:
                    cps.append(pltpu.make_async_copy(
                        sc_src[0].at[page], sc_buf.at[slot, gi],
                        sem.at[slot]))
                out.append((lp < npages, cps))
            return out

        def each(step, slot, op):
            for live, cps in page_copies(step, slot):
                @pl.when(live)
                def _(cps=cps):
                    for c in cps:
                        getattr(c, op)()

        def start(step, slot):
            each(step, slot, "start")

        def wait(step, slot):
            each(step, slot, "wait")

        @pl.when((b == 0) & (j == 0))
        def _clear():
            # past a lane's last page a buffer holds what an earlier step
            # fetched there (masked); clear what none has, so no NaN left
            # in VMEM reaches p @ V
            kv_buf[...] = jnp.zeros_like(kv_buf)
            if quant:
                sc_buf[...] = jnp.zeros_like(sc_buf)

        @pl.when((j == 0) & (npages > 0))
        def _first():
            start(0, 0)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * g_pages < npages)
    def _accumulate():
        if manual:
            slot = j % 2

            @pl.when((j + 1) * g_pages < npages)
            def _next():
                start(j + 1, 1 - slot)  # the next step's pages in flight
            wait(j, slot)               # while this step computes

            def rows(gi, kv, h):
                return kv_buf[slot, gi, kv, :, h, :]

            def page_scale(gi, i):
                return sc_buf[slot, gi, 0, i]
        else:
            def rows(gi, kv, h):
                return kv_src[gi][0, kv, :, h, :]

            def page_scale(gi, i):
                return sc_src[gi][0, 0, i]

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
            for gi in range(g_pages):
                x = rows(gi, kv, h).astype(jnp.float32)
                if quant:
                    x = x * page_scale(gi, kv * kv_heads + h)
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
                      pages_per_step: int | None = None,
                      scale: float | None = None,
                      interpret: bool = False,
                      kv_scales: jax.Array | None = None) -> jax.Array:
    """q: (B,C,H,hd) — a chunk of C query tokens per request, occupying
    positions ``q_start[b] .. q_start[b]+C-1``; their K/V must already be
    written into the pool (``lengths`` includes them).  Other args as
    ``paged_decode_fwd``.  Rows past a request's real chunk length attend
    to the full resident sequence (callers ignore them).

    When ``kv_scales`` is given — (P, 2, Kv) float32, one scale per (page,
    K/V, kv-head) — the pool is int8 and each fetched page block is
    dequantized in-kernel before the attention math; each page's scales
    travel beside it as one lane-aligned row of 128 floats.

    Returns (B,C,H,hd)."""
    B, C, H, hd = q.shape
    P, _, page, Kv, _ = kv_pages.shape
    n_pages = block_table.shape[1]
    g = kv_pages_per_step(page, n_pages) if pages_per_step is None \
        else max(1, min(pages_per_step, n_pages))
    n_steps = _cdiv(n_pages, g)
    groups = H // Kv
    R = C * groups
    sc = scale if scale is not None else 1.0 / math.sqrt(hd)
    quant = kv_scales is not None
    # q per kv head: (B, Kv, C*G, hd), row c*G + g holds query head k*G + g
    # of chunk token c (the kv-major GQA head layout)
    qh = q.reshape(B, C, Kv, groups, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Kv, R, hd)

    q_spec = pl.BlockSpec((1, Kv, R, hd), lambda b, j, *_: (b, 0, 0, 0))
    sources = [kv_pages]
    if quant:
        # one lane-aligned row of scales per page (K's heads, then V's): a
        # page's (2, Kv) scale block is not a slice a DMA can take
        width = _cdiv(2 * Kv, 128) * 128
        sources.append(jnp.pad(kv_scales.reshape(P, 1, 2 * Kv),
                               ((0, 0), (0, 0), (0, width - 2 * Kv))))
    scratch = [pltpu.VMEM((Kv, R, 1), jnp.float32),
               pltpu.VMEM((Kv, R, 1), jnp.float32),
               pltpu.VMEM((Kv, R, hd), jnp.float32)]
    # the kernel copies a step's pages itself where a DMA can take a page
    # out of the pool's tiling, else they come as g pipelined operands
    manual = _page_dma_aligned(kv_pages.dtype, Kv)
    if manual:
        hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
        in_specs = [q_spec] + [hbm] * len(sources)
        operands = [qh] + sources
        scratch = [pltpu.VMEM((2, g, 2, page, Kv, hd), kv_pages.dtype)] + \
            ([pltpu.VMEM((2, g, 1, width), jnp.float32)] if quant else []) + \
            [pltpu.SemaphoreType.DMA((2,))] + scratch
    else:
        def page_spec(gi, block):
            # past its lane's last page an operand resolves to that page
            # (the table is repeat-padded), so the block repeats and the
            # pipeline elides the DMA
            def imap(b, j, bt, *_):
                page_id = bt[b, jnp.minimum(j * g + gi, n_pages - 1)]
                return (page_id,) + (0,) * (len(block) - 1)
            return pl.BlockSpec(block, imap)

        blocks = [(1, 2, page, Kv, hd)] + ([(1, 1, width)] if quant else [])
        in_specs = [q_spec] + [page_spec(gi, blk) for blk in blocks
                               for gi in range(g)]
        operands = [qh] + [src for src in sources for _ in range(g)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, n_steps),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=sc, page_size=page,
                          g_pages=g, groups=groups, kv_heads=Kv,
                          quant=quant, manual=manual),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, R, hd), q.dtype),
        interpret=interpret,
    )(block_table, page_counts, lengths, q_start, *operands)
    return out.reshape(B, Kv, C, groups, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, C, H, hd)
