"""Kernel sweeps: shapes x dtypes, interpret-mode vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cluster_matmul import cluster_matmul, cluster_matmul_ref
from repro.kernels.flash_attention import (
    flash_attention, flash_attention_ref, mha_flash,
)
from repro.kernels.paged_attention import (
    paged_attention, paged_attention_ref, paged_prefill, paged_prefill_ref,
    paged_prefill_fused, pad_block_table, page_counts_for,
)
from repro.kernels.paged_attention.kernel import kv_pages_per_step
from repro.optim.compress import headwise_scales, quantize_int8

TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 512),
                                   (384, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cluster_matmul(m, k, n, dtype, rng):
    a = jax.random.normal(rng, (m, k), jnp.float32).astype(dtype)
    b = jax.random.normal(jax.random.fold_in(rng, 1), (k, n),
                          jnp.float32).astype(dtype)
    out = cluster_matmul(a, b, interpret=True)
    ref = cluster_matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("bhsd", [(2, 128, 128, 64), (4, 256, 128, 32),
                                  (1, 128, 384, 128)])
@pytest.mark.parametrize("causal,window,cap", [
    (True, 0, 0.0), (False, 0, 0.0), (True, 64, 0.0), (True, 0, 50.0),
    (True, 32, 30.0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(bhsd, causal, window, cap, dtype, rng):
    BH, S, T, d = bhsd
    q = (jax.random.normal(rng, (BH, S, d), jnp.float32) * 0.3).astype(dtype)
    k = (jax.random.normal(jax.random.fold_in(rng, 1), (BH, T, d),
                           jnp.float32) * 0.3).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (BH, T, d),
                          jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal, window, cap, True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_flash_attention_grad(rng):
    q = jax.random.normal(rng, (2, 128, 32), jnp.float32) * 0.3
    k = jax.random.normal(jax.random.fold_in(rng, 1), (2, 128, 32),
                          jnp.float32) * 0.3
    v = jax.random.normal(jax.random.fold_in(rng, 2), (2, 128, 32),
                          jnp.float32)
    g = jax.grad(lambda q_: flash_attention(q_, k, v, True, 0, 0.0,
                                            True).sum())(q)
    gr = jax.grad(lambda q_: flash_attention_ref(q_, k, v,
                                                 causal=True).sum())(q)
    np.testing.assert_allclose(g, gr, rtol=2e-3, atol=2e-3)


def test_mha_flash_gqa_grad(rng):
    """The groups>1 backward (repeat-based VJP over the unexpanded KV
    layout) must sum per-group grads back onto the shared KV heads."""
    B, S, H, Kv, hd = 2, 128, 4, 2, 32
    q = jax.random.normal(rng, (B, S, H, hd), jnp.float32) * 0.3
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, Kv, hd),
                          jnp.float32) * 0.3
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, Kv, hd),
                          jnp.float32)
    from repro.models.attention import attend_fullseq
    pos = jnp.arange(S, dtype=jnp.int32)

    def loss_kernel(q_, k_, v_):
        return (mha_flash(q_, k_, v_, interpret=True) ** 2).sum()

    def loss_ref(q_, k_, v_):
        out = attend_fullseq(q_, k_, v_, q_positions=pos, k_positions=pos,
                             causal=True)
        return (out ** 2).sum()

    g = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_mha_flash_gqa(rng):
    B, S, H, Kv, hd = 2, 128, 8, 2, 32
    q = jax.random.normal(rng, (B, S, H, hd), jnp.float32) * 0.3
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, Kv, hd),
                          jnp.float32) * 0.3
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, Kv, hd),
                          jnp.float32)
    out = mha_flash(q, k, v, interpret=True)
    from repro.models.attention import attend_fullseq
    pos = jnp.arange(S, dtype=jnp.int32)
    ref = attend_fullseq(q, k, v, q_positions=pos, k_positions=pos,
                         causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("B,H,Kv,hd,page,npg,P", [
    (3, 8, 4, 32, 8, 6, 16),
    (2, 4, 4, 64, 16, 4, 8),
    (1, 16, 2, 128, 8, 8, 12),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention(B, H, Kv, hd, page, npg, P, dtype, rng):
    q = (jax.random.normal(rng, (B, H, hd), jnp.float32) * 0.3).astype(dtype)
    kp = (jax.random.normal(jax.random.fold_in(rng, 1), (P, page, Kv, hd),
                            jnp.float32) * 0.3).astype(dtype)
    vp = jax.random.normal(jax.random.fold_in(rng, 2), (P, page, Kv, hd),
                           jnp.float32).astype(dtype)
    lengths = np.minimum(
        np.asarray(jax.random.randint(jax.random.fold_in(rng, 3), (B,), 1,
                                      npg * page)), npg * page).astype(np.int32)
    bt = np.full((B, npg), -1, np.int32)
    nxt = 0
    for i, ln in enumerate(lengths):
        for j in range(-(-int(ln) // page)):
            bt[i, j] = nxt % P
            nxt += 1
    out = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths),
                          interpret=True)
    ref = paged_attention_ref(q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


# ---------------------------------------------------------------------------
# chunked prefill + multi-page decode parity
# ---------------------------------------------------------------------------

def _paged_pool(rng, B, Kv, hd, page, npg, P, lengths):
    """Random pool + a block table mapping ceil(len/page) pages per lane."""
    kp = jax.random.normal(jax.random.fold_in(rng, 1), (P, page, Kv, hd),
                           jnp.float32) * 0.3
    vp = jax.random.normal(jax.random.fold_in(rng, 2), (P, page, Kv, hd),
                           jnp.float32)
    bt = np.full((B, npg), -1, np.int32)
    nxt = 0
    for i, ln in enumerate(lengths):
        for j in range(-(-int(ln) // page)):
            bt[i, j] = nxt % P
            nxt += 1
    return kp, vp, jnp.asarray(bt)


# pages per grid step: one and two pages, the value the kernel derives from
# the shapes (None), and one wider than the block table (clamped to it)
WIDE = 99
PAGES_PER_STEP = [1, 2, None, WIDE]


@pytest.mark.parametrize("pages_per_step", [1, 2, 3, None, WIDE])
def test_paged_decode_multi_page_grid(pages_per_step, rng):
    """Multi-page decode grid is numerically identical to the oracle for
    every pages-per-step grouping (incl. groups that don't divide npg)."""
    B, H, Kv, hd, page, npg, P = 3, 8, 4, 32, 8, 7, 24
    q = jax.random.normal(rng, (B, H, hd), jnp.float32) * 0.3
    lengths = np.array([1, 29, 56], np.int32)
    kp, vp, bt = _paged_pool(rng, B, Kv, hd, page, npg, P, lengths)
    out = paged_attention(q, kp, vp, bt, jnp.asarray(lengths),
                          interpret=True, pages_per_step=pages_per_step)
    ref = paged_attention_ref(q, kp, vp, bt, jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("C,H,Kv,hd,page,npg,P", [
    (8, 8, 4, 32, 4, 10, 24),      # G=2
    (16, 8, 2, 16, 8, 6, 32),      # G=4
    (5, 6, 6, 32, 16, 3, 8),       # G=1, chunk not a divisor of anything
])
@pytest.mark.parametrize("pages_per_step", PAGES_PER_STEP)
def test_paged_prefill_matches_ref(C, H, Kv, hd, page, npg, P,
                                   pages_per_step, rng):
    """Chunked prefill vs the dense oracle across random prompt lengths,
    page sizes and GQA group counts."""
    B = 3
    cap = npg * page - C
    start = np.asarray(jax.random.randint(jax.random.fold_in(rng, 5), (B,),
                                          0, max(cap, 1))).astype(np.int32)
    lengths = (start + C).astype(np.int32)
    q = jax.random.normal(rng, (B, C, H, hd), jnp.float32) * 0.3
    kp, vp, bt = _paged_pool(rng, B, Kv, hd, page, npg, P, lengths)
    out = paged_prefill(q, kp, vp, bt, jnp.asarray(lengths),
                        jnp.asarray(start), interpret=True,
                        pages_per_step=pages_per_step)
    ref = paged_prefill_ref(q, kp, vp, bt, jnp.asarray(lengths),
                            jnp.asarray(start))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("pages_per_step", PAGES_PER_STEP)
def test_paged_prefill_fused_aliased_pages(pages_per_step, rng):
    """Shared-prefix block tables alias the same physical pages across
    lanes (prefix-cache hits); the fused kernel must match the oracle when
    reads of one physical page serve several lanes at different logical
    positions."""
    B, C, H, Kv, hd, page, npg, P = 3, 4, 4, 2, 16, 4, 4, 10
    # lanes share physical pages 0 and 1 for their first two logical pages
    # (a 8-token shared prefix), then diverge into private tails
    lengths = np.array([12, 11, 10], np.int32)
    start = (lengths - C).astype(np.int32)
    bt = np.full((B, npg), -1, np.int32)
    bt[0, :3] = [0, 1, 2]
    bt[1, :3] = [0, 1, 3]
    bt[2, :3] = [0, 1, 4]
    q = jax.random.normal(rng, (B, C, H, hd), jnp.float32) * 0.3
    kp = jax.random.normal(jax.random.fold_in(rng, 1), (P, page, Kv, hd),
                           jnp.float32) * 0.3
    vp = jax.random.normal(jax.random.fold_in(rng, 2), (P, page, Kv, hd),
                           jnp.float32)
    counts = page_counts_for(jnp.asarray(lengths), page)
    out = paged_prefill_fused(
        q, jnp.stack([kp, vp], axis=1),
        pad_block_table(jnp.asarray(bt), counts), counts,
        jnp.asarray(lengths), jnp.asarray(start), interpret=True,
        pages_per_step=pages_per_step)
    ref = paged_prefill_ref(q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths),
                            jnp.asarray(start))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("H,Kv", [(32, 4), (32, 8)])
def test_paged_prefill_fused_at_cell_head_groupings(H, Kv, kv_dtype, rng):
    """The benchmark cells' GQA groupings (yi-6b: 32 heads over 4 KV heads,
    Mistral-NeMo-12B: over 8) at a small head dim, through the engine's
    fused entry at the derived pages per step (16 pages of 8 tokens): a
    lane over two grid steps, one inside its first page, one empty."""
    B, C, hd, page, npg, P = 3, 4, 16, 8, 20, 48
    lengths = np.array([150, 5, 0], np.int32)
    start = np.maximum(lengths - C, 0).astype(np.int32)
    q = jax.random.normal(rng, (B, C, H, hd), jnp.float32) * 0.3
    kp, vp, bt = _paged_pool(rng, B, Kv, hd, page, npg, P, lengths)
    counts = page_counts_for(jnp.asarray(lengths), page)
    pool = jnp.stack([kp, vp], axis=1)               # (P, 2, page, Kv, hd)
    scales, ref_scales = None, {}
    if kv_dtype == "int8":
        scales = headwise_scales(pool.transpose(0, 1, 3, 2, 4)
                                 .reshape(P, 2, Kv, page * hd))  # (P,2,Kv)
        pool = quantize_int8(pool, scales[:, :, None, :, None])
        ref_scales = dict(k_scales=scales[:, 0], v_scales=scales[:, 1])
    else:
        pool = pool.astype(jnp.bfloat16)
    assert kv_pages_per_step(page, npg) == 16
    out = paged_prefill_fused(
        q, pool, pad_block_table(bt, counts), counts, jnp.asarray(lengths),
        jnp.asarray(start), interpret=True, kv_scales=scales)
    ref = paged_prefill_ref(q, pool[:, 0], pool[:, 1], bt,
                            jnp.asarray(lengths), jnp.asarray(start),
                            **ref_scales)
    live = lengths > 0
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("page,width,want", [
    (8, 320, 16),       # the benchmark cells: 128 tokens per grid step
    (8, 2, 2),          # clamped to the block table's width
    (128, 320, 1),      # a page of 128 tokens or more: one page a step
    (256, 320, 1),
])
def test_kv_pages_per_step(page, width, want):
    assert kv_pages_per_step(page, width) == want


def test_paged_prefill_matches_token_by_token(rng):
    """A whole chunk through the prefill kernel equals feeding the same
    positions one at a time through the decode kernel (the pre-chunked
    engine's path)."""
    B, C, H, Kv, hd, page, npg, P = 2, 8, 4, 2, 16, 4, 6, 16
    start = np.array([0, 5], np.int32)
    lengths = (start + C).astype(np.int32)
    q = jax.random.normal(rng, (B, C, H, hd), jnp.float32) * 0.3
    kp, vp, bt = _paged_pool(rng, B, Kv, hd, page, npg, P, lengths)
    chunked = np.asarray(paged_prefill(q, kp, vp, bt, jnp.asarray(lengths),
                                       jnp.asarray(start), interpret=True))
    for c in range(C):
        step_len = jnp.asarray((start + c + 1).astype(np.int32))
        one = paged_attention(q[:, c], kp, vp, bt, step_len, interpret=True)
        np.testing.assert_allclose(chunked[:, c], np.asarray(one),
                                   rtol=1e-4, atol=1e-4)
