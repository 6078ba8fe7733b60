"""Public paged-attention ops (decode + chunked-prefill fast paths).

Two API levels:

* ``paged_attention`` / ``paged_prefill`` — convenience wrappers over
  separate K/V pools and -1-marked block tables (the host-friendly form the
  tests and older callers use).  They fuse K/V and repeat-pad the table per
  call, which costs a stack + gather.
* ``paged_decode_fused`` / ``paged_prefill_fused`` — zero-overhead entry
  points for callers (the serving engine) that natively maintain the fused
  ``(P, 2, page, Kv, hd)`` pool and a repeat-padded device block table.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import kernel as K
from repro.kernels.paged_attention.ref import (
    paged_attention_ref, paged_prefill_ref,
)

__all__ = [
    "paged_attention", "paged_prefill", "paged_decode_fused",
    "paged_prefill_fused", "pad_block_table", "page_counts_for",
    "paged_attention_ref", "paged_prefill_ref", "validate_head_sharding",
]


def validate_head_sharding(num_heads: int, num_kv_heads: int,
                           shards: int) -> int:
    """Check a tensor-parallel head split is GQA-safe for these kernels.

    The kernels' head layout is kv-major: query head ``k*G + g`` reads kv
    head ``k`` (``G = H // Kv``).  A split into ``shards`` equal contiguous
    blocks therefore keeps every query head on the same shard as its kv
    head iff ``shards`` divides ``num_kv_heads``.  Returns the per-shard
    kv-head count; raises ``ValueError`` on an unsafe split.
    """
    if shards < 1:
        raise ValueError(f"head shards must be >= 1, got {shards}")
    if num_heads % max(num_kv_heads, 1):
        raise ValueError(f"H={num_heads} not a multiple of Kv={num_kv_heads}")
    if num_kv_heads % shards:
        raise ValueError(
            f"head axis {shards} does not divide num_kv_heads="
            f"{num_kv_heads}: a shard would split a GQA group across "
            f"devices and the block-table gather could not stay local")
    return num_kv_heads // shards


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _itp(interpret):
    return (not _on_tpu()) if interpret is None else interpret


def page_counts_for(lengths: jax.Array, page_size: int) -> jax.Array:
    """(B,) number of mapped logical pages implied by token counts."""
    return (lengths + page_size - 1) // page_size


def pad_block_table(block_table: jax.Array, page_counts: jax.Array
                    ) -> jax.Array:
    """-1-marked (B, n_pages) table -> repeat-padded form the kernels want.

    Entries past ``page_counts[b]`` are replaced by the last mapped page so
    consecutive trailing grid steps resolve to the same block (DMA elided).

    Contract: mapping must be *dense* — every logical page below
    ``page_counts[b]`` mapped (>= 0), -1 only past the mapped prefix (what
    ``PagedKVPool`` produces: pages are allocated in logical order and the
    count derives from the token length).  An interior -1 hole would be
    silently remapped to physical page 0 here, where the masked oracle
    (``paged_attention_ref``) would exclude it.
    """
    n_pages = block_table.shape[1]
    idx = jnp.arange(n_pages, dtype=jnp.int32)[None, :]
    last = jnp.maximum(page_counts - 1, 0).astype(jnp.int32)[:, None]
    return jnp.take_along_axis(jnp.maximum(block_table, 0),
                               jnp.minimum(idx, last), axis=1)


def paged_attention(q, k_pages, v_pages, block_table, lengths, *,
                    interpret=None, pages_per_step: int | None = None):
    """Decode fast path, host-friendly form.

    q: (B,H,hd); k/v_pages: (P, page, Kv, hd); block_table: (B, max_pages)
    int32 physical page ids, densely mapped for the first
    ``ceil(length/page)`` logical pages and -1 past them (see
    ``pad_block_table``); lengths: (B,).  Returns (B,H,hd).
    """
    counts = page_counts_for(lengths, k_pages.shape[1])
    return K.paged_decode_fwd(
        q, jnp.stack([k_pages, v_pages], axis=1),
        pad_block_table(block_table, counts), counts, lengths,
        pages_per_step=pages_per_step, interpret=_itp(interpret))


def paged_prefill(q, k_pages, v_pages, block_table, lengths, q_start, *,
                  interpret=None, pages_per_step: int | None = None):
    """Chunked-prefill fast path, host-friendly form.

    q: (B,C,H,hd) — C chunk tokens at positions q_start..q_start+C-1, whose
    K/V are already in the pool; other args as ``paged_attention``.
    """
    counts = page_counts_for(lengths, k_pages.shape[1])
    return K.paged_prefill_fwd(
        q, jnp.stack([k_pages, v_pages], axis=1),
        pad_block_table(block_table, counts), counts, lengths, q_start,
        pages_per_step=pages_per_step, interpret=_itp(interpret))


def paged_decode_fused(q, kv_pages, block_table, page_counts, lengths, *,
                       interpret=None, pages_per_step: int | None = None,
                       kv_scales=None):
    """Decode on a fused pool + repeat-padded device block table.  Pass
    ``kv_scales`` ((P, 2, Kv) f32) for an int8 pool — dequant happens
    inside the kernel's K/V fetch."""
    return K.paged_decode_fwd(q, kv_pages, block_table, page_counts, lengths,
                              pages_per_step=pages_per_step,
                              interpret=_itp(interpret), kv_scales=kv_scales)


def paged_prefill_fused(q, kv_pages, block_table, page_counts, lengths,
                        q_start, *, interpret=None,
                        pages_per_step: int | None = None, kv_scales=None):
    """Chunked prefill on a fused pool + repeat-padded device block table.
    ``kv_scales`` as in ``paged_decode_fused``."""
    return K.paged_prefill_fwd(q, kv_pages, block_table, page_counts,
                               lengths, q_start,
                               pages_per_step=pages_per_step,
                               interpret=_itp(interpret), kv_scales=kv_scales)
