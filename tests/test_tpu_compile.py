"""The paged-attention kernel compiled for a described TPU v5e at yi-6b's
published widths (32 query heads over 4 KV heads, head dim 128, page 8),
and at the benchmark cells' widths and block-table width, where the pages
per grid step derived from the shapes set the kernel's VMEM footprint.

The TPU compiler is installed even where no chip is attached, and it
refuses what Pallas interpret mode accepts: layouts Mosaic cannot lower,
VMEM overruns.  These compiles catch that before any chip run.  Only the
``one_chip`` fixture loads the TPU library, so collecting this file (in
any number of test workers) never does.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import kernel as K

# yi-6b attention widths, and the serving shapes of the one-chip smoke run
HEADS, KV_HEADS, HEAD_DIM = 32, 4, 128
PAGE, LANES, POOL_PAGES, MAX_PAGES = 8, 8, 1025, 64
# the benchmark cells: 32 lanes, a block table as wide as the longest
# request (2,560 tokens), yi-6b's 4 KV heads and Mistral-NeMo-12B's 8
CELL_LANES, CELL_MAX_PAGES = 32, 320
CELL_KV_HEADS = {"yi-6b": 4, "mistral-nemo-12b": 8}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs on disk
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without a chip: keep it off
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)


def _compile_kernel(where, C, heads, kv_heads, kv_dtype, *, lanes=LANES,
                    max_pages=MAX_PAGES):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)

    lane = arg((lanes,), jnp.int32)
    pool_dtype = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    scales = arg((POOL_PAGES, 2, kv_heads), jnp.float32) \
        if kv_dtype == "int8" else None
    compiled = K.paged_prefill_fwd.lower(
        arg((lanes, C, heads, HEAD_DIM), jnp.bfloat16),
        arg((POOL_PAGES, 2, PAGE, kv_heads, HEAD_DIM), pool_dtype),
        arg((lanes, max_pages), jnp.int32), lane, lane, lane,
        kv_scales=scales).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("C", [1, 5, 16])
def test_paged_kernel_compiles_at_yi6b_widths(one_chip, C, kv_dtype):
    """Decode (C=1), an odd chunk (5) and the engine's default chunk (16)."""
    _compile_kernel(one_chip, C, HEADS, KV_HEADS, kv_dtype)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_head_sharded_decode_kernel_compiles(one_chip, kv_dtype):
    """One shard of a 2-way head split holds 2 KV heads and their 16 query
    heads (``ShardedPagedServer`` with heads=2)."""
    _compile_kernel(one_chip, 1, HEADS // 2, KV_HEADS // 2, kv_dtype)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("C", [1, 16])
@pytest.mark.parametrize("model", sorted(CELL_KV_HEADS))
def test_paged_kernel_compiles_at_cell_widths(one_chip, model, C, kv_dtype):
    """The decode and chunk calls of both benchmark cells, at the pages per
    grid step the kernel derives from page 8 and 320 table columns."""
    assert K.kv_pages_per_step(PAGE, CELL_MAX_PAGES) == 16
    _compile_kernel(one_chip, C, HEADS, CELL_KV_HEADS[model], kv_dtype,
                    lanes=CELL_LANES, max_pages=CELL_MAX_PAGES)
