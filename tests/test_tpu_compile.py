"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology, with the interpreter off, and refuses
what the chip's compiler would refuse (tiling, layouts, VMEM). Nothing runs,
so these tests say nothing of results; ``tests/test_kernels.py`` checks those
in interpret mode. The topology is described inside a fixture, never at
import: only one process may load the TPU library at a time.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.aggregate.kernel import (BLOCK_ROWS, TILE_GROUPS, TILE_N,
                                           sum_product_pallas,
                                           sum_product_pallas_packed)
from repro.kernels.dequant.kernel import dequant_pallas
from repro.kernels.filter.kernel import range_mask_pallas

N_ROWS = 65536          # one production row group


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU program written to a persistent cache cannot be read back
    # without a chip; keep such compiles out of any cache a test turned on
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, *shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("n_cols", [1, 3])
def test_range_mask_compiles_for_v5e(one_chip, n_cols):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in ((n_cols, N_ROWS), (n_cols,), (n_cols,))]
    lowered = range_mask_pallas.lower(*args, interpret=False)
    # the kernel's own name, whatever the call's shape, and the jitted
    # module's name, which the benchmark's roofline reader matches
    assert 'kernel_name = "range_mask"' in lowered.as_text()
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert "HloModule jit_range_mask_pallas" in text


@pytest.mark.parametrize("q_dtype", [jnp.int8, jnp.uint16],
                         ids=["int8_affine", "bf16_bits"])
def test_dequant_compiles_for_v5e(one_chip, q_dtype):
    fn = jax.jit(lambda q, s, z: dequant_pallas(q, s, z,
                                                out_dtype=jnp.float32,
                                                interpret=False))
    text = _compile_text(fn, ((N_ROWS, 256), q_dtype),
                         ((256,), jnp.float32), ((256,), jnp.float32),
                         sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_cols", [2, 4])
def test_sum_product_compiles_for_v5e(one_chip, n_cols):
    """The fused filter-and-sum kernel at one row group: TPC-H Q6 reads
    four int32 columns (three predicate columns and the price)."""
    lanes = N_ROWS // 128
    assert lanes % BLOCK_ROWS == 0
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in ((n_cols, lanes, 128), (2 * n_cols + 1, 1, 128))]
    lowered = sum_product_pallas.lower(*args, a=n_cols - 1, b=1,
                                       interpret=False)
    assert 'kernel_name = "sum_product"' in lowered.as_text()
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert "HloModule jit_sum_product_pallas" in text


def test_sum_product_packed_compiles_for_v5e(one_chip):
    """The packed form at one row group of TPC-H Q6's pages: quantity,
    price and discount bit-packed at 6, 24 and 4 bits, the ship date
    frame-of-reference at 12 bits with a base a row."""
    widths = (12, 4, 6, 24)
    tiles = N_ROWS // TILE_N
    rows = tiles * 8
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((tiles * TILE_GROUPS * sum(widths),), jnp.int32),
        ((2 * len(widths) + 1 + len(widths) * rows,), jnp.int32))]
    lowered = sum_product_pallas_packed.lower(
        *args, widths=widths, based=(True, False, False, False), a=3, b=1,
        interpret=False)
    assert 'kernel_name = "sum_product_packed"' in lowered.as_text()
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert "HloModule jit_sum_product_pallas_packed" in text
