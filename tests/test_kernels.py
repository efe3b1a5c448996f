"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.dequant import dequant, dequant_ref
from repro.kernels.filter import range_mask, range_mask_ref
from repro.kernels.flash_attention import attention_ref, flash_attention


@pytest.mark.parametrize("n_cols,n", [(1, 2048), (3, 4096), (5, 2048 + 777)])
def test_filter_range_mask(n_cols, n):
    rng = np.random.default_rng(n_cols)
    cols = rng.normal(size=(n_cols, n)).astype(np.float32)
    lo = rng.normal(size=n_cols).astype(np.float32) - 0.5
    hi = lo + rng.random(n_cols).astype(np.float32) * 2
    out = range_mask(cols, lo, hi)
    assert np.array_equal(out, range_mask_ref(cols, lo, hi))
    assert out.shape == (n,)


def test_filter_range_mask_nan_and_inf():
    cols = np.array([[0.0, np.nan, 1.0, -np.inf, np.inf, 0.5]], np.float32)
    lo = np.array([-np.inf], np.float32)
    hi = np.array([np.inf], np.float32)
    out = range_mask(cols, lo, hi)
    assert np.array_equal(out, [True, False, True, True, True, True])  # NaN fails
    out2 = range_mask(cols, np.array([0.4], np.float32),
                      np.array([0.6], np.float32))
    assert np.array_equal(out2, [False, False, False, False, False, True])


@pytest.mark.parametrize("dtype", [np.int8, np.uint8, np.int16])
def test_dequant_affine(dtype):
    rng = np.random.default_rng(1)
    info = np.iinfo(dtype)
    q = rng.integers(info.min, info.max, (130, 70)).astype(dtype)
    scale = rng.random(70).astype(np.float32) + 0.1
    zero = rng.normal(size=70).astype(np.float32)
    out = np.asarray(dequant(q, scale, zero, out_dtype=jnp.float32))
    ref = np.asarray(dequant_ref(jnp.asarray(q), jnp.asarray(scale),
                                 jnp.asarray(zero), jnp.float32))
    assert np.allclose(out, ref, atol=1e-3)


def test_dequant_bf16_bits():
    import ml_dtypes
    rng = np.random.default_rng(2)
    f = rng.normal(size=(256, 128)).astype(np.float32)
    u16 = f.astype(ml_dtypes.bfloat16).view(np.uint16)
    out = np.asarray(dequant(u16, np.ones(128, np.float32),
                             np.zeros(128, np.float32), out_dtype=jnp.float32))
    assert np.allclose(out, f, atol=0.02)


@pytest.mark.parametrize("shape,causal,window", [
    ((2, 2, 256, 64), True, 0),
    ((1, 2, 384, 128), True, 0),
    ((1, 1, 256, 64), False, 0),
    ((2, 1, 256, 64), True, 64),
    ((1, 1, 200, 80), True, 0),       # ragged S and D (padding path)
])
def test_flash_attention(shape, causal, window):
    rng = np.random.default_rng(0)
    B, H, S, D = shape
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32)
               for _ in range(3))
    out = flash_attention(q, k, v, causal=causal, window=window)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    assert float(jnp.abs(out - ref).max()) < 3e-5


def test_flash_attention_bf16():
    rng = np.random.default_rng(0)
    shape = (1, 2, 256, 128)
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
               for _ in range(3))
    out = flash_attention(q, k, v)
    ref = attention_ref(q, k, v)
    assert float(jnp.abs(out.astype(jnp.float32)
                         - ref.astype(jnp.float32)).max()) < 3e-2
