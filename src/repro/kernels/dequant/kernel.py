"""Pallas TPU kernel: fused per-feature dequantize + cast.

The storage quantization read path (§2.4): integer/bf16-bit columns arrive in
HBM straight from Bullion pages; the kernel fuses (dequantize, scale, cast)
into a single VMEM pass so the FP32 intermediate never exists — feeding
embeddings/features to the model at storage precision.

Grid tiles (rows, features); per-feature scale/zero ride along the feature
axis only (index_map pins the row coordinate) as (1, BLOCK_C) tiles of a
[1, C] row: Mosaic lays out a 1-D f32 block in 128-lane tiles where XLA
uses 256, and refuses the operand.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_R = 256
BLOCK_C = 128


def _kernel(q_ref, scale_ref, zero_ref, out_ref, *, from_bf16_bits: bool,
            out_dtype):
    q = q_ref[...]
    if from_bf16_bits:
        f = jax.lax.bitcast_convert_type(q.astype(jnp.uint32) << 16,
                                         jnp.float32)
    else:
        f = q.astype(jnp.float32) * scale_ref[...] + zero_ref[...]
    out_ref[...] = f.astype(out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "interpret"))
def dequant_pallas(q, scale, zero, out_dtype=jnp.bfloat16, *,
                   interpret: bool):
    """q: [R, C] (R % BLOCK_R == 0, C % BLOCK_C == 0); scale, zero: f32[C]."""
    R, C = q.shape
    assert R % BLOCK_R == 0 and C % BLOCK_C == 0, (R, C)
    from_bf16 = q.dtype == jnp.uint16
    return pl.pallas_call(
        functools.partial(_kernel, from_bf16_bits=from_bf16,
                          out_dtype=out_dtype),
        grid=(R // BLOCK_R, C // BLOCK_C),
        in_specs=[
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda r, c: (r, c)),
            pl.BlockSpec((1, BLOCK_C), lambda r, c: (0, c)),
            pl.BlockSpec((1, BLOCK_C), lambda r, c: (0, c)),
        ],
        out_specs=pl.BlockSpec((BLOCK_R, BLOCK_C), lambda r, c: (r, c)),
        out_shape=jax.ShapeDtypeStruct((R, C), out_dtype),
        interpret=interpret,
    )(q, scale.reshape(1, C), zero.reshape(1, C))
