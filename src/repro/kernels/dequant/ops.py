"""jit'd public wrapper with shape padding."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .. import interpret
from .kernel import BLOCK_C, BLOCK_R, dequant_pallas


def dequant(q, scale, zero, out_dtype=jnp.bfloat16):
    """q: [R, C] quantized column batch; scale/zero: [C]. Pads to kernel
    tiling and crops back."""
    q = jnp.asarray(q)
    R, C = q.shape
    Rp, Cp = -(-R // BLOCK_R) * BLOCK_R, -(-C // BLOCK_C) * BLOCK_C
    qp = jnp.pad(q, ((0, Rp - R), (0, Cp - C)))
    sp = jnp.pad(jnp.asarray(scale, jnp.float32), (0, Cp - C))
    zp = jnp.pad(jnp.asarray(zero, jnp.float32), (0, Cp - C))
    out = dequant_pallas(qp, sp, zp, out_dtype=out_dtype,
                         interpret=interpret())
    return out[:R, :C]
