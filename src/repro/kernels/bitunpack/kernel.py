"""Pallas TPU kernel: BP32 bit-planar fixed-width unpack.

Grid tiles the group axis; each program unpacks a (GROUPS_PER_BLOCK, 32)
value tile from its (GROUPS_PER_BLOCK, w) plane words held in VMEM. The
inner loop over the w planes is unrolled at trace time (w is static), so the
body is pure lane-parallel shift/and/or on the VPU — the MXU is not involved,
matching the decode's integer character.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

GROUPS_PER_BLOCK = 256          # 256 groups x 32 lanes = 8192 values per block


def _kernel(planes_ref, out_ref, *, width: int):
    planes = planes_ref[...]                        # [G_blk, w] uint32
    lanes = jax.lax.broadcasted_iota(jnp.uint32, (1, 32), 1)
    acc = jnp.zeros((planes.shape[0], 32), jnp.uint32)
    for j in range(width):                          # static unroll
        word = planes[:, j:j + 1]                   # [G_blk, 1]
        bit = (word >> lanes) & jnp.uint32(1)
        acc = acc | (bit << jnp.uint32(j))
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def bitunpack_pallas(planes: jax.Array, width: int,
                     interpret: bool) -> jax.Array:
    """planes: uint32[G, w] (G % GROUPS_PER_BLOCK == 0) -> uint32[G, 32]."""
    G = planes.shape[0]
    grid = (G // GROUPS_PER_BLOCK,)
    return pl.pallas_call(
        functools.partial(_kernel, width=width),
        grid=grid,
        in_specs=[pl.BlockSpec((GROUPS_PER_BLOCK, width), lambda g: (g, 0))],
        out_specs=pl.BlockSpec((GROUPS_PER_BLOCK, 32), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((G, 32), jnp.uint32),
        interpret=interpret,
    )(planes)
