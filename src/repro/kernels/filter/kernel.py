"""Pallas TPU kernel: conjunctive range filter (predicate pushdown).

Grid tiles the row axis; each program holds a (C, BLOCK_N) tile of the
filter columns in VMEM plus the (C, 1) interval bounds, evaluates both bound
checks lane-parallel on the VPU, and AND-reduces across the (small, static)
column axis. Pure element-wise compare/select — the MXU is never involved,
matching the scan's integer/compare character. The uint8 survivor mask is
what the scanner feeds to compress/gather steps downstream.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 2048                  # rows per program: 16 sublane rows x 128 lanes


def _kernel(cols_ref, lo_ref, hi_ref, out_ref):
    x = cols_ref[...]                               # [C, B] float32
    lo = lo_ref[...]                                # [C, 1]
    hi = hi_ref[...]
    ok = jnp.logical_and(x >= lo, x <= hi)          # NaN fails both -> False
    out_ref[...] = jnp.all(ok, axis=0, keepdims=True).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def range_mask_pallas(cols: jax.Array, lo: jax.Array, hi: jax.Array,
                      interpret: bool) -> jax.Array:
    """cols: f32[C, N] (N % BLOCK_N == 0); lo, hi: f32[C] -> uint8[1, N]."""
    C, N = cols.shape
    grid = (N // BLOCK_N,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((C, BLOCK_N), lambda i: (0, i)),
            pl.BlockSpec((C, 1), lambda i: (0, 0)),
            pl.BlockSpec((C, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_N), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.uint8),
        interpret=interpret,
        name="range_mask",
    )(cols, lo.reshape(C, 1), hi.reshape(C, 1))
