"""Pallas TPU kernel: fused range filter and exact masked sum of products.

One call answers ``sum(a * b)`` and the count over the rows of one row group
where every column lies inside its closed interval ``[lo, hi]``, all on
int32 columns. The grid walks the row axis; each program holds a (K,
BLOCK_ROWS, 128) tile of the group's distinct columns (predicate columns and
the two factors) in VMEM, evaluates the conjunctive range test lane-parallel
on the VPU, and adds its rows into a resident (3, 8, 128) int32 accumulator:
per-lane partial sums of the low and high limb products and of the matches.

Exactness: ``a`` is split into a 12-bit low limb ``a & 0xFFF`` and the rest
``a >> 12`` (arithmetic), so ``a * b == (a >> 12) * b * 4096 + (a & 0xFFF) *
b`` for every int32 ``a``. Each accumulator lane sums ``n / 1024`` rows of
one limb product, which stays inside int32 when the caller's bound
(``ops.exact_for``) holds; the host adds the lanes in Python ints. A
float32 or single int32 accumulator would round or wrap: one TPC-H product
reaches 1.05e8, past float32's 2**24, and 64 of them pass 2**31.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
BLOCK_ROWS = 64                  # sublane rows per program
BLOCK_N = BLOCK_ROWS * LANES     # 8192 values per program
LIMB_BITS = 12
LIMB_MASK = (1 << LIMB_BITS) - 1
PARTS = 3                        # low limb, high limb, matched rows


def _fold(x):
    """(BLOCK_ROWS, 128) -> (8, 128): sum over whole (8, 128) tiles."""
    return x.reshape(BLOCK_ROWS // SUBLANES, SUBLANES, LANES).sum(axis=0)


def _kernel(params_ref, cols_ref, out_ref, *, a: int, b: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    K = cols_ref.shape[0]
    shape = (BLOCK_ROWS, LANES)
    idx = (i * BLOCK_N
           + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    ok = idx < params_ref[2 * K]                    # padding rows fail
    for k in range(K):
        x = cols_ref[k]                             # [BLOCK_ROWS, 128] int32
        ok = ok & (x >= params_ref[k]) & (x <= params_ref[K + k])
    xa = cols_ref[a]
    xb = jnp.where(ok, cols_ref[b], 0)
    out_ref[0] += _fold((xa & LIMB_MASK) * xb)
    out_ref[1] += _fold((xa >> LIMB_BITS) * xb)
    out_ref[2] += _fold(ok.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("a", "b", "interpret"))
def sum_product_pallas(cols: jax.Array, params: jax.Array, *, a: int, b: int,
                       interpret: bool) -> jax.Array:
    """cols: i32[K, M, 128] (M % BLOCK_ROWS == 0); params: i32[2K + 1, 1,
    128], each value along the lanes: the K columns' lower bounds, their
    upper bounds, then the valid row count; ``a``, ``b``: the factors' rows
    of ``cols`` -> i32[3, 8, 128] per-lane partial sums (low limb, high
    limb, count)."""
    K, M, _ = cols.shape
    return pl.pallas_call(
        functools.partial(_kernel, a=a, b=b),
        grid=(M // BLOCK_ROWS,),
        in_specs=[
            pl.BlockSpec((2 * K + 1, 1, LANES), lambda i: (0, 0, 0)),
            pl.BlockSpec((K, BLOCK_ROWS, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((PARTS, SUBLANES, LANES),
                               lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((PARTS, SUBLANES, LANES), jnp.int32),
        interpret=interpret,
        name="sum_product",
    )(params, cols)
