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

``sum_product_pallas_packed`` is the same filter and sum over columns still
bit-packed as the store writes them (FixedBitWidth and FOR pages): each
``w``-bit column arrives as its little-endian bitstream, in which 32
consecutive values fill exactly ``w`` uint32 words, and the kernel unpacks
it. A program holds a tile of 1,024 such 32-value groups, a column's ``w``
words transposed to (w, 8, 128) so that word ``r`` of every group is one
full (8, 128) slab. Value ``j`` of a group starts in word ``(j * w) >> 5``
at bit ``(j * w) & 31``, both static: one or two whole-slab shifts, an OR
and a mask give value ``j`` of 1,024 groups at once. Slab position ``(s,
l)`` holds value ``32 * (128 * s + l) + j`` of the tile: filter and sum do
not care about the order, since every column is permuted alike, and the
valid-row test uses the original index.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
BLOCK_ROWS = 64                  # sublane rows per program
BLOCK_N = BLOCK_ROWS * LANES     # 8192 values per program
LIMB_BITS = 12
LIMB_MASK = (1 << LIMB_BITS) - 1
PARTS = 3                        # low limb, high limb, matched rows
GROUP = 32                       # values in w packed words of width w
TILE_GROUPS = SUBLANES * LANES   # packed groups per program
TILE_N = GROUP * TILE_GROUPS     # 32768 packed values per program
ROW_N = GROUP * LANES            # 4096 values: one sublane row of a tile


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


def _unpack(words_ref, j: int, width: int):
    """Value ``j`` of each of the tile's 32-value groups, as an (8, 128)
    int32 slab: the ``width`` bits at bit ``j * width`` of its words."""
    bit = j * width
    r, s = bit >> 5, bit & 31
    v = words_ref[0, r]
    if s:
        v = jax.lax.shift_right_logical(v, s)
    if s + width > 32:                          # the value spans two words
        v = v | jax.lax.shift_left(words_ref[0, r + 1], 32 - s)
    if s + width != 32:
        v = v & ((1 << width) - 1)
    return v


def _packed_kernel(params_ref, *refs, widths: tuple, based: tuple, a: int,
                   b: int):
    *words_refs, out_ref = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    K = len(widths)
    rows = pl.num_programs(0) * SUBLANES        # sublane rows of a column
    shape = (SUBLANES, LANES)
    sub = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    group = sub * LANES + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    # value j of a group is a row of the table while j < left
    left = params_ref[2 * K] - (i * TILE_N + group * GROUP)
    bases = []
    for k in range(K):
        base = None
        if based[k]:                            # one base a sublane row
            at = 2 * K + 1 + k * rows + i * SUBLANES
            base = jnp.full(shape, params_ref[at], jnp.int32)
            for s in range(1, SUBLANES):
                base = jnp.where(sub == s, params_ref[at + s], base)
        bases.append(base)
    low = high = count = jnp.zeros(shape, jnp.int32)
    for j in range(GROUP):
        ok = left > j
        vals = []
        for k in range(K):
            x = _unpack(words_refs[k], j, widths[k])
            if bases[k] is not None:
                x = x + bases[k]                # int32 wraparound: exact
            ok = ok & (x >= params_ref[k]) & (x <= params_ref[K + k])
            vals.append(x)
        xb = jnp.where(ok, vals[b], 0)
        low += (vals[a] & LIMB_MASK) * xb
        high += (vals[a] >> LIMB_BITS) * xb
        count += ok.astype(jnp.int32)
    out_ref[0] += low
    out_ref[1] += high
    out_ref[2] += count


@functools.partial(jax.jit, static_argnames=("widths", "based", "a", "b",
                                             "interpret"))
def sum_product_pallas_packed(words: jax.Array, params: jax.Array, *,
                              widths: tuple, based: tuple, a: int, b: int,
                              interpret: bool) -> jax.Array:
    """words: i32[T * TILE_GROUPS * sum(widths)], each column's bitstream
    in turn as 32-bit words, ``widths[k]`` bits a value, padded to T tiles
    of TILE_N values; params: i32[2K + 1 + 8TK], the K columns' lower
    bounds, their upper bounds, the valid row count, then each column's
    base for each of its 8T rows of ROW_N values (added where
    ``based[k]``); ``a``, ``b``: the factors' columns -> i32[3, 8, 128]
    per-lane partial sums (low limb, high limb, count)."""
    T = words.shape[0] // (TILE_GROUPS * sum(widths))
    cols, at = [], 0
    for w in widths:
        size = T * TILE_GROUPS * w
        # the barrier keeps XLA from reshaping the whole buffer at this
        # column's width before slicing it out
        col = jax.lax.optimization_barrier(words[at:at + size])
        cols.append(col.reshape(T, TILE_GROUPS, w).transpose(0, 2, 1)
                    .reshape(T, w, SUBLANES, LANES))
        at += size
    return pl.pallas_call(
        functools.partial(_packed_kernel, widths=widths, based=based, a=a,
                          b=b),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T,),
            in_specs=[pl.BlockSpec((1, w, SUBLANES, LANES),
                                   lambda i, p: (i, 0, 0, 0))
                      for w in widths],
            out_specs=pl.BlockSpec((PARTS, SUBLANES, LANES),
                                   lambda i, p: (0, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((PARTS, SUBLANES, LANES), jnp.int32),
        interpret=interpret,
        name="sum_product_packed",
    )(params, *cols)
