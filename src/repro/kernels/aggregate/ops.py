"""Public wrapper: pads and lays out a row group's int32 columns, or their
bit-packed pages, runs the kernel, and adds its per-lane partial sums
exactly on the host.

``sum_product`` and ``sum_product_packed`` are whole device round trips;
``stage`` or ``stage_packed``, ``launch`` (the package's) and ``fetch`` are
their steps, for a caller that times them apart. ``plan`` lays out a call
whose int32 lane sums are exact, and ``pack_column`` one column's pages as
the packed kernel reads them; each answers None where the kernel cannot
take its input. ``exact_for`` says whether a call's int32 accumulators are
exact for given factor magnitudes.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import Staged, launch
from .kernel import (BLOCK_N, LANES, LIMB_BITS, LIMB_MASK, ROW_N, SUBLANES,
                     TILE_GROUPS, TILE_N, sum_product_pallas,
                     sum_product_pallas_packed)

INT32 = (-(1 << 31), (1 << 31) - 1)


def padded_rows(n: int, block: int = BLOCK_N) -> int:
    return max(block, -(-n // block) * block)


def exact_for(n: int, max_abs_a: int, max_abs_b: int,
              block: int = BLOCK_N) -> bool:
    """Are the kernel's int32 lane sums exact for ``n`` rows whose factors
    are at most ``max_abs_a`` and ``max_abs_b`` in magnitude, in programs
    of ``block`` rows (``TILE_N`` for the packed kernel)? Each of the 8 x
    128 lanes adds ``n / 1024`` (padded) limb products: the low limb is
    below 2**12, the high limb at most ``(max_abs_a >> 12) + 1``."""
    per_lane = padded_rows(n, block) // (SUBLANES * LANES)
    limb = max(LIMB_MASK, (int(max_abs_a) >> LIMB_BITS) + 1)
    return per_lane * limb * int(max_abs_b) <= INT32[1]


class Call(NamedTuple):
    """A call's columns in the order the kernel reads them, their closed
    int32 intervals, and the factors' positions among them (``a`` is split
    into limbs)."""

    names: list
    lo: list
    hi: list
    a: int
    b: int


def plan(ranges: dict, factors: Sequence[str], bounds: Sequence[int],
         n: int, packed: bool) -> Optional[Call]:
    """The call that sums ``factors[0] * factors[1]`` over the ``n`` rows
    inside every closed interval of ``ranges`` (column -> (lo, hi)), the
    factors at most ``bounds`` in magnitude: each interval clipped to int32,
    and the factor whose limbs keep the lane sums exact, in the programs of
    the plain or the ``packed`` kernel, split. None where neither is."""
    block = TILE_N if packed else BLOCK_N
    a, b = factors
    if exact_for(n, bounds[0], bounds[1], block):
        order = (a, b)
    elif exact_for(n, bounds[1], bounds[0], block):
        order = (b, a)
    else:
        return None
    names = list(dict.fromkeys([*ranges, *order]))
    return Call(names, [max(ranges.get(c, INT32)[0], INT32[0]) for c in names],
                [min(ranges.get(c, INT32)[1], INT32[1]) for c in names],
                names.index(order[0]), names.index(order[1]))


def stage(cols, lo, hi, a: int, b: int) -> Staged:
    """Pad the rows of int32 ``cols`` [K, N] to a BLOCK_N multiple, lay
    them out as [K, N / 128, 128], and put them on the device, and in one
    more put each column's bounds ``lo``, ``hi`` [K] and the row count,
    each along the lanes. Bounds must lie in int32."""
    cols = np.atleast_2d(np.asarray(cols, np.int32))
    K, n = cols.shape
    pad = padded_rows(n) - n
    if pad:
        cols = np.concatenate([cols, np.zeros((K, pad), np.int32)], axis=1)
    params = np.empty((2 * K + 1, 1, LANES), np.int32)
    params[:K] = np.asarray(lo, np.int32).reshape(K, 1, 1)
    params[K:2 * K] = np.asarray(hi, np.int32).reshape(K, 1, 1)
    params[2 * K] = n
    return Staged(sum_product_pallas,
                  (jnp.asarray(cols.reshape(K, -1, LANES)),
                   jnp.asarray(params)), {"a": a, "b": b})


def pack_column(pages: Sequence[tuple], width: int
                ) -> Optional[tuple[np.ndarray, np.ndarray]]:
    """One column's bit-packed pages, each ``(payload, values, base)``, as
    the packed kernel reads them: their ``width``-bit little-endian
    bitstreams end to end in uint32 words, zero-padded to whole tiles of
    TILE_N values, and the base of each row of ROW_N values. None where the
    kernel cannot take them: ``width`` is not 1 to 31 bits, a page but the
    last does not hold a multiple of ROW_N values (each must start a row),
    or a base is not an int32."""
    if not 1 <= width <= 31 or any(
            not INT32[0] <= base <= INT32[1] for _, _, base in pages) \
            or any(count % ROW_N for _, count, _ in pages[:-1]):
        return None
    n = sum(count for _, count, _ in pages)
    tiles = padded_rows(n, TILE_N) // TILE_N
    words = np.empty(tiles * TILE_GROUPS * width, np.uint32)
    buf = words.view(np.uint8)
    bases = np.zeros(tiles * SUBLANES, np.int32)
    at = row = 0
    for payload, count, base in pages:
        nbytes = (count * width + 7) // 8
        buf[at:at + nbytes] = np.frombuffer(payload, np.uint8, count=nbytes)
        rows = -(-count // ROW_N)
        bases[row:row + rows] = base
        at, row = at + nbytes, row + rows
    buf[at:] = 0
    return words, bases


def stage_packed(columns: Sequence[tuple[np.ndarray, np.ndarray]],
                 widths: Sequence[int], n: int, lo, hi, a: int,
                 b: int) -> Staged:
    """Put ``pack_column``'s K columns of ``n`` values on the device: their
    words in one put, and in one more each column's bounds ``lo``, ``hi``
    [K], the row count and the columns' bases. A column whose bases are all
    0 skips the add. Bounds must lie in int32."""
    bases = np.stack([base for _, base in columns])
    params = np.concatenate([np.asarray(lo, np.int32),
                             np.asarray(hi, np.int32),
                             np.asarray([n], np.int32), bases.ravel()])
    words = np.concatenate([w for w, _ in columns]).view(np.int32)
    return Staged(sum_product_pallas_packed,
                  (jnp.asarray(words), jnp.asarray(params)),
                  {"widths": tuple(int(w) for w in widths),
                   "based": tuple(bool(x.any()) for x in bases),
                   "a": a, "b": b})


def fetch(out: jax.Array) -> tuple[int, int]:
    """Wait for the partial sums, copy them back and add them exactly:
    (sum of products, matching rows)."""
    low, high, count = np.asarray(out).astype(np.int64).sum(axis=(1, 2))
    return (int(high) << LIMB_BITS) + int(low), int(count)


def sum_product(cols, lo, hi, a: int, b: int) -> tuple[int, int]:
    """Fused range filter and exact sum of products over int32 ``cols``
    [K, N]: (sum of ``cols[a] * cols[b]`` over the rows inside every
    ``[lo, hi]``, their count). The caller checks ``exact_for``."""
    return fetch(launch(stage(cols, lo, hi, a, b)))


def sum_product_packed(columns: Sequence[tuple[np.ndarray, np.ndarray]],
                       widths: Sequence[int], n: int, lo, hi, a: int,
                       b: int) -> tuple[int, int]:
    """``sum_product`` over ``pack_column``'s columns of ``n`` values. The
    caller checks ``exact_for`` with ``block=TILE_N``."""
    return fetch(launch(stage_packed(columns, widths, n, lo, hi, a, b)))
