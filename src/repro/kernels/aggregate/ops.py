"""Public wrapper: pads and lays out a row group's int32 columns, runs the
kernel, and adds its per-lane partial sums exactly on the host.

``sum_product`` is the whole device round trip; ``stage``, ``launch`` and
``fetch`` are its three steps, for a caller that times them apart.
``exact_for`` says whether a call's int32 accumulators are exact for given
factor magnitudes.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import interpret
from .kernel import (BLOCK_N, LANES, LIMB_BITS, LIMB_MASK, SUBLANES,
                     sum_product_pallas)

INT32_MAX = (1 << 31) - 1


def padded_rows(n: int) -> int:
    return max(BLOCK_N, -(-n // BLOCK_N) * BLOCK_N)


def exact_for(n: int, max_abs_a: int, max_abs_b: int) -> bool:
    """Are the kernel's int32 lane sums exact for ``n`` rows whose factors
    are at most ``max_abs_a`` and ``max_abs_b`` in magnitude? Each of the
    8 x 128 lanes adds ``n / 1024`` (padded) limb products: the low limb is
    below 2**12, the high limb at most ``(max_abs_a >> 12) + 1``."""
    per_lane = padded_rows(n) // (SUBLANES * LANES)
    limb = max(LIMB_MASK, (int(max_abs_a) >> LIMB_BITS) + 1)
    return per_lane * limb * int(max_abs_b) <= INT32_MAX


class Staged(NamedTuple):
    """A call's inputs on the device, and which of its columns multiply."""

    cols: jax.Array
    params: jax.Array
    a: int
    b: int


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
    return Staged(jnp.asarray(cols.reshape(K, -1, LANES)),
                  jnp.asarray(params), a, b)


def launch(staged: Staged) -> jax.Array:
    """Dispatch the kernel; its partial sums may not be ready yet."""
    return sum_product_pallas(staged.cols, staged.params, a=staged.a,
                              b=staged.b, interpret=interpret())


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
