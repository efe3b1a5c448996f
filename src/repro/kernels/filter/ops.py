"""Public wrapper: pads ragged row counts, dispatches to the kernel.

``range_mask`` is the whole device round trip; ``stage``, ``launch`` and
``fetch`` are its three steps, for a caller that times them apart.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import interpret
from .kernel import BLOCK_N, range_mask_pallas


class Staged(NamedTuple):
    """A filter call's inputs on the device, and the rows it answers for."""

    cols: jax.Array
    lo: jax.Array
    hi: jax.Array
    n_values: int


def stage(cols, lo, hi, n_values: int | None = None) -> Staged:
    """Pad the row axis to a BLOCK_N multiple (padding rows are sliced back
    off, so their mask value is irrelevant) and put columns and bounds on
    the device."""
    cols = np.atleast_2d(np.asarray(cols, np.float32))
    C, n = cols.shape
    if n_values is None:
        n_values = n
    pad = (-n) % BLOCK_N
    if pad:
        cols = np.concatenate([cols, np.zeros((C, pad), np.float32)], axis=1)
    return Staged(jnp.asarray(cols), jnp.asarray(lo, jnp.float32),
                  jnp.asarray(hi, jnp.float32), n_values)


def launch(staged: Staged) -> jax.Array:
    """Dispatch the kernel; the mask it returns may not be ready yet."""
    return range_mask_pallas(staged.cols, staged.lo, staged.hi,
                             interpret=interpret())


def fetch(out: jax.Array, n_values: int) -> np.ndarray:
    """Wait for the mask, copy it back and cut it to ``n_values`` rows."""
    return np.asarray(out).reshape(-1)[:n_values].astype(bool)


def range_mask(cols, lo, hi, n_values: int | None = None) -> np.ndarray:
    """Conjunctive range filter: f32[C, N] columns -> bool[N] survivor mask."""
    staged = stage(cols, lo, hi, n_values)
    return fetch(launch(staged), staged.n_values)
