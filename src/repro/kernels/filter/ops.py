"""jit'd public wrapper: pads ragged row counts, dispatches to the kernel."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .. import interpret
from .kernel import BLOCK_N, range_mask_pallas


def range_mask(cols, lo, hi, n_values: int | None = None) -> np.ndarray:
    """Conjunctive range filter: f32[C, N] columns -> bool[N] survivor mask.

    Pads the row axis to a BLOCK_N multiple (padding rows are sliced back
    off, so their mask value is irrelevant).
    """
    cols = np.atleast_2d(np.asarray(cols, np.float32))
    C, n = cols.shape
    if n_values is None:
        n_values = n
    pad = (-n) % BLOCK_N
    if pad:
        cols = np.concatenate([cols, np.zeros((C, pad), np.float32)], axis=1)
    out = range_mask_pallas(jnp.asarray(cols),
                            jnp.asarray(lo, jnp.float32),
                            jnp.asarray(hi, jnp.float32),
                            interpret=interpret())
    return np.asarray(out).reshape(-1)[:n_values].astype(bool)
