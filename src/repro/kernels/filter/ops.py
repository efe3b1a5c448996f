"""Public wrapper: pads ragged row counts, dispatches to the kernel.

``range_mask`` is the whole device round trip; ``stage``, ``launch`` (the
package's) and ``fetch`` are its three steps, for a caller that times them
apart.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import Staged, launch
from .kernel import BLOCK_N, range_mask_pallas


def stage(cols, lo, hi) -> Staged:
    """Pad the row axis to a BLOCK_N multiple (padding rows are sliced back
    off, so their mask value is irrelevant) and put columns and bounds on
    the device."""
    cols = np.atleast_2d(np.asarray(cols, np.float32))
    C, n = cols.shape
    pad = (-n) % BLOCK_N
    if pad:
        cols = np.concatenate([cols, np.zeros((C, pad), np.float32)], axis=1)
    return Staged(range_mask_pallas,
                  (jnp.asarray(cols), jnp.asarray(lo, jnp.float32),
                   jnp.asarray(hi, jnp.float32)), {})


def fetch(out: jax.Array, n: int) -> np.ndarray:
    """Wait for the mask, copy it back and cut it to ``n`` rows."""
    return np.asarray(out).reshape(-1)[:n].astype(bool)


def range_mask(cols, lo, hi) -> np.ndarray:
    """Conjunctive range filter: f32[C, N] columns -> bool[N] survivor mask."""
    cols = np.atleast_2d(np.asarray(cols, np.float32))
    return fetch(launch(stage(cols, lo, hi)), cols.shape[1])
