"""jit'd public wrapper: pads ragged group counts, dispatches to the kernel."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .. import interpret
from .kernel import GROUPS_PER_BLOCK, bitunpack_pallas
from .ref import pack_bp32_ref


def pack_bp32(values: np.ndarray, width: int) -> np.ndarray:
    """Host-side packing (write path runs on CPU in the storage layer)."""
    n = len(values)
    pad = (-n) % (32 * GROUPS_PER_BLOCK)
    v = np.concatenate([values.astype(np.uint32), np.zeros(pad, np.uint32)])
    return pack_bp32_ref(v, width)


def bitunpack(planes, width: int, n_values: int | None = None):
    """Device-side unpack: uint32[G, w] -> uint32[n_values]."""
    out = bitunpack_pallas(jnp.asarray(planes), width, interpret=interpret())
    flat = out.reshape(-1)
    if n_values is not None:
        flat = flat[:n_values]
    return flat
