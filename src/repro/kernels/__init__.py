"""Pallas TPU kernels for Bullion's compute hot-spots.

  aggregate       — fused range filter and exact masked sum of products
                    over int32 columns, or over their FixedBitWidth/FOR
                    pages unpacked on the VPU (the aggregate node's partials)
  dequant         — fused per-feature dequantize + cast (C4 read path)
  filter          — conjunctive range filter for predicate pushdown (the
                    scan subsystem's batch row-survivor mask)
  flash_attention — blocked online-softmax attention (beyond-paper training
                    perf; the §Perf answer to vanilla attention's HBM traffic)

Each kernel ships kernel.py (pl.pallas_call + BlockSpec, with ``interpret``
a static argument), ops.py (the public wrapper) and ref.py (pure-jnp
oracle). The wrappers take no ``interpret`` option: on a TPU backend every
kernel compiles through Mosaic, on any other backend (the CPU the tests run
on) it runs in the Pallas interpreter. ``tests/test_tpu_compile.py`` compiles
the main-path kernels for a described v5e with the interpreter off.

A main-path kernel's device round trip has three steps, for a caller that
times them apart: its ops module's ``stage`` lays out the inputs and puts
them on the device as a ``Staged`` call, ``launch`` dispatches it, and the
ops module's ``fetch`` waits for the output and copies it back.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax


@functools.cache
def interpret() -> bool:
    """True unless JAX's default backend is a TPU, decided once per process."""
    return jax.default_backend() != "tpu"


class Staged(NamedTuple):
    """A kernel call ready to dispatch: the jitted kernel, its inputs on the
    device, and its static arguments."""

    kernel: Callable
    inputs: tuple
    static: dict


def launch(staged: Staged) -> jax.Array:
    """Dispatch a staged call; its output may not be ready yet."""
    return staged.kernel(*staged.inputs, **staged.static,
                         interpret=interpret())
