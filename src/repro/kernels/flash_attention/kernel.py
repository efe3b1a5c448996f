"""Pallas TPU kernel: blocked online-softmax (Flash) attention, forward.

Baseline vanilla attention materializes [S, S] f32 scores — the dominant HBM
term in the dry-run roofline for every dense train cell (EXPERIMENTS.md
§Roofline). This kernel streams K/V blocks through VMEM with running
(max, sum, acc) statistics so score tiles never leave VMEM.

Grid: (batch*heads, q_blocks, k_blocks) — the k axis is the innermost,
"revisiting" dimension: out/scratch blocks are indexed by (bh, q) only, so the
running statistics accumulate across k steps. Causal + sliding-window masking
prunes whole blocks via index arithmetic (fully masked blocks short-circuit).

MXU alignment: BLOCK_Q = BLOCK_K = 128, head_dim padded to a multiple of 128
by ops.py. Working set per program: q (128 x D) + k,v (128 x D each) + f32
scores tile (128 x 128) + acc (128 x D) — ~0.5 MB at D=128, far under VMEM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, k_blocks: int,
            kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * BLOCK_Q
    k_start = ki * BLOCK_K

    def compute():
        q = q_ref[0].astype(jnp.float32)               # [BQ, D]
        k = k_ref[0].astype(jnp.float32)               # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (BLOCK_Q, BLOCK_K), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (BLOCK_Q, BLOCK_K), 1)
        mask = k_pos < kv_len            # padded keys never participate
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                          # [BQ, BK]
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if causal or window > 0:
        # whole-block pruning: block is live iff some (q, k) pair is unmasked
        live = jnp.asarray(True)
        if causal:
            live &= q_start + BLOCK_Q - 1 >= k_start
        if window > 0:
            live &= (q_start - (k_start + BLOCK_K - 1)) < window
        pl.when(live)(compute)
    else:
        compute()

    @pl.when(ki == k_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "kv_len", "d_real",
                                    "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=0,
                           kv_len=None, d_real=None, interpret: bool):
    """q,k,v: [BH, S, D] with S % BLOCK == 0, D % 128 == 0.
    kv_len: number of real (non-padded) keys; d_real: real head_dim for the
    softmax scale."""
    BH, S, D = q.shape
    scale = 1.0 / math.sqrt(d_real or D)
    kv_len = kv_len or S
    k_blocks = S // BLOCK_K
    grid = (BH, S // BLOCK_Q, k_blocks)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          k_blocks=k_blocks, kv_len=kv_len),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BLOCK_Q, D), lambda b, q_, k_: (b, q_, 0)),
            pl.BlockSpec((1, BLOCK_K, D), lambda b, q_, k_: (b, k_, 0)),
            pl.BlockSpec((1, BLOCK_K, D), lambda b, q_, k_: (b, k_, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_Q, D), lambda b, q_, k_: (b, q_, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BLOCK_Q, 1), jnp.float32),   # running max
            pltpu.VMEM((BLOCK_Q, 1), jnp.float32),   # running sum
            pltpu.VMEM((BLOCK_Q, D), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
