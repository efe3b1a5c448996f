"""jit'd public wrapper: [B, H, S, D] API with padding to kernel tiling."""

from __future__ import annotations

import jax.numpy as jnp

from .. import interpret
from .kernel import BLOCK_K, BLOCK_Q, flash_attention_pallas


def flash_attention(q, k, v, *, causal=True, window=0):
    """q,k,v: [B, H, S, D] -> [B, H, S, D]. S padded to 128, D padded to 128.

    Padded keys are masked out by the causal mask for padded queries and by
    zero-padding of K (their exp-scores underflow against real rows' max) —
    we additionally rely on cropping the padded queries from the output."""
    B, H, S, D = q.shape
    Sp = -(-S // BLOCK_Q) * BLOCK_Q
    Dp = -(-D // 128) * 128
    pad = ((0, 0), (0, 0), (0, Sp - S), (0, Dp - D))

    def prep(x):
        return jnp.pad(x, pad).reshape(B * H, Sp, Dp)

    qp, kp, vp = prep(q), prep(k), prep(v)
    out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                 kv_len=S, d_real=D, interpret=interpret())
    return out.reshape(B, H, Sp, Dp)[:, :, :S, :D]
