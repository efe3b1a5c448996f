"""Decoder-only assembly over heterogeneous block patterns.

Depth is expressed as segments of repeating patterns; parameters (and caches)
are stacked over the repeat count and the pattern is applied inside
``jax.lax.scan`` — HLO size and compile time stay O(pattern), not O(depth).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import mla as mla_mod
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import rwkv6 as rwkv_mod
from .base import P, constrain, is_decl
from .config import ModelConfig
from .layers import (attention_decl, attn_out, attn_qkv, dot_attention,
                     gelu_mlp, gelu_mlp_decl, layernorm, layernorm_decl,
                     rmsnorm, rmsnorm_decl, swiglu, swiglu_decl)


# ---------------------------------------------------------------------------
# declarations
# ---------------------------------------------------------------------------


def _norm_decl(cfg):
    return rmsnorm_decl(cfg.d_model) if cfg.norm == "rmsnorm" \
        else layernorm_decl(cfg.d_model)


def _norm(cfg, p, x):
    return rmsnorm(p, x) if cfg.norm == "rmsnorm" else layernorm(p, x)


def block_decl(cfg: ModelConfig, block: str) -> dict:
    attn_kind, mlp_kind = block.split(":")
    decl: dict = {}
    if attn_kind in ("full", "window", "local", "global"):
        decl["ln_attn"] = _norm_decl(cfg)
        decl["attn"] = attention_decl(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                      cfg.head_dim, qk_norm=cfg.qk_norm,
                                      fused=cfg.fused_qkv)
    elif attn_kind == "mla":
        decl["ln_attn"] = _norm_decl(cfg)
        decl["attn"] = mla_mod.mla_decl(cfg)
    elif attn_kind == "rwkv":
        return rwkv_mod.rwkv_decl(cfg)   # self-contained (incl. channel mix)
    elif attn_kind == "rglru":
        decl["rec"] = rglru_mod.rglru_decl(cfg)
    else:
        raise ValueError(attn_kind)

    if mlp_kind == "swiglu":
        decl["ln_mlp"] = _norm_decl(cfg)
        decl["mlp"] = swiglu_decl(cfg.d_model, cfg.d_ff)
    elif mlp_kind == "gelu":
        decl["ln_mlp"] = _norm_decl(cfg)
        decl["mlp"] = gelu_mlp_decl(cfg.d_model, cfg.d_ff)
    elif mlp_kind == "moe":
        decl["ln_mlp"] = _norm_decl(cfg)
        decl["moe"] = moe_mod.moe_decl(cfg)
    elif mlp_kind != "none":
        raise ValueError(mlp_kind)
    return decl


def stack_decl(decl, n: int):
    return jax.tree.map(
        lambda p: P((n,) + p.shape, ("layers",) + p.axes, p.init, p.scale),
        decl, is_leaf=is_decl)


def model_decl(cfg: ModelConfig) -> dict:
    decl: dict = {
        "embed": P((cfg.vocab, cfg.d_model), ("vocab", "embed"), init="embed",
                   scale=0.02),
        "final_norm": _norm_decl(cfg),
    }
    if not cfg.tie_embeddings:
        decl["lm_head"] = P((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    decl["segments"] = [
        {f"b{j}": stack_decl(block_decl(cfg, b), rep)
         for j, b in enumerate(blocks)}
        for blocks, rep in cfg.segments
    ]
    return decl


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def _attn_cache(cfg, kind: str, batch: int, seq_len: int, dtype):
    S = seq_len if kind in ("full", "global") else min(cfg.window, seq_len)
    shape = (batch, S, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def block_cache(cfg, block: str, batch: int, seq_len: int, dtype=jnp.bfloat16):
    attn_kind, _ = block.split(":")
    if attn_kind in ("full", "window", "local", "global"):
        return _attn_cache(cfg, attn_kind, batch, seq_len, dtype)
    if attn_kind == "mla":
        return mla_mod.mla_cache_decl(cfg, batch, seq_len, dtype)
    if attn_kind == "rwkv":
        return rwkv_mod.rwkv_cache_decl(cfg, batch)
    if attn_kind == "rglru":
        return rglru_mod.rglru_cache_decl(cfg, batch)
    raise ValueError(attn_kind)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16):
    segs = []
    for blocks, rep in cfg.segments:
        segs.append({
            f"b{j}": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (rep,) + a.shape).copy()
                if rep > 0 else a,
                block_cache(cfg, b, batch, seq_len, dtype))
            for j, b in enumerate(blocks)})
    return {"pos": jnp.zeros((), jnp.int32), "segments": segs}


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Ctx:
    cfg: ModelConfig
    dist: Any = None
    mode: str = "train"                 # train | prefill | decode
    positions: Optional[jax.Array] = None
    cache_pos: Optional[jax.Array] = None


def _rolling_pos(pos, W):
    """Absolute position held by each rolling-buffer slot."""
    slots = jnp.arange(W, dtype=jnp.int32)
    return pos - ((pos - slots) % W)


def _attn_block(p, x, kind: str, ctx: Ctx, cache):
    cfg = ctx.cfg
    windowed = kind in ("window", "local")
    W = cfg.window
    xn = _norm(cfg, p["ln_attn"], x)

    if ctx.mode == "decode":
        pos = ctx.cache_pos
        positions = pos[None]
        q, k_new, v_new = attn_qkv(p["attn"], xn, positions,
                                   rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                                   n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                                   head_dim=cfg.head_dim)
        S = cache["k"].shape[1]
        slot = pos % S if windowed else pos
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
        if windowed:
            kv_pos = _rolling_pos(pos, S)
            kv_valid = (kv_pos >= 0)[None, :]
        else:
            kv_pos = jnp.arange(S, dtype=jnp.int32)
            kv_valid = (kv_pos <= pos)[None, :]
        o = dot_attention(q, k.astype(x.dtype), v.astype(x.dtype),
                          positions, kv_pos, causal=True,
                          window=W if windowed else 0,
                          kv_valid=jnp.broadcast_to(kv_valid, (x.shape[0], S)))
        new_cache = {"k": k, "v": v}
    else:
        positions = ctx.positions
        q, k, v = attn_qkv(p["attn"], xn, positions,
                           rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                           n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                           head_dim=cfg.head_dim)
        o = dot_attention(q, k, v, positions, positions, causal=True,
                          window=W if windowed else 0)
        new_cache = None
        if ctx.mode == "prefill" and cache is not None:
            S_cache = cache["k"].shape[1]
            T = x.shape[1]
            if windowed and T > S_cache:
                tail_k = k[:, T - S_cache:]
                tail_v = v[:, T - S_cache:]
                shift = (T - S_cache) % S_cache
                ck = jnp.roll(tail_k, shift, axis=1)
                cv = jnp.roll(tail_v, shift, axis=1)
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(
                    jnp.zeros_like(cache["k"]), k.astype(cache["k"].dtype), 0, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    jnp.zeros_like(cache["v"]), v.astype(cache["v"].dtype), 0, axis=1)
            new_cache = {"k": ck.astype(cache["k"].dtype),
                         "v": cv.astype(cache["v"].dtype)}
    return x + attn_out(p["attn"], o), new_cache


def apply_block(p, x, block: str, ctx: Ctx, cache=None):
    """Returns (x, new_cache, aux_loss)."""
    cfg = ctx.cfg
    attn_kind, mlp_kind = block.split(":")
    aux = jnp.zeros((), jnp.float32)

    if attn_kind in ("full", "window", "local", "global"):
        x, new_cache = _attn_block(p, x, attn_kind, ctx, cache)
    elif attn_kind == "mla":
        xn = _norm(cfg, p["ln_attn"], x)
        positions = ctx.cache_pos[None] if ctx.mode == "decode" else ctx.positions
        o, new_cache = mla_mod.mla_attention(p["attn"], xn, positions, cfg,
                                             cache=cache,
                                             cache_pos=ctx.cache_pos)
        x = x + o
    elif attn_kind == "rwkv":
        x, new_cache = rwkv_mod.rwkv_block(
            p, x, cache, cfg=cfg, dist=ctx.dist,
            use_chunked=cfg.rwkv_chunked and ctx.mode != "decode")
        return x, new_cache, aux
    elif attn_kind == "rglru":
        x, new_cache = rglru_mod.rglru_block(p["rec"], x, cache, cfg=cfg)
    else:
        raise ValueError(attn_kind)

    if mlp_kind in ("swiglu", "gelu"):
        xn = _norm(cfg, p["ln_mlp"], x)
        x = x + (swiglu(p["mlp"], xn) if mlp_kind == "swiglu"
                 else gelu_mlp(p["mlp"], xn))
    elif mlp_kind == "moe":
        xn = _norm(cfg, p["ln_mlp"], x)
        y, aux = moe_mod.moe_block(p["moe"], xn, cfg, ctx.dist)
        x = x + y
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def embed_tokens(params, tokens, cfg, dtype):
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    return x


def logits_fn(params, x, cfg):
    x32 = x
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x32, params["embed"].astype(x.dtype))
    return jnp.einsum("bsd,dv->bsv", x32, params["lm_head"].astype(x.dtype))


def forward(params, x, cfg: ModelConfig, ctx: Ctx, cache=None):
    """x: [B, T, d] embedded inputs. Returns (hidden, new_cache, aux)."""
    x = constrain(x, ctx.dist, ("batch", "seq", None))
    aux_total = jnp.zeros((), jnp.float32)
    new_segments = []
    for si, (blocks, rep) in enumerate(cfg.segments):
        seg_params = params["segments"][si]
        seg_cache = cache["segments"][si] if cache is not None else None

        def body(carry, xs):
            h, aux_c = carry
            if seg_cache is not None:
                ps, cs = xs
            else:
                ps, cs = xs, None
            new_cs = {}
            for j, b in enumerate(blocks):
                c_j = cs[f"b{j}"] if cs is not None else None
                h, nc, aux = apply_block(ps[f"b{j}"], h, b, ctx, c_j)
                if nc is not None:
                    new_cs[f"b{j}"] = nc
            h = constrain(h, ctx.dist, ("batch", "seq", None))
            out_cs = new_cs if seg_cache is not None else None
            return (h, aux_c + aux), out_cs

        if ctx.mode == "train":
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if cfg.remat_policy == "dots" else None)
            body_fn = jax.checkpoint(body, policy=policy)
        else:
            body_fn = body
        xs = (seg_params, seg_cache) if seg_cache is not None else seg_params
        (x, aux_total), new_seg_cache = jax.lax.scan(body_fn, (x, aux_total), xs)
        new_segments.append(new_seg_cache)

    x = _norm(cfg, params["final_norm"], x)
    new_cache = None
    if cache is not None:
        new_cache = {"pos": cache["pos"], "segments": new_segments}
    return x, new_cache, aux_total
