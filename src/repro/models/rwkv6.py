"""RWKV-6 "Finch" block: data-dependent token-shift (ddlerp), data-dependent
per-channel decay, WKV linear recurrence, and squared-ReLU channel mix.
Attention-free; decode state is O(1) in sequence length."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import P
from .layers import layernorm, layernorm_decl

LORA_R = 32
LORA_W = 64
MIX_KEYS = ("r", "k", "v", "g", "w")


def rwkv_decl(cfg) -> dict:
    d, H, dh, ff = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff
    tm = {
        "mu_x": P((d,), (None,), init="zeros"),
        "w0": P((H, dh), ("heads", None), init="zeros"),
        "u": P((H, dh), ("heads", None)),
        "lora_w1": P((d, LORA_W), ("embed", None)),
        "lora_w2": P((LORA_W, d), (None, "embed")),
        "wo": P((H, dh, d), ("heads", None, "embed")),
        "ln_x": layernorm_decl(dh),
    }
    if cfg.fused_qkv:
        # fused r/k/v/g projection: one x all-gather fwd, one dx all-reduce
        # bwd instead of four each (§Perf rwkv iteration 4)
        tm["wrkvg"] = P((d, 4, H, dh), ("embed", None, "heads", None))
    else:
        for key in ("wr", "wk", "wv", "wg"):
            tm[key] = P((d, H, dh), ("embed", "heads", None))
    for key in MIX_KEYS:
        tm[f"mu_{key}"] = P((d,), (None,), init="zeros")
        tm[f"A_{key}"] = P((d, LORA_R), ("embed", None))
        tm[f"B_{key}"] = P((LORA_R, d), (None, "embed"))
    cm = {
        "mu_k": P((d,), (None,), init="zeros"),
        "mu_r": P((d,), (None,), init="zeros"),
        "wk": P((d, ff), ("embed", "ff")),
        "wv": P((ff, d), ("ff", "embed")),
        "wr": P((d, d), ("embed", None)),
    }
    return {"ln1": layernorm_decl(d), "ln2": layernorm_decl(d), "tm": tm, "cm": cm}


def _shift(x, prev):
    """x: [B,T,d]; prev: [B,d] (last token of the previous window)."""
    return jnp.concatenate([prev[:, None, :], x[:, :-1, :]], axis=1)


def _ddlerp(p, key, x, xx, xin):
    mu = p[f"mu_{key}"].astype(x.dtype)
    lora = jnp.tanh(jnp.einsum("btd,dr->btr", xin, p[f"A_{key}"].astype(x.dtype)))
    lora = jnp.einsum("btr,rd->btd", lora, p[f"B_{key}"].astype(x.dtype))
    return x + (xx - x) * (mu + lora)


def wkv_scan(r, k, v, w, u, state):
    """Reference WKV6 recurrence via scan over time.
    r,k,v,w: [B,T,H,D]; u: [H,D]; state: [B,H,D,D] (f32). Returns y, state'."""
    B, T, H, D = r.shape
    rt = jnp.moveaxis(r, 1, 0).astype(jnp.float32)
    kt = jnp.moveaxis(k, 1, 0).astype(jnp.float32)
    vt = jnp.moveaxis(v, 1, 0).astype(jnp.float32)
    wt = jnp.moveaxis(w, 1, 0).astype(jnp.float32)

    def step(S, inp):
        r_, k_, v_, w_ = inp
        kv = jnp.einsum("bhi,bhj->bhij", k_, v_)
        y = jnp.einsum("bhi,bhij->bhj", r_, S + u[None, :, :, None] * kv)
        S = w_[..., None] * S + kv
        return S, y

    state, ys = jax.lax.scan(step, state.astype(jnp.float32), (rt, kt, vt, wt))
    return jnp.moveaxis(ys, 0, 1).astype(r.dtype), state


def wkv_chunked(r, k, v, w, u, state, chunk: int = 64):
    """Chunk-parallel WKV6 (GLA-style): O(T/c) sequential steps of MXU-friendly
    matmuls instead of T elementwise steps. Exact (fp32 accumulation)."""
    B, T, H, D = r.shape
    assert T % chunk == 0, (T, chunk)
    n = T // chunk
    f32 = jnp.float32
    rc = r.reshape(B, n, chunk, H, D).astype(f32)
    kc = k.reshape(B, n, chunk, H, D).astype(f32)
    vc = v.reshape(B, n, chunk, H, D).astype(f32)
    lw = jnp.log(jnp.maximum(w.reshape(B, n, chunk, H, D).astype(f32), 1e-38))
    # cumulative log-decay within each chunk, exclusive of self. Clamped so
    # the factorized exp() terms stay finite in f32; channels decaying below
    # e^-60 within one chunk contribute ~0 anyway (see wkv_scan oracle).
    cum = jnp.cumsum(lw, axis=2)                 # inclusive
    cum_excl = jnp.maximum(cum - lw, -60.0)
    total = jnp.maximum(cum[:, :, -1], -60.0)    # [B,n,H,D]

    def chunk_step(S, inp):
        r_, k_, v_, ce, tot, lw_ = inp           # [B,c,H,D] ...
        # inter-chunk: y += (r ⊙ prod_{<t} w) @ S
        r_dec = r_ * jnp.exp(ce)
        y_inter = jnp.einsum("bchi,bhij->bchj", r_dec, S)
        # intra-chunk: pairwise decays between positions s < t
        k_dec = k_ * jnp.exp(-ce - lw_)          # k_s / prod_{<=s} w
        att = jnp.einsum("bchi,bdhi->bhcd", r_dec, k_dec)
        mask = jnp.tril(jnp.ones((r_.shape[1], r_.shape[1]), bool), -1)
        att = jnp.where(mask[None, None], att, 0.0)
        diag = jnp.einsum("bchi,bchi,hi->bch", r_, k_, u)
        y_intra = jnp.einsum("bhcd,bdhj->bchj", att, v_) + diag[..., None] * v_
        # state update: S' = diag(prod w) S + sum_s (prod_{>s} w ⊙ k_s) v_s^T
        k_tail = k_ * jnp.exp(tot[:, None] - ce - lw_)
        S = jnp.exp(tot)[..., None] * S + jnp.einsum("bchi,bchj->bhij", k_tail, v_)
        return S, y_inter + y_intra

    xs = (jnp.moveaxis(rc, 1, 0), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
          jnp.moveaxis(cum_excl, 1, 0), jnp.moveaxis(total, 1, 0),
          jnp.moveaxis(lw.reshape(B, n, chunk, H, D), 1, 0))
    state, ys = jax.lax.scan(chunk_step, state.astype(f32), xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, T, H, D)
    return y.astype(r.dtype), state


def rwkv_block(p, x, cache=None, *, cfg, use_chunked=False, dist=None):
    """Full RWKV-6 layer (time mix + channel mix).
    cache: {"S": [B,H,D,D] f32, "tm_prev": [B,d], "cm_prev": [B,d]} or None."""
    from .base import constrain

    B, T, d = x.shape
    H, dh = cfg.n_heads, cfg.head_dim

    # ---- time mix ----
    xn = layernorm(p["ln1"], x)
    tm = p["tm"]
    prev = cache["tm_prev"].astype(x.dtype) if cache is not None \
        else jnp.zeros((B, d), x.dtype)
    xx = _shift(xn, prev)
    xin = xn + (xx - xn) * tm["mu_x"].astype(x.dtype)
    xr = _ddlerp(tm, "r", xn, xx, xin)
    xk = _ddlerp(tm, "k", xn, xx, xin)
    xv = _ddlerp(tm, "v", xn, xx, xin)
    xg = _ddlerp(tm, "g", xn, xx, xin)
    xw = _ddlerp(tm, "w", xn, xx, xin)

    if "wrkvg" in tm:
        # stack the four ddlerp'd inputs and project through the fused weight
        xs4 = jnp.stack([xr, xk, xv, xg], axis=2)            # [B,T,4,d]
        rkvg = jnp.einsum("btfd,dfhk->btfhk", xs4, tm["wrkvg"].astype(x.dtype))
        r, k, v, g = (rkvg[:, :, i] for i in range(4))
    else:
        r = jnp.einsum("btd,dhk->bthk", xr, tm["wr"].astype(x.dtype))
        k = jnp.einsum("btd,dhk->bthk", xk, tm["wk"].astype(x.dtype))
        v = jnp.einsum("btd,dhk->bthk", xv, tm["wv"].astype(x.dtype))
        g = jnp.einsum("btd,dhk->bthk", xg, tm["wg"].astype(x.dtype))
    wlo = jnp.einsum("btd,dr->btr", xw, tm["lora_w1"].astype(x.dtype))
    wlo = jnp.einsum("btr,rd->btd", jnp.tanh(wlo), tm["lora_w2"].astype(x.dtype))
    wln = tm["w0"].astype(jnp.float32)[None, None] + wlo.reshape(B, T, H, dh).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(wln))                                  # (0,1) decay

    if dist is not None and T > 1:
        # the WKV scan iterates the time axis: keep T *replicated* and heads
        # model-sharded here, or every scan step emits an all-gather (the
        # §Perf rwkv baseline pathology — one collective per token step)
        spec = ("batch", None, "heads", None)
        r = constrain(r, dist, spec)
        k = constrain(k, dist, spec)
        v = constrain(v, dist, spec)
        w = constrain(w, dist, spec)

    state = cache["S"] if cache is not None else jnp.zeros((B, H, dh, dh), jnp.float32)
    u = tm["u"].astype(jnp.float32)
    if use_chunked and T > 1 and T % 64 == 0:
        y, state = wkv_chunked(r, k, v, w.astype(x.dtype), u, state)
    else:
        y, state = wkv_scan(r, k, v, w.astype(x.dtype), u, state)
    y = layernorm(tm["ln_x"], y)                                 # per-head norm
    y = y * jax.nn.silu(g)
    x = x + jnp.einsum("bthk,hkd->btd", y, tm["wo"].astype(x.dtype))

    # ---- channel mix ----
    cm = p["cm"]
    xn2 = layernorm(p["ln2"], x)
    prev2 = cache["cm_prev"].astype(x.dtype) if cache is not None \
        else jnp.zeros((B, d), x.dtype)
    xx2 = _shift(xn2, prev2)
    xk2 = xn2 + (xx2 - xn2) * cm["mu_k"].astype(x.dtype)
    xr2 = xn2 + (xx2 - xn2) * cm["mu_r"].astype(x.dtype)
    kk = jnp.einsum("btd,df->btf", xk2, cm["wk"].astype(x.dtype))
    kk = jnp.square(jax.nn.relu(kk))
    out = jnp.einsum("btf,fd->btd", kk, cm["wv"].astype(x.dtype))
    rr = jax.nn.sigmoid(jnp.einsum("btd,de->bte", xr2, cm["wr"].astype(x.dtype)))
    x = x + rr * out

    new_cache = None
    if cache is not None:
        new_cache = {"S": state, "tm_prev": xn[:, -1, :].astype(jnp.float32),
                     "cm_prev": xn2[:, -1, :].astype(jnp.float32)}
    return x, new_cache


def rwkv_cache_decl(cfg, batch: int) -> dict:
    H, dh, d = cfg.n_heads, cfg.head_dim, cfg.d_model
    return {"S": jnp.zeros((batch, H, dh, dh), jnp.float32),
            "tm_prev": jnp.zeros((batch, d), jnp.float32),
            "cm_prev": jnp.zeros((batch, d), jnp.float32)}
