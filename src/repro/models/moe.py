"""Mixture-of-Experts block: token-choice top-k routing with capacity,
grouped-GEMM expert compute, and a chunked EP x TP hybrid layout:

Routed expert weights are stored as lcm(E, M) *chunks* — chunk ``e*tp + j``
holds expert e's j-th d_ff slice (tp = M / gcd(E, M)) — and the chunk axis is
sharded over 'model'. This gives pure EP when E % M == 0 (DeepSeek: 4 experts
per rank), expert-TP when E < M (Mixtral on model=16: each rank holds half of
one expert's d_ff), and every hybrid in between, with zero weight replication
across the TP axis.

Dispatch is *local* per data shard (standard at scale): inside ``shard_map``
each rank routes its own tokens, computes its chunk's partial expert outputs,
combines into per-token outputs, and one [T_local, d] psum over 'model'
finishes the job — the cheapest possible combine collective.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from .base import P

PRODUCTION_M = 16  # model-axis size of the production mesh (chunk layout)


def moe_chunking(E: int, M: int = PRODUCTION_M) -> tuple[int, int]:
    """Returns (tp, n_chunks): tp d_ff slices per expert, E*tp chunks total."""
    tp = M // math.gcd(E, M)
    return tp, E * tp


def moe_decl(cfg) -> dict:
    E, d, ff = cfg.n_experts, cfg.d_model, cfg.moe_ff or cfg.d_ff
    tp, n_chunks = moe_chunking(E)
    assert ff % tp == 0, (ff, tp)
    ff_tp = ff // tp
    decl = {
        "router": P((d, E), ("embed", None)),
        "wg": P((n_chunks, d, ff_tp), ("experts", "embed", None)),
        "wu": P((n_chunks, d, ff_tp), ("experts", "embed", None)),
        "wd": P((n_chunks, ff_tp, d), ("experts", None, "embed")),
    }
    if cfg.n_shared:
        sff = (cfg.moe_ff or cfg.d_ff) * cfg.n_shared
        decl["shared"] = {
            "w_gate": P((d, sff), ("embed", "ff")),
            "w_up": P((d, sff), ("embed", "ff")),
            "w_down": P((sff, d), ("ff", "embed")),
        }
    return decl


def unchunk(w, E: int, ff_axis: int):
    """[n_chunks, a, b] chunk layout -> dense [E, d, ff] / [E, ff, d]."""
    n_chunks = w.shape[0]
    tp = n_chunks // E
    if tp == 1:
        return w
    if ff_axis == 2:   # wg/wu: [E, tp, d, ff_tp] -> [E, d, ff]
        return w.reshape(E, tp, w.shape[1], w.shape[2]) \
                .transpose(0, 2, 1, 3).reshape(E, w.shape[1], tp * w.shape[2])
    # wd: [E, tp, ff_tp, d] -> [E, ff, d]
    return w.reshape(E, tp, w.shape[1], w.shape[2]) \
            .reshape(E, tp * w.shape[1], w.shape[2])


def _route(xt, router, top_k):
    """xt: [T, d] -> (weights [T,k], idx [T,k], aux_loss)."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router.astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(gates, top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    E = router.shape[-1]
    me = gates.mean(axis=0)                                   # [E]
    ce = jnp.zeros(E, jnp.float32).at[idx.reshape(-1)].add(1.0) / idx.size
    aux = E * jnp.sum(me * ce)
    return w.astype(xt.dtype), idx, aux


def _dispatch(xt, idx, E, C):
    """Scatter tokens into an expert-major buffer [E, C, d] with capacity."""
    T, k = idx.shape
    flat_e = idx.reshape(-1)                                    # [T*k]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos_in_e = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = pos_in_e < C
    slot = jnp.where(keep, flat_e * C + pos_in_e, E * C)        # OOB when dropped
    token_of_slot = jnp.zeros((E * C,), jnp.int32).at[slot].set(
        jnp.repeat(jnp.arange(T, dtype=jnp.int32), k), mode="drop")
    filled = jnp.zeros((E * C,), bool).at[slot].set(True, mode="drop")
    buf = jnp.where(filled[:, None], xt[token_of_slot], 0).reshape(E, C, xt.shape[1])
    return buf, slot, keep


def moe_apply(p, x, cfg, *, model_axis: Optional[str] = None,
              all_axes: tuple = ()):
    """MoE block over x: [B, S, d]. Inside shard_map, p holds local chunks."""
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    E, k = cfg.n_experts, cfg.top_k
    C = max(1, int(math.ceil(T * k / E * cfg.capacity_factor)))

    w, idx, aux = _route(xt, p["router"], k)
    buf, slot, keep = _dispatch(xt, idx, E, C)

    wg, wu, wd = p["wg"], p["wu"], p["wd"]
    tp_total, n_chunks_total = moe_chunking(E, PRODUCTION_M)

    if model_axis is not None:
        # Local chunk slice: chunk ids r*cpr + [0, cpr) map to a contiguous,
        # statically-sized expert range (expert of chunk c == c // tp).
        cpr = wg.shape[0]                       # chunks on this rank (static)
        tp_static = n_chunks_total // E
        r = jax.lax.axis_index(model_axis)
        n_exp = max(1, cpr // tp_static)
        e_start = (r * cpr) // tp_static
        mybuf = jax.lax.dynamic_slice_in_dim(buf, e_start, n_exp, axis=0)
        mybuf_chunks = jnp.repeat(mybuf, cpr // n_exp, axis=0)  # [cpr, C, d]
        h = jnp.einsum("ecd,edf->ecf", mybuf_chunks, wg.astype(x.dtype))
        u = jnp.einsum("ecd,edf->ecf", mybuf_chunks, wu.astype(x.dtype))
        out_chunks = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u,
                                wd.astype(x.dtype))             # [cpr, C, d]
        out_loc = out_chunks.reshape(n_exp, cpr // n_exp, C, d).sum(axis=1)
        out = jnp.zeros((E, C, d), x.dtype)
        out = jax.lax.dynamic_update_slice_in_dim(out, out_loc, e_start, axis=0)
    else:
        # single-device / no-mesh path: reconstruct dense expert weights
        wg_f = unchunk(wg, E, ff_axis=2).astype(x.dtype)
        wu_f = unchunk(wu, E, ff_axis=2).astype(x.dtype)
        wd_f = unchunk(wd, E, ff_axis=1).astype(x.dtype)
        h = jnp.einsum("ecd,edf->ecf", buf, wg_f)
        u = jnp.einsum("ecd,edf->ecf", buf, wu_f)
        out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, wd_f)

    # combine: gather each (token, k) slot's output, weight, sum over k.
    slot_g = jnp.minimum(slot, E * C - 1)
    gathered = jnp.where(keep[:, None], out.reshape(E * C, d)[slot_g], 0)
    y = (gathered.reshape(T, k, d) * w[..., None].astype(x.dtype)).sum(axis=1)

    if cfg.n_shared:
        sp = p["shared"]
        g = jnp.einsum("td,df->tf", xt, sp["w_gate"].astype(x.dtype))
        u2 = jnp.einsum("td,df->tf", xt, sp["w_up"].astype(x.dtype))
        y = y + jnp.einsum("tf,fd->td", jax.nn.silu(g) * u2,
                           sp["w_down"].astype(x.dtype))

    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)
    if all_axes:
        aux = jax.lax.pmean(aux, all_axes)

    return y.reshape(B, S, d), aux


def moe_specs(p, cfg, mesh, batch_axes):
    """shard_map in/out specs for the MoE params + activations."""
    xspec = PS(batch_axes, None, None)
    wspec = PS("model", None, None)
    pspec = {"router": PS(None, None), "wg": wspec, "wu": wspec, "wd": wspec}
    if "shared" in p:
        pspec["shared"] = {"w_gate": PS(None, "model"), "w_up": PS(None, "model"),
                           "w_down": PS("model", None)}
    return pspec, xspec


def moe_block(p, x, cfg, dist=None):
    """Entry point: shard_map'd when a mesh is available, local otherwise."""
    if (dist is None or dist.mesh is None
            or "model" not in dist.mesh.axis_names
            or p["wg"].shape[0] % dist.mesh.shape["model"] != 0):
        return moe_apply(p, x, cfg, model_axis=None)

    mesh = dist.mesh
    batch_axes = dist.batch_axes_for(x.shape[0])
    pspec, xspec = moe_specs(p, cfg, mesh, batch_axes)
    all_axes = tuple(mesh.axis_names)
    fn = partial(moe_apply, cfg=cfg, model_axis="model", all_axes=all_axes)
    smapped = jax.shard_map(fn, mesh=mesh, in_specs=(pspec, xspec),
                            out_specs=(xspec, PS()), check_vma=False)
    return smapped(p, x)
