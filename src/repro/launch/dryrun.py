"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production meshes and record memory/cost/collective artifacts.

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Run as a script it gives the CPU backend 512 host devices; a process that
imports it sets ``--xla_force_host_platform_device_count`` itself before
JAX starts. Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>.json
and feed the roofline table in EXPERIMENTS.md.
"""

import os

if __name__ == "__main__":
    # before jax initializes its backend; added to, never overwriting, flags
    # the caller set
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512")

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

from .. import configs
from ..distributed import make_dist
from ..models import zoo
from ..models.base import spec_tree
from ..models.config import SHAPES
from ..train import AdamWConfig, adamw_init, make_train_step
from . import hlo_cost
from .mesh import make_production_mesh
from .roofline import (active_params, model_flops, parse_collectives,
                       roofline_terms)

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def _sds(tree_abstract, tree_spec, mesh):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tree_abstract, tree_spec)


def cache_specs(cache, cfg, dist):
    """Shape-aware KV/state cache shardings (SP when batch is unshardable)."""
    mesh = dist.mesh
    M = mesh.shape["model"]

    def leaf_spec(path, a):
        key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if key in ("enc_k", "enc_v"):
            key = key[-1]  # treat like stacked k/v
        shape = a.shape
        if key == "pos":
            return PS()
        batch_dim = 1 if key in ("k", "v") and len(shape) == 5 else 0
        b_ax = dist.batch_axes_for(shape[batch_dim])
        seq_ax = None
        if b_ax is None and key in ("k", "v", "ckv", "kr") and len(shape) >= 3:
            # sequence parallelism over the cache when batch can't shard
            if shape[batch_dim + 1] % mesh.shape["data"] == 0:
                seq_ax = "data"
        if key in ("k", "v"):
            if len(shape) == 5:   # [L, B, S, H, dh] (enc-dec stacks)
                h_ax = "model" if shape[3] % M == 0 else None
                d_ax = "model" if h_ax is None and shape[4] % M == 0 else None
                return PS(None, b_ax, seq_ax, h_ax, d_ax)
            h_ax = "model" if shape[2] % M == 0 else None
            d_ax = "model" if h_ax is None and shape[3] % M == 0 else None
            return PS(b_ax, seq_ax, h_ax, d_ax)
        if key in ("ckv", "kr"):
            return PS(b_ax, seq_ax, None)
        if key == "S":            # rwkv state [B, H, dk, dv]
            return PS(b_ax, "model" if shape[1] % M == 0 else None, None, None)
        if key in ("tm_prev", "cm_prev"):
            return PS(b_ax, None)
        if key == "h":            # rglru [B, lru]
            return PS(b_ax, "model" if shape[1] % M == 0 else None)
        if key == "conv":         # [B, K-1, lru]
            return PS(b_ax, None, "model" if shape[2] % M == 0 else None)
        return PS(*([None] * len(shape)))

    import jax.tree_util as jtu
    return jtu.tree_map_with_path(leaf_spec, cache)


def _strip_layer_axis(specs_tree):
    return specs_tree


def abstract_cache(cfg, model, batch, seq_len, dtype=jnp.bfloat16):
    cache = jax.eval_shape(lambda: model.init_cache(batch, seq_len, dtype))
    return cache


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides: dict | None = None):
    """Returns (lower_fn, meta). lower_fn() -> lowered."""
    cfg = configs.get(arch)
    _driver_keys = ("microbatches", "no_train_sp", "param_dtype")
    if overrides:
        cfg_over = {k: v for k, v in overrides.items() if k not in _driver_keys}
        if cfg_over:
            cfg = cfg.scaled(**cfg_over)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    seq_sharded = (shape.kind == "decode"
                   and shape.global_batch < mesh.shape["data"])
    train_sp = (shape.kind in ("train", "prefill")
                and shape.seq_len % mesh.shape["model"] == 0
                and not (overrides or {}).get("no_train_sp"))
    dist = make_dist(mesh, seq_sharded=seq_sharded,
                     train_seq_sharded=train_sp)
    model = zoo.build(cfg, dist)
    B = shape.global_batch
    pspecs = spec_tree(model.decl, dist.rules, mesh)
    # training uses fp32 master weights; serving cells may opt into bf16
    # weights (beyond-paper: §2.4 storage quantization feeds the serving
    # precision directly — weight streaming is decode's memory bound)
    param_dtype = jnp.dtype((overrides or {}).get("param_dtype", "float32"))
    params_sds = _sds(model.abstract_params(param_dtype), pspecs, mesh)
    b_ax = dist.batch_axes_for(B)

    def tok_sds(S):
        return jax.ShapeDtypeStruct((B, S), jnp.int32,
                                    sharding=NamedSharding(mesh, PS(b_ax, None)))

    vocab_ax = "model" if cfg.vocab % mesh.shape["model"] == 0 else None

    frames_sds = None
    if cfg.encoder is not None:
        frames_sds = jax.ShapeDtypeStruct(
            (B, cfg.encoder.seq, cfg.d_model), jnp.bfloat16,
            sharding=NamedSharding(mesh, PS(b_ax, None, None)))

    if shape.kind == "train":
        opt_specs = {"m": pspecs, "v": pspecs, "step": PS()}
        opt_sds = {"m": params_sds, "v": params_sds,
                   "step": jax.ShapeDtypeStruct((), jnp.int32,
                                                sharding=NamedSharding(mesh, PS()))}
        batch_sds = {"tokens": tok_sds(shape.seq_len + 1)}
        if frames_sds is not None:
            batch_sds["frames"] = frames_sds
        # microbatch so each accumulation step sees <= ~16Ki tokens per data
        # shard: bounds activation/dispatch working sets and lets XLA overlap
        # per-microbatch collectives with the next microbatch's compute.
        data_shards = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        tokens_per_shard = B * shape.seq_len // data_shards
        mb = 1
        for cand in (1, 2, 4, 8, 16):
            if B % cand == 0 and tokens_per_shard // cand > 16384:
                mb = cand * 2 if B % (cand * 2) == 0 else cand
        mb = (overrides or {}).get("microbatches", mb)
        step = make_train_step(model, AdamWConfig(), microbatches=mb)
        out_shardings = (
            jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
            jax.tree.map(lambda s: NamedSharding(mesh, s), opt_specs,
                         is_leaf=lambda x: isinstance(x, PS)),
            NamedSharding(mesh, PS()),
        )
        def lower():
            with mesh:
                return jax.jit(step, out_shardings=out_shardings,
                               donate_argnums=(0, 1)).lower(
                    params_sds, opt_sds, batch_sds)
    elif shape.kind == "prefill":
        cache_abs = abstract_cache(cfg, model, B, shape.seq_len)
        cspecs = cache_specs(cache_abs, cfg, dist)
        cache_sds = _sds(cache_abs, cspecs, mesh)
        batch_sds = {"tokens": tok_sds(shape.seq_len)}
        if frames_sds is not None:
            batch_sds["frames"] = frames_sds
        out_shardings = (NamedSharding(mesh, PS(b_ax, vocab_ax)),
                         jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs,
                                      is_leaf=lambda x: isinstance(x, PS)))
        def lower():
            with mesh:
                return jax.jit(model.prefill, out_shardings=out_shardings,
                               donate_argnums=(2,)).lower(
                    params_sds, batch_sds, cache_sds)
    else:  # decode
        cache_abs = abstract_cache(cfg, model, B, shape.seq_len)
        cspecs = cache_specs(cache_abs, cfg, dist)
        cache_sds = _sds(cache_abs, cspecs, mesh)
        tokens_sds = tok_sds(1)
        out_shardings = (NamedSharding(mesh, PS(b_ax, vocab_ax)),
                         jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs,
                                      is_leaf=lambda x: isinstance(x, PS)))
        def lower():
            with mesh:
                return jax.jit(model.decode_step, out_shardings=out_shardings,
                               donate_argnums=(1,)).lower(
                    params_sds, cache_sds, tokens_sds)

    meta = {"arch": cfg.name, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "n_devices": int(np.prod(list(mesh.shape.values()))),
            "n_params": model.n_params,
            "n_params_active": active_params(cfg, model.n_params)}
    return lower, meta, cfg, shape


def should_skip(arch: str, shape_name: str) -> str | None:
    cfg = configs.get(arch)
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return ("full-attention family: long_500k requires sub-quadratic "
                "attention (see DESIGN.md §Arch-applicability)")
    return None


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = ARTIFACT_DIR, overrides: dict | None = None,
             tag: str = "") -> dict:
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_tag}
    skip = should_skip(arch, shape_name)
    if skip:
        rec.update(status="skipped", reason=skip)
        _save(rec, out_dir, arch, shape_name, mesh_tag, tag)
        return rec
    t0 = time.time()
    try:
        lower, meta, cfg, shape = build_cell(arch, shape_name, multi_pod,
                                             overrides)
        rec.update(meta)
        lowered = lower()
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        xla_cost = compiled.cost_analysis() or {}
        if isinstance(xla_cost, (list, tuple)):  # older jaxlib: one dict per device
            xla_cost = xla_cost[0] if xla_cost else {}
        try:
            mem = compiled.memory_analysis()
            mem_rec = {k: int(getattr(mem, k)) for k in
                       ("argument_size_in_bytes", "output_size_in_bytes",
                        "temp_size_in_bytes", "generated_code_size_in_bytes")
                       if hasattr(mem, k)}
        except Exception as e:  # CPU backend may not implement it
            mem_rec = {"error": str(e)}
        text = compiled.as_text()
        # trip-count-aware analysis (XLA's cost_analysis counts while bodies
        # once — see hlo_cost.py); xla_cost kept as a reference field
        hc = hlo_cost.analyze(text, meta["n_devices"])
        flops = hc["flops"]
        bytes_acc = hc["bytes"]
        coll = {"bytes_by_kind": hc["collective_by_kind"],
                "counts": hc["collective_counts"],
                "total_bytes": hc["collective_bytes"]}

        mf = model_flops(cfg, shape, meta["n_params"], meta["n_params_active"])
        mf_per_dev = mf / meta["n_devices"]
        terms = roofline_terms(flops, bytes_acc, coll["total_bytes"])
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            flops_per_device=flops, bytes_per_device=bytes_acc,
            collectives=coll, memory=mem_rec,
            xla_cost={"flops": float(xla_cost.get("flops", 0.0)),
                      "bytes_accessed": float(xla_cost.get("bytes accessed", 0.0))},
            model_flops_total=mf, model_flops_per_device=mf_per_dev,
            useful_flops_ratio=(mf_per_dev / flops) if flops else None,
            roofline=terms,
        )
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    _save(rec, out_dir, arch, shape_name, mesh_tag, tag)
    return rec


def _save(rec, out_dir, arch, shape_name, mesh_tag, tag=""):
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    fn = f"{arch.replace('.', '_')}__{shape_name}__{mesh_tag}{suffix}.json"
    with open(os.path.join(out_dir, fn), "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    args = ap.parse_args()

    archs = list(configs.ARCHS) if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, out_dir=args.out)
                status = rec.get("status")
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" compute={r['compute_s']:.3e}s "
                             f"mem={r['memory_s']:.3e}s coll={r['collective_s']:.3e}s"
                             f" dom={r['dominant']} compile={rec['compile_s']}s")
                    mem_rec = rec.get("memory", {})
                    print(f"[mem] {mem_rec}")
                elif status == "error":
                    extra = " " + rec["error"][:200]
                elif status == "skipped":
                    extra = " " + rec["reason"][:80]
                print(f"{arch:18s} {shape:12s} {rec['mesh']:8s} {status}{extra}",
                      flush=True)


if __name__ == "__main__":
    main()
