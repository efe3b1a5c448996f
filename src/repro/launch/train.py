"""End-to-end training driver: Bullion data -> loader -> model -> AdamW, with
fault-tolerant checkpointing and auto-resume.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 50 --batch 8 --seq 128

Full-size configs lower the same code path on the production mesh via
repro.launch.dryrun; this driver runs a config end-to-end on the default
device. ``--layers`` cuts depth at the published widths (llama3.2-1b holds
one TPU v5e at 4 of its 16 layers with f32 AdamW); the step's device memory
from ``memory_analysis()`` is printed before the first step.
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import configs
from ..data import BullionLoader, write_lm_corpus
from ..data.loader import LoaderState
from ..models import zoo
from ..train import AdamWConfig, adamw_init, make_train_step
from ..train.checkpoint import CheckpointManager
from .cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--data", default="/tmp/bullion_lm")
    ap.add_argument("--ckpt", default="/tmp/bullion_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--d-model", type=int, default=0,
                    help="override width (0 = config default)")
    ap.add_argument("--layers", type=int, default=0,
                    help="override depth (0 = config default)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the generated corpus and the initial weights")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    cfg = cfg.scaled(compute_dtype="float32")
    if args.d_model:
        cfg = cfg.scaled(d_model=args.d_model,
                         head_dim=args.d_model // cfg.n_heads,
                         d_ff=args.d_model * 4)
    if args.layers:
        if len(cfg.segments) != 1 or args.layers % len(cfg.segments[0][0]):
            raise ValueError(f"--layers {args.layers} does not fit the "
                             f"segments of {cfg.name}: {cfg.segments}")
        blocks = cfg.segments[0][0]
        cfg = cfg.scaled(segments=((blocks, args.layers // len(blocks)),))
    model = zoo.build(cfg)

    os.makedirs(args.data, exist_ok=True)
    corpus = os.path.join(args.data, "corpus.bln")
    if not os.path.exists(corpus):
        stats = write_lm_corpus(corpus, vocab=cfg.vocab,
                                n_docs=max(64, args.batch * 8),
                                doc_len=max(512, args.seq * 4),
                                seed=args.seed)
        print(f"wrote corpus: {stats}")

    mgr = CheckpointManager(args.ckpt, keep=2)
    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps)
    opt_state = adamw_init(params)
    start_step = 0
    loader_state = LoaderState()

    latest = mgr.latest_step()
    if latest is not None:
        (params, opt_state), manifest = mgr.restore((params, opt_state))
        start_step = manifest["step"]
        loader_state = LoaderState(manifest.get("epoch", 0),
                                   manifest.get("group", 0))
        print(f"resumed from step {start_step}")

    # params and optimizer state are donated: the step updates them in place
    # instead of holding both generations on the device
    tokens_spec = jax.ShapeDtypeStruct((args.batch, args.seq + 1), jnp.int32)
    step_fn = jax.jit(make_train_step(model, opt_cfg,
                                      microbatches=args.microbatches),
                      donate_argnums=(0, 1)) \
        .lower(params, opt_state, {"tokens": tokens_spec}).compile()
    mem = step_fn.memory_analysis()
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{model.n_params:,} params; step device memory: "
          + (f"args {mem.argument_size_in_bytes:,} B, "
             f"temp {mem.temp_size_in_bytes:,} B, "
             f"out {mem.output_size_in_bytes:,} B, "
             f"aliased {mem.alias_size_in_bytes:,} B"
             if mem is not None else "not reported"))
    loader = BullionLoader(corpus, batch_size=args.batch, seq_len=args.seq,
                           state=loader_state)

    it = iter(loader)
    t0 = time.perf_counter()
    losses = []
    cursor = loader_state
    for step in range(start_step, args.steps):
        batch_np, cursor = next(it)
        batch = {"tokens": jnp.asarray(batch_np)}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if (step + 1) % args.log_every == 0:
            dt = time.perf_counter() - t0
            tok_s = args.log_every * args.batch * args.seq / dt
            print(f"step {step+1:5d} loss {np.mean(losses[-args.log_every:]):.4f} "
                  f"tokens {batch_np.size} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} tok/s {tok_s:,.0f}")
            t0 = time.perf_counter()
        if (step + 1) % args.ckpt_every == 0 or step + 1 == args.steps:
            mgr.save(step + 1, (params, opt_state),
                     extra={"epoch": cursor.epoch, "group": cursor.group,
                            "loss": float(metrics["loss"])})
    mgr.wait()
    loader.close()
    first = np.mean(losses[:10]) if len(losses) >= 10 else losses[0]
    last = np.mean(losses[-10:])
    print(f"done: loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    return losses


if __name__ == "__main__":
    main()
