#!/usr/bin/env python3
"""Drive Bullion's two device paths once on a TPU and check every answer.

  python chip_smoke.py              # phases A and B on one chip
  python chip_smoke.py --chips 4    # phase C only, on a 2x2 v5e host

Phase A (store and serve): writes a sharded feature table with
``BullionWriter`` (4 shards x 1,048,576 rows, 65,536-row groups: int64
``user_id``, 8 raw float32 features, 8 BF16-quantized float32 features, an
int8 ``label``), attaches it to a ``DatasetServer`` and answers a 3-column
range filter over the AF_UNIX socket, a 1-column range, a ``user_id`` point
probe (bloom sketch path) and a dequantized BF16 projection through
``dataset()``. Every answer must equal a NumPy evaluation of the same
predicate over the generated arrays. It then checks that the range filter
ran as a compiled Mosaic kernel and runs the dequant kernel against its
reference on the BF16 bits the store holds.

Phase B (training ingest): ``repro.launch.train.main`` trains llama3.2-1b at
its published widths, depth cut to 4 of 16 layers, batch 4 x seq 1024, for 5
steps over a corpus it writes and reads through ``BullionLoader``. Every loss
must be finite.

Phase C (``--chips 4``): four ``BullionLoader`` ranks, one per device, feed
a train step sharded over a (data=4, model=1) mesh. The first step's loss
must agree with the same global batch stepped on one device within 1e-3.

Data comes from ``--seed`` and lives in a temporary directory outside the
checkout. Where JAX finds no TPU the script exits non-zero and prints no
result. The last line of stdout is the result as JSON; timings on earlier
lines are host wall clock of a cold run, not device metrics.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro import configs, kernels  # noqa: E402
from repro.core import BullionWriter, ColumnSpec, QuantMode, QuantSpec  # noqa: E402
from repro.data import BullionLoader, write_lm_corpus  # noqa: E402
from repro.dataset import dataset  # noqa: E402
from repro.distributed import make_dist  # noqa: E402
from repro.kernels.dequant import dequant, dequant_ref  # noqa: E402
from repro.kernels.filter.kernel import range_mask_pallas  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import zoo  # noqa: E402
from repro.models.base import spec_tree  # noqa: E402
from repro.obs import metrics  # noqa: E402
from repro.scan import C  # noqa: E402
from repro.serve import DatasetServer, ServeClient  # noqa: E402
from repro.train import AdamWConfig, adamw_init, make_train_step  # noqa: E402

N_FEATURES = 8
ARCH = "llama3.2-1b"
LAYERS = 4              # of 16: what one v5e holds with f32 AdamW state


def log(msg: str) -> None:
    print(msg, flush=True)


def _between(col: str, lo: float, hi: float):
    return (C(col) >= lo) & (C(col) <= hi)


def _np_between(x: np.ndarray, lo: float, hi: float) -> np.ndarray:
    return (x >= lo) & (x <= hi)


def _same(name: str, got: dict, want: dict) -> None:
    """Rows and values identical, column by column."""
    for col, ref in want.items():
        if not np.array_equal(np.asarray(got[col]), ref):
            raise AssertionError(f"{name}: column {col!r} differs from the "
                                 f"NumPy reference ({len(got[col])} rows "
                                 f"served, {len(ref)} expected)")


# ---------------------------------------------------------------------------
# phase A: store and serve
# ---------------------------------------------------------------------------


def feature_schema() -> list:
    return ([ColumnSpec("user_id", "int64")]
            + [ColumnSpec(f"f{i}", "float32") for i in range(N_FEATURES)]
            + [ColumnSpec(f"q{i}", "float32", quant=QuantSpec(QuantMode.BF16))
               for i in range(N_FEATURES)]
            + [ColumnSpec("label", "int8")])


def make_features(rng, n: int) -> dict:
    """One shard's columns, drawn in bulk. ``user_id`` is unclustered."""
    tbl = {"user_id": rng.integers(0, 1 << 40, n, dtype=np.int64)}
    for i in range(N_FEATURES):
        tbl[f"f{i}"] = rng.standard_normal(n, dtype=np.float32)
    for i in range(N_FEATURES):
        tbl[f"q{i}"] = rng.standard_normal(n, dtype=np.float32)
    tbl["label"] = rng.integers(0, 2, n, dtype=np.int8)
    return tbl


def phase_store_and_serve(root: str, seed: int, *, shards: int = 4,
                          rows_per_shard: int = 1 << 20,
                          rows_per_group: int = 65536) -> dict:
    rng = np.random.default_rng(seed)
    table_dir = os.path.join(root, "features")
    os.makedirs(table_dir)
    parts = []
    t0 = time.perf_counter()
    for s in range(shards):
        part = make_features(rng, rows_per_shard)
        w = BullionWriter(os.path.join(table_dir, f"part-{s:03d}.bln"),
                          feature_schema(), rows_per_group=rows_per_group)
        w.write_table(part)
        w.close()
        parts.append(part)
    ref = {c: np.concatenate([p[c] for p in parts]) for c in parts[0]}
    del parts
    n = len(ref["user_id"])
    on_disk = sum(os.path.getsize(os.path.join(table_dir, f))
                  for f in os.listdir(table_dir))
    log(f"phase A: wrote {shards} shards x {rows_per_shard:,} rows "
        f"({n // rows_per_group} groups of {rows_per_group:,}), "
        f"{on_disk:,} bytes, in {time.perf_counter() - t0:.3f} s "
        f"(host wall clock, cold run)")
    # a BF16 column reads back rounded to bfloat16, in the float32 domain
    for i in range(N_FEATURES):
        ref[f"q{i}"] = ref[f"q{i}"].astype(ml_dtypes.bfloat16) \
            .astype(np.float32)

    calls0 = metrics.counter("bullion.filter.kernel_calls").value
    # bounds are exact in float32, so every evaluation order agrees.
    # ~10%: each interval holds ~46.5% of a standard normal, cubed ~0.1
    r = 0.62109375
    a_pred = (_between("f0", -r, r) & _between("f1", -r, r)
              & _between("f2", -r, r))
    a_mask = (_np_between(ref["f0"], -r, r) & _np_between(ref["f1"], -r, r)
              & _np_between(ref["f2"], -r, r))
    b_pred = _between("f5", 2.328125, 8.0)                  # ~1%
    b_mask = _np_between(ref["f5"], 2.328125, 8.0)
    key = int(ref["user_id"][n // 3])
    c_mask = ref["user_id"] == key
    d_pred = _between("q0", 1.0, 1.5)                       # ~9%
    d_mask = _np_between(ref["q0"], 1.0, 1.5)
    q_cols = [f"q{i}" for i in range(N_FEATURES)]
    f_cols = [f"f{i}" for i in range(N_FEATURES)]

    with DatasetServer({"features": table_dir}) as server:
        sock = server.serve()
        t0 = time.perf_counter()
        with ServeClient(sock, timeout=600) as client:
            # an error frame raises ServeError here
            got = client.query("features", columns=["user_id", "f3", "f4"],
                               where=a_pred)
        log(f"phase A: query a (3-column range over the socket) "
            f"{got.rows:,} rows in {time.perf_counter() - t0:.3f} s "
            f"(host wall clock, cold run)")
        _same("query a", got.table,
              {c: ref[c][a_mask] for c in ("user_id", "f3", "f4")})

        t0 = time.perf_counter()
        res = server.query("features", columns=["user_id", "f5"], where=b_pred)
        log(f"phase A: query b (1-column range) {res.rows:,} rows in "
            f"{time.perf_counter() - t0:.3f} s (host wall clock, cold run)")
        _same("query b", res.table,
              {c: ref[c][b_mask] for c in ("user_id", "f5")})

        def sketch_refuted() -> int:
            return server.stats()["datasets"]["features"]["io"][
                "groups_pruned_sketch"]

        refuted = sketch_refuted()
        t0 = time.perf_counter()
        res = server.query("features", columns=["user_id"] + f_cols + q_cols,
                           where=C("user_id") == key)
        refuted = sketch_refuted() - refuted
        log(f"phase A: query c (user_id point probe) {res.rows} row(s) in "
            f"{time.perf_counter() - t0:.3f} s (host wall clock, cold run); "
            f"{refuted} of {n // rows_per_group} groups refuted by bloom "
            f"sketches")
        _same("query c", res.table,
              {c: ref[c][c_mask] for c in ["user_id"] + f_cols + q_cols})
        if refuted == 0:
            raise AssertionError("query c: no group was refuted by a sketch")

    ds = dataset(table_dir).select(q_cols).where(d_pred)
    t0 = time.perf_counter()
    deq = ds.dequantized().to_table()
    log(f"phase A: query d (dequantized BF16 projection under a range) "
        f"{len(deq['q0']):,} rows in {time.perf_counter() - t0:.3f} s "
        f"(host wall clock, cold run)")
    _same("query d", deq, {c: ref[c][d_mask] for c in q_cols})
    ds.close()
    calls = metrics.counter("bullion.filter.kernel_calls").value - calls0

    # the dequant kernel on the bits the store holds, against its reference
    # and against the host dequantize that served query d
    ds = dataset(table_dir).select(q_cols).where(d_pred)
    bits = ds.dequantized(False).to_table()
    ds.close()
    q = jnp.asarray(np.stack([bits[c] for c in q_cols], axis=1))
    ones, zeros = jnp.ones(N_FEATURES), jnp.zeros(N_FEATURES)
    out = np.asarray(dequant(q, ones, zeros, out_dtype=jnp.float32))
    if not np.array_equal(out, np.asarray(
            dequant_ref(q, ones, zeros, jnp.float32))):
        raise AssertionError("dequant kernel (bf16 bits) differs from "
                             "dequant_ref")
    _same("dequant kernel", {c: out[:, i] for i, c in enumerate(q_cols)},
          {c: deq[c] for c in q_cols})
    q8 = jnp.asarray(rng.integers(-128, 128, (rows_per_group, 256),
                                  dtype=np.int8))
    scale = jnp.asarray(rng.uniform(0.01, 1.0, 256).astype(np.float32))
    zero = jnp.asarray(rng.uniform(-1.0, 1.0, 256).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(dequant(q8, scale, zero, out_dtype=jnp.float32)),
        np.asarray(dequant_ref(q8, scale, zero, jnp.float32)),
        rtol=1e-6, atol=1e-6)
    log(f"phase A: dequant kernel equals dequant_ref on {q.shape[0]:,} x "
        f"{N_FEATURES} stored BF16 values and on {rows_per_group:,} x 256 "
        f"int8 affine values")

    # how the filter ran: the range filter's program for one row group, with
    # the interpreter choice the kernel wrappers make on this backend
    spec = jax.ShapeDtypeStruct((3, rows_per_group), jnp.float32)
    bound = jax.ShapeDtypeStruct((3,), jnp.float32)
    text = range_mask_pallas.lower(spec, bound, bound,
                                   interpret=kernels.interpret()) \
        .compile().as_text()
    mosaic = "tpu_custom_call" in text
    how = ("compiled Mosaic kernel (tpu_custom_call)" if mosaic
           else "Pallas interpreter")
    log(f"phase A: range filter ran as {how} on {jax.default_backend()}; "
        f"{calls} kernel call(s) served queries a, b and d")
    if calls < 3 * n // rows_per_group:
        raise AssertionError(f"range filter kernel ran {calls} times")
    return {"rows": n, "kernel_calls": calls, "mosaic": mosaic,
            "sketch_pruned": refuted}


# ---------------------------------------------------------------------------
# phase B: training ingest through the training driver
# ---------------------------------------------------------------------------


def phase_train(root: str, seed: int, *, layers: int = LAYERS,
                batch: int = 4, seq: int = 1024, steps: int = 5,
                smoke: bool = False) -> list:
    argv = ["--arch", ARCH, "--layers", str(layers), "--steps", str(steps),
            "--batch", str(batch), "--seq", str(seq), "--seed", str(seed),
            "--data", os.path.join(root, "lm"),
            "--ckpt", os.path.join(root, "ckpt"),
            "--ckpt-every", str(steps), "--log-every", "1"]
    if smoke:
        argv.append("--smoke")
    t0 = time.perf_counter()
    losses = train.main(argv)
    log(f"phase B: {len(losses)} steps of {ARCH} ({layers} layers) in "
        f"{time.perf_counter() - t0:.3f} s, corpus write, compile and "
        f"checkpoint included (host wall clock, cold run)")
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"phase B losses: {losses}")
    return losses


# ---------------------------------------------------------------------------
# phase C: four loader ranks feed a step sharded over four chips
# ---------------------------------------------------------------------------


def phase_sharded_ingest(root: str, seed: int, *, chips: int = 4,
                         layers: int = LAYERS, rank_batch: int = 1,
                         seq: int = 1024, steps: int = 3,
                         smoke: bool = False) -> dict:
    cfg = configs.get_smoke(ARCH) if smoke else configs.get(ARCH)
    blocks = cfg.segments[0][0]
    cfg = cfg.scaled(compute_dtype="float32",
                     segments=((blocks, layers // len(blocks)),))
    corpus = os.path.join(root, "lm")
    os.makedirs(corpus)
    for r in range(chips):            # one shard per rank: shard striping
        write_lm_corpus(os.path.join(corpus, f"part-{r:03d}.bln"),
                        vocab=cfg.vocab, n_docs=16, doc_len=max(512, 4 * seq),
                        seed=seed + r)
    loaders = [BullionLoader(corpus, batch_size=rank_batch, seq_len=seq,
                             rank=r, world=chips) for r in range(chips)]
    its = [iter(ld) for ld in loaders]
    opt_cfg = AdamWConfig(lr=1e-3)
    rng = jax.random.PRNGKey(seed)

    def sum_sq(params):
        return float(jax.jit(lambda t: sum(jnp.sum(x * x) for x in
                                           jax.tree.leaves(t)))(params))

    try:
        first = [next(it)[0] for it in its]

        # reference: the same global batch, stepped on one device
        model = zoo.build(cfg)
        params = jax.jit(model.init)(rng)
        ref_sum = sum_sq(params)
        step = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0, 1))
        params, opt, met = step(params, adamw_init(params),
                                {"tokens": jnp.asarray(np.concatenate(first))})
        ref_loss = float(met["loss"])
        for x in jax.tree.leaves((params, opt)):
            x.delete()
        del params, opt, met, step

        mesh = make_mesh((chips, 1), ("data", "model"),
                         devices=jax.devices()[:chips])
        dist = make_dist(mesh)
        model = zoo.build(cfg, dist)
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 spec_tree(model.decl, dist.rules, mesh))
        params = jax.jit(model.init, out_shardings=shardings)(rng)
        if abs(sum_sq(params) - ref_sum) > 1e-5 * ref_sum:
            raise AssertionError("sharded init differs from the one-device "
                                 "init")
        opt = adamw_init(params)
        tok_sharding = NamedSharding(mesh, PartitionSpec("data", None))
        shape = (chips * rank_batch, seq + 1)

        def global_batch(rank_parts):
            """Each rank's slice onto the device that owns its rows."""
            bufs = [jax.device_put(rank_parts[idx[0].start // rank_batch], d)
                    for d, idx in
                    tok_sharding.devices_indices_map(shape).items()]
            return {"tokens": jax.make_array_from_single_device_arrays(
                shape, tok_sharding, bufs)}

        step = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0, 1))
        losses = []
        t0 = time.perf_counter()
        for i in range(steps):
            parts = first if i == 0 else [next(it)[0] for it in its]
            params, opt, met = step(params, opt, global_batch(parts))
            losses.append(float(met["loss"]))
            log(f"phase C: step {i + 1} loss {losses[-1]:.6f} tokens "
                f"{sum(p.size for p in parts)} from {chips} loader ranks")
        log(f"phase C: {steps} sharded steps in "
            f"{time.perf_counter() - t0:.3f} s, compile included "
            f"(host wall clock, cold run)")
    finally:
        for ld in loaders:
            ld.close()
    rel = abs(losses[0] - ref_loss) / abs(ref_loss)
    log(f"phase C: mesh {dict(mesh.shape)} over {chips} devices; first-step "
        f"loss {losses[0]:.7f} sharded vs {ref_loss:.7f} on one device, "
        f"relative difference {rel:.3e}")
    if rel > 1e-3 or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"phase C: losses {losses}, one-device "
                             f"{ref_loss}")
    return {"losses": losses, "ref_loss": ref_loss, "rel": rel}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded ingest phase on 4 chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        sys.exit(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
                 f"{len(devices)} {devices[0].platform!r} device(s)")
    log(f"device: {devices[0].device_kind}, {len(devices)} visible, "
        f"{args.chips} used; compile cache {enable_compile_cache()}")
    root = tempfile.mkdtemp(prefix="bullion-chip-smoke-")
    try:
        if args.chips == 4:
            phase_sharded_ingest(root, args.seed)
        else:
            info = phase_store_and_serve(root, args.seed)
            if not info["mosaic"]:
                raise AssertionError("the range filter did not compile to "
                                     "a Mosaic kernel on the TPU")
            phase_train(root, args.seed)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}), flush=True)


if __name__ == "__main__":
    main()
