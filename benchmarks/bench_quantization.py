"""§2.4 reproduction: storage quantization. Bytes on disk for FP32 vs
BF16/FP8/INT8 columns (through the full page-encode path), worst-case error,
dual-FP16 reconstruction, and device-side fused dequant throughput (Pallas
kernel; interpreted off a TPU)."""

from __future__ import annotations

import time

import numpy as np

from repro.core import (EncodeContext, QuantMode, QuantSpec, affine_spec_for,
                        dequantize, quantize, rejoin_dual_fp16, suggest_spec)
from repro.core.encodings import encode_array


def run(report):
    rng = np.random.default_rng(0)
    emb = np.tanh(rng.normal(size=65536).astype(np.float32))  # (-1,1) embeddings
    ctx = EncodeContext()
    base = len(encode_array(emb, ctx))

    for mode in (QuantMode.BF16, QuantMode.FP16, QuantMode.FP8_E4M3,
                 QuantMode.INT8_AFFINE):
        spec = affine_spec_for(emb, mode) if "AFFINE" in mode.name \
            else QuantSpec(mode)
        q = quantize(emb, spec)
        blob = len(encode_array(q, ctx))
        err = float(np.abs(dequantize(q, spec) - emb).max())
        report(f"quant/bytes_ratio/{mode.name}", base / blob,
               f"{base / blob:.2f}x smaller, max_err={err:.2e}")

    # dual-FP16 decomposition (the paper's FP32 mitigation)
    hi = quantize(emb, QuantSpec(QuantMode.DUAL_FP16_HI))
    lo = quantize(emb, QuantSpec(QuantMode.DUAL_FP16_LO))
    err = float(np.abs(rejoin_dual_fp16(hi, lo) - emb).max())
    report("quant/dual_fp16_max_err", err, f"max_err={err:.2e} (2 cols, 1:1 join)")

    # per-feature mixed precision policy
    spec = suggest_spec(emb, rel_tolerance=5e-3)
    report("quant/suggested_mode", float(int(spec.mode)),
           f"policy picked {spec.mode.name} at tol=5e-3")

    # fused dequant kernel throughput (a structural check off a TPU, where
    # the kernel runs in the Pallas interpreter)
    from repro.kernels import interpret
    from repro.kernels.dequant import dequant
    q8 = quantize(emb, affine_spec_for(emb, QuantMode.INT8_AFFINE))
    qm = np.tile(q8.reshape(256, 256), (2, 1))
    spec8 = affine_spec_for(emb, QuantMode.INT8_AFFINE)
    t0 = time.perf_counter()
    out = dequant(qm, np.full(256, spec8.scale, np.float32),
                  np.full(256, spec8.zero, np.float32))
    out.block_until_ready()
    dt = time.perf_counter() - t0
    report("quant/dequant_kernel_MBps", qm.nbytes / dt / 1e6,
           f"{qm.nbytes / dt / 1e6:.1f} MB/s "
           f"({'interpret mode' if interpret() else 'compiled'})")
