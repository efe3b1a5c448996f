"""Tables from a configuration file and a seed.

A configuration (``bench/configs/<name>.json``) lists its columns, each
with a storage dtype, an optional storage quantization and a generator
``"gen": {"kind": k, ...}``, found as ``bench/gen/<k>.py``. Its
``column(ctx, g)`` makes one shard's column from ``g``, the generator's
parameters, and ``ctx``: ``n`` rows, the shard's ``first`` global row, the
run's ``seed``, the column's ``index``, its NumPy ``dtype`` (None for a
string column, which is a list of bytes) and ``rng``, the column's own
stream ``SeedSequence([seed, shard, column index])``, so one column can be
made again without the others, in any process, bit for bit.

Quantized columns (``"quant": "bf16"``) are generated in float32; the store
rounds them to bfloat16 on write and serves them back as float32.
"""

from __future__ import annotations

import json
import os
import types
from typing import Sequence

import numpy as np

from bench import spec

ALNUM = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz0123456789", np.uint8)
LOWER = ALNUM[:26]


def load_config(path: str) -> dict:
    with open(path) as f:
        cfg = json.load(f)
    for key in ("name", "shards", "rows_per_shard", "rows_per_group",
                "columns"):
        if key not in cfg:
            raise ValueError(f"{path}: configuration lacks {key!r}")
    return cfg


def num_rows(cfg: dict) -> int:
    return int(cfg["shards"]) * int(cfg["rows_per_shard"])


def column_rng(seed: int, shard: int, col: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([seed % (1 << 64), shard, col]))


def zipf_ranks(rng, n: int, size: int, s: float) -> np.ndarray:
    """Ranks 0..n-1 drawn with P(rank k) about proportional to
    1 / (k + 1)**s: the inverse of the continuous Zipf's distribution
    function over [1, n + 1), so a draw costs the same for any ``n``."""
    u = rng.random(size)
    if abs(s - 1.0) < 1e-9:
        x = np.exp(u * np.log(n + 1.0))
    else:
        x = ((np.power(n + 1.0, 1 - s) - 1) * u + 1) ** (1 / (1 - s))
    return np.clip(x.astype(np.int64) - 1, 0, n - 1)


def join_pieces(pool: np.ndarray, starts: np.ndarray, lens: np.ndarray,
                row_pieces: np.ndarray) -> list[bytes]:
    """Rows made of consecutive pieces, each a slice ``pool[start:start +
    len]``; ``row_pieces[i]`` pieces belong to row ``i``. One gather."""
    lens = lens.astype(np.int64)
    dst = np.concatenate([[0], np.cumsum(lens)])
    total = int(dst[-1])
    src = np.repeat(starts.astype(np.int64) - dst[:-1], lens) \
        + np.arange(total, dtype=np.int64)
    buf = pool[src].tobytes()
    piece_end = np.concatenate([[0], np.cumsum(row_pieces)]).astype(np.int64)
    bounds = dst[piece_end].tolist()
    return [buf[bounds[i]:bounds[i + 1]] for i in range(len(row_pieces))]


def word_pool(rng, vocab: int, max_word: int):
    """A vocabulary as one byte pool: word k at starts[k], lens[k] long."""
    lens = rng.integers(2, max_word + 1, vocab)
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    pool = rng.choice(LOWER, int(lens.sum()))
    return pool, starts, lens


def generate_column(cfg: dict, col: int, seed: int, shard: int,
                    bench_dir: str = spec.BENCH_DIR):
    """Column ``col`` of shard ``shard``: a NumPy array, or a list of
    bytes for a string column."""
    c = cfg["columns"][col]
    n = int(cfg["rows_per_shard"])
    ctx = types.SimpleNamespace(
        n=n, first=shard * n, seed=seed, index=col,
        dtype=np.dtype(c["dtype"]) if c["dtype"] != "string" else None,
        rng=column_rng(seed, shard, col))
    return spec.plugin("gen", c["gen"]["kind"], bench_dir).column(
        ctx, c["gen"])


def generate_shard(cfg: dict, seed: int, shard: int,
                   columns: Sequence[str] | None = None,
                   bench_dir: str = spec.BENCH_DIR) -> dict:
    names = [c["name"] for c in cfg["columns"]]
    want = names if columns is None else list(columns)
    return {name: generate_column(cfg, names.index(name), seed, shard,
                                  bench_dir)
            for name in want}


def generate_table(cfg: dict, seed: int,
                   columns: Sequence[str] | None = None,
                   bench_dir: str = spec.BENCH_DIR) -> dict:
    """Every shard's columns, concatenated in table order."""
    parts = [generate_shard(cfg, seed, s, columns, bench_dir)
             for s in range(int(cfg["shards"]))]
    out = {}
    for name in parts[0]:
        cols = [p[name] for p in parts]
        out[name] = np.concatenate(cols) if isinstance(cols[0], np.ndarray) \
            else [r for c in cols for r in c]
    return out


def schema(cfg: dict) -> list:
    """The configuration's columns as the store's ``ColumnSpec`` list."""
    from repro.core import ColumnSpec, QuantMode, QuantSpec
    modes = {"bf16": QuantMode.BF16, "fp8_e4m3": QuantMode.FP8_E4M3}
    out = []
    for c in cfg["columns"]:
        q = c.get("quant")
        out.append(ColumnSpec(c["name"], c["dtype"],
                              quant=QuantSpec(modes[q]) if q else QuantSpec()))
    return out


def write_shard(cfg: dict, seed: int, shard: int, path: str,
                bench_dir: str = spec.BENCH_DIR) -> int:
    """Generate shard ``shard`` and write it with ``BullionWriter``;
    returns the bytes on disk."""
    from repro.core import BullionWriter
    w = BullionWriter(path, schema(cfg),
                      rows_per_group=int(cfg["rows_per_group"]))
    w.write_table(generate_shard(cfg, seed, shard, bench_dir=bench_dir))
    w.close()
    return os.path.getsize(path)
