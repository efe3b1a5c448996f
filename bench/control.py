#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's runs and the
control's, many seeds in one process (one JAX start).

  python3 bench/control.py --workload <name> --seconds 5 \\
      --seeds 11,12,13 --control-seeds 21,22,23

A program run is a normal run of the cell. A control run is the same run
with the configuration's BF16 columns stored as FP8 (E4M3), the program's
own lower-precision path, while the reference stays at BF16: it has to
come out not correct. One JSON line per run on stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_BENCH = os.path.dirname(os.path.abspath(__file__))
if os.path.abspath(sys.path[0]) == _BENCH:
    sys.path[0] = os.path.dirname(_BENCH)   # import bench's modules as bench.*

from bench import run as bench_run  # noqa: E402
from bench import generate, spec  # noqa: E402


def control_quant(cfg: dict) -> dict:
    """Every BF16 column stored one precision lower."""
    return {c["name"]: "fp8_e4m3" for c in cfg["columns"]
            if c.get("quant") == "bf16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    devices = bench_run.require_tpu(1)
    cell = spec.cell(spec.load(), args.workload)
    cfg = generate.load_config(spec.config_file(cell["config"]))
    runs = [(int(s), None) for s in args.seeds.split(",") if s] + \
        [(int(s), control_quant(cfg)) for s in args.control_seeds.split(",")
         if s]
    for seed, quant in runs:
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0)
        res = bench_run.run_cell(ns, devices=devices,
                                 start=time.perf_counter(), quant=quant)
        print(json.dumps({"seed": seed,
                          "kind": "control" if quant else "program",
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "metrics": res["metrics"], "load": res["load"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
