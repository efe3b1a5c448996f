#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: run it at each of a
few fixed rates, one JAX start for all, and print what was offered, what
completed, how late the generator ran and the latency tails.

  python3 bench/sweep.py --workload <open-loop cell> --seconds 10 \\
      --rates 4,6,8,10 --seed 7

A rate is sustained when the completed rate equals the offered one and the
generator's lateness in the last quarter of the window is no higher than
in the first. A cell below capacity then fixes its rate in its mix at
about four fifths of the highest sustained one and reports latency tails;
a cell above capacity fixes it well over that rate (``lookup-zipf``: 12
per second, 1.5 times the 8 found) and reports the completed rate.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = bench_run.require_tpu(1)
    for rate in [float(r) for r in args.rates.split(",")]:
        ns = argparse.Namespace(workload=args.workload, seed=args.seed,
                                seconds=args.seconds, trace=0)
        res = bench_run.run_cell(ns, devices=devices,
                                 start=time.perf_counter(),
                                 rates={"rate_per_s": rate})
        print(json.dumps({"rate_per_s": rate, "correct": res["correct"],
                          "load": res["load"], "metrics": res["metrics"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
