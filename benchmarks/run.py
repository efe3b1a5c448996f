# One function per paper table/figure. Prints
# ``name,us_per_call,<STAT_COLUMNS...>,derived`` CSV; ``pruned_bytes`` is
# the plan-proven avoided I/O (IOStats.bytes_pruned) and ``pages_pruned``
# the page reads those proofs skipped (IOStats.pages_pruned — group- plus
# page-granular zone maps), so pruning regressions at either granularity
# show up in the perf trajectory. ``preads``/``bytes_read`` track the I/O a
# probe actually issued, ``coalesced_preads``/``wasted_bytes`` the pipelined
# scheduler's batching win and its hole-read cost, and
# ``footer_cache_hits`` the shard opens served without a metadata pread;
# all blank for suites where they don't apply. ``STAT_FIELDS`` maps each
# stat column to the ``IOStats`` field it mirrors (regression-tested, so
# the CSV schema can't silently drift from the accounting).
#
# ``--only scan,compact`` restricts to matching suites (substring match on
# the label or module name — select the I/O suite with ``--only bench_io``;
# the bare key "io" also matches deletion/quantization/projection);
# ``--trace out.json`` wraps each suite in a span and writes one merged
# Chrome trace_event JSON (open in Perfetto / chrome://tracing) covering
# every instrumented stage the suites exercised;
# ``BULLION_BENCH_SMOKE=1`` makes the suites that honor it (scan, compact,
# bench_io, bench_serve) shrink their datasets — the CI smoke mode that
# keeps the perf-trajectory CSV accumulating on every push.
from __future__ import annotations

import argparse
import sys
import time
import traceback

# CSV stat column -> the IOStats field it reports (order = column order
# between ``us_per_call`` and ``derived``)
STAT_FIELDS = {
    "pruned_bytes": "bytes_pruned",
    "pages_pruned": "pages_pruned",
    "groups_pruned_sketch": "groups_pruned_sketch",
    "preads": "preads",
    "bytes_read": "bytes_read",
    "footer_cache_hits": "footer_cache_hits",
    "coalesced_preads": "coalesced_preads",
    "wasted_bytes": "wasted_bytes",
    "backend_fetches": "backend_fetches",
    "backend_retries": "backend_retries",
    "backend_wasted_bytes": "backend_wasted_bytes",
    "pages_verified": "pages_verified",
    "checksum_failures": "checksum_failures",
    "pages_quarantined": "pages_quarantined",
    "degraded_rows": "degraded_rows",
}
STAT_COLUMNS = tuple(STAT_FIELDS)


def main(argv=None) -> None:
    from . import (bench_cascade, bench_chaos, bench_compact, bench_deletion,
                   bench_io, bench_metadata, bench_multimodal,
                   bench_projection, bench_quantization, bench_roofline,
                   bench_scan, bench_serve, bench_sparse_delta)

    ap = argparse.ArgumentParser(description="Bullion benchmark suites")
    ap.add_argument("--only", default=None,
                    help="comma-separated substrings; run only suites whose "
                         "label or module matches (e.g. --only scan,compact)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record spans across all suites and write one "
                         "merged Chrome trace_event JSON (Perfetto) to PATH")
    ap.add_argument("--baseline", default=None, metavar="OUT.json",
                    help="also write every probe's timing + stats as a "
                         "machine-readable baseline JSON")
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="diff this run against a recorded baseline; "
                         "warn-only (CI trend signal, not a gate)")
    ap.add_argument("--tolerance", type=float, default=35.0,
                    help="--compare flags probes whose us_per_call moved "
                         "more than this many percent (default 35)")
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    results: dict[str, dict] = {}

    def report(name: str, value: float, derived: str = "", **stats) -> None:
        bad = set(stats) - set(STAT_COLUMNS)
        if bad:
            raise TypeError(f"unknown stat column(s) {sorted(bad)}; "
                            f"expected one of {list(STAT_COLUMNS)}")
        cells = ",".join("" if stats.get(c) is None else str(int(stats[c]))
                         for c in STAT_COLUMNS)
        results[name] = {
            "us_per_call": value,
            "stats": {c: int(stats[c]) for c in STAT_COLUMNS
                      if stats.get(c) is not None},
            "derived": derived,
        }
        print(f"{name},{value:.6g},{cells},{derived}", flush=True)

    print("name,us_per_call," + ",".join(STAT_COLUMNS) + ",derived")
    suites = [
        ("metadata  (Fig. 5)", bench_metadata),
        ("deletion  (§2.1)", bench_deletion),
        ("sparse_delta (§2.2, Figs. 3-4)", bench_sparse_delta),
        ("quantization (§2.4, Fig. 6)", bench_quantization),
        ("multimodal (§2.5, Fig. 7)", bench_multimodal),
        ("cascade   (§2.6, Table 2)", bench_cascade),
        ("projection (§2.3, Table 1)", bench_projection),
        ("scan      (zone maps / pushdown)", bench_scan),
        ("compact   (write_to sink / recluster)", bench_compact),
        ("io        (pipelined scheduler / footer cache)", bench_io),
        ("chaos     (self-healing read path)", bench_chaos),
        ("serve     (dataset service / bloom probes)", bench_serve),
        ("roofline  (dry-run artifacts)", bench_roofline),
    ]
    if args.only:
        keys = [k.strip() for k in args.only.split(",") if k.strip()]
        suites = [(label, mod) for label, mod in suites
                  if any(k in label or k in mod.__name__ for k in keys)]
        if not suites:
            sys.exit(f"--only {args.only!r} matched no suites")
    scope = tracer = None
    if args.trace:
        from repro.obs import trace as _trace
        # a forwarding scope, not enable(): a concurrent BULLION_TRACE
        # recording keeps seeing every span
        scope = _trace.collect()
        tracer = scope.__enter__()
    failures = 0
    for label, mod in suites:
        t0 = time.time()
        try:
            if tracer is not None:
                with tracer.span(f"bench.{mod.__name__.rsplit('.', 1)[-1]}",
                                 "bench"):
                    mod.run(report)
            else:
                mod.run(report)
            print(f"# {label}: done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {label}: FAILED\n{traceback.format_exc()}", flush=True)
    if scope is not None:
        from repro.obs.export import write_trace
        scope.__exit__(None, None, None)
        write_trace(args.trace, tracer.spans, dropped=tracer.dropped)
        print(f"# trace: {args.trace} ({len(tracer.spans)} span(s), "
              f"{tracer.dropped} dropped)", flush=True)
    if args.baseline:
        _write_baseline(args.baseline, results)
    if args.compare:
        _compare_baseline(args.compare, results, args.tolerance)
    if failures:
        sys.exit(1)


def _write_baseline(path: str, results: dict) -> None:
    import json
    import os
    payload = {
        "schema": 1,
        "smoke": bool(os.environ.get("BULLION_BENCH_SMOKE")),
        "stat_columns": list(STAT_COLUMNS),
        "results": results,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# baseline: {path} ({len(results)} probe(s))", flush=True)


def _compare_baseline(path: str, results: dict, tolerance: float) -> None:
    """Warn-only diff against a recorded baseline. Timings on shared CI
    runners are noisy, so regressions print as ``# compare:`` commentary
    for the perf-trajectory log rather than failing the run; the exact
    I/O counters (preads, bytes, pruning) are the stable signal and get
    flagged on ANY drift."""
    import json
    with open(path) as f:
        base = json.load(f)
    old = base.get("results", {})
    flagged = 0
    for name, rec in sorted(results.items()):
        prev = old.get(name)
        if prev is None:
            print(f"# compare: {name}: new probe (no baseline)", flush=True)
            continue
        was, now = prev["us_per_call"], rec["us_per_call"]
        if was > 0:
            delta = (now - was) / was * 100.0
            if abs(delta) > tolerance:
                flagged += 1
                print(f"# compare: {name}: us_per_call {was:.6g} -> "
                      f"{now:.6g} ({delta:+.1f}%, tolerance "
                      f"{tolerance:g}%)", flush=True)
        for col, v in rec["stats"].items():
            pv = prev.get("stats", {}).get(col)
            if pv is not None and pv != v:
                flagged += 1
                print(f"# compare: {name}: {col} {pv} -> {v}", flush=True)
    gone = sorted(set(old) - set(results))
    for name in gone:
        print(f"# compare: {name}: probe missing from this run", flush=True)
    print(f"# compare: {len(results)} probe(s) vs {path}: "
          f"{flagged} drift(s), {len(gone)} missing", flush=True)


if __name__ == "__main__":
    main()
