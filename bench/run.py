#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this machine holds.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix (``BENCHMARK.json``).
Set-up: the configuration's table is generated from ``--seed`` and written
with ``BullionWriter`` by one process per shard into a temporary directory;
meanwhile JAX starts and must find a TPU, else the run exits non-zero
and prints no result. The table is attached to a ``DatasetServer`` at the
program's defaults and served over AF_UNIX; the first request of each of
the mix's templates is served once in-process, which compiles (or loads
from the persistent cache) every kernel shape the window uses. The load
generator, a spawned process without JAX, opens its connections, and the
window starts.

``--trace 0`` reports the cell's end-to-end metrics. ``--trace 1`` also
installs the program's process-wide span tracer, profiles a few seconds of
the window with ``jax.profiler``, and reports the per-layer metrics, the
device's busy time and a breakdown. After the window the peak device
memory is read, the server is closed, and every answer is compared with
the plain NumPy reference (``bench/query.py``) over the same generated
table; the run is correct when no answer is wrong, missing or failed.

The last line of stdout is the result as JSON; the comparisons, each with
its limit, are the last lines of stderr and the result's last key.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SRC = os.path.join(ROOT, "src")
if sys.path and os.path.abspath(sys.path[0]) == BENCH_DIR:
    sys.path.pop(0)          # bench's modules are imported as ``bench.*``
for _p in (SRC, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import generate, loadgen, query, spec, stats, traffic  # noqa: E402

SERVER = {"max_workers": 4, "default_io_depth": 2}   # the program's defaults
CLIENT_TIMEOUT_S = 120.0     # an answer may come a minute past the close
TRACE_MAX_S = 5.0            # profiled stretch of the window
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(SystemExit):
    pass


def require_tpu(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"bench: the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform!r} device(s)")
    return devices


def watch_compiles() -> list:
    """Instants of every trace, compile or cache load JAX reports."""
    import jax
    seen: list = []

    def on_event(event: str, _secs: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            seen.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return seen


def enable_compile_cache() -> str:
    """The program's persistent cache; every program is kept, however
    quickly it compiled, so a second run compiles nothing."""
    import jax
    from repro.launch.cache import enable_compile_cache as program_cache
    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def write_table(pool, cfg: dict, seed: int, work: str,
                bench_dir: str) -> list:
    table_dir = os.path.join(work, "table")
    os.makedirs(table_dir)
    return [pool.apply_async(generate.write_shard, (
        cfg, seed, s, os.path.join(table_dir, f"part-{s:03d}.bln"),
        bench_dir)) for s in range(int(cfg["shards"]))]


def ops_of(plan: dict, bench_dir: str) -> dict:
    return {s["op"]: spec.plugin("ops", s["op"], bench_dir)
            for s in plan["specs"] if s is not None}


def warm_up(server, plan: dict, ops: dict) -> None:
    """Serve the first request of each template once in-process. A
    template fixes its columns and its filter's columns, so the kernel
    shapes of all its requests are one: this compiles (or loads) every
    program the window runs, and plans no other request."""
    seen = set()
    for s in plan["specs"]:
        if s["template"] not in seen:
            seen.add(s["template"])
            ops[s["op"]].warm(server, s)


def profile_window(t0: float, seconds: float, out_dir: str) -> tuple:
    """Profile the middle of the window; returns (anchor perf_counter, the
    profiled stretch's start and end on the host clock)."""
    import jax
    start = t0 + 0.25 * seconds
    length = min(TRACE_MAX_S, 0.5 * seconds)
    wait = start - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.anchor"):
        anchor = time.perf_counter()
    lo = anchor
    time.sleep(max(0.0, anchor + length - time.perf_counter()))
    hi = time.perf_counter()
    jax.profiler.stop_trace()
    return anchor, lo, hi


def span_dicts(tracer) -> list[dict]:
    from repro.obs import trace as program_trace
    base = program_trace._EPOCH
    return [{"name": s.name, "start": base + s.ts, "end": base + s.ts + s.dur,
             "tid": s.tid, "args": s.args} for s in tracer.spans]


def reduce_trace(run, trace_dir: str, anchor: float, lo: float, hi: float):
    """Device numbers of the profiled stretch, and the breakdown."""
    from bench import devtrace as tr
    t = tr.extract(trace_dir)
    off = tr.anchor_ns(t) - anchor * 1e9        # perf_counter s -> trace ns
    lo_ns, hi_ns = lo * 1e9 + off, hi * 1e9 + off
    run.trace = t
    run.trace_window_ns = (lo_ns, hi_ns)
    busy_iv = tr.busy(t, lo_ns, hi_ns)
    run.busy_s = tr.busy_seconds(t, lo_ns, hi_ns)
    run.window_s = hi - lo
    host = [(s["name"], s["start"] * 1e9 + off, s["end"] * 1e9 + off)
            for s in run.spans if s["end"] * 1e9 + off > lo_ns
            and s["start"] * 1e9 + off < hi_ns]
    gaps = tr.idle_gaps(busy_iv[0], lo_ns, hi_ns) if busy_iv else \
        [(lo_ns, hi_ns)]
    # an op's name is its whole HLO line; its head names it
    return {"device_ops": [[n[:120], v] for n, v in tr.top_ops(t, lo_ns,
                                                             hi_ns)],
            "idle_gaps": tr.gaps_by_host_span(gaps, host)}


def check_answers(run) -> dict:
    """Every answer against the reference: wrong, missing and failed
    counts, each with its limit of 0."""
    cfg, plan, ops = run.cfg, run.plan, run.ops
    used = sorted({c for s in plan["specs"] if s is not None
                   for c in ops[s["op"]].columns(s)})
    table = query.stored_table(cfg, generate.generate_table(
        cfg, run.seed, [c["name"] for c in cfg["columns"]
                        if c["name"] in used], run.bench_dir))
    cache: dict = {}
    want: dict = {}
    wrong = failed = 0
    for r in run.records:
        if not r["ok"]:
            failed += 1
            continue
        s = plan["specs"][r["qid"]]
        key = json.dumps(s, sort_keys=True)
        if key not in want:
            want[key] = ops[s["op"]].expected(s, table, cache)
        wrong += r["digest"] != want[key]
    expected = len(plan["due"]) if plan["loop"] == "open" else \
        len(run.records)
    return {"wrong_answers": {"value": wrong, "limit": 0},
            "missing_answers": {"value": expected - len(run.records),
                                "limit": 0},
            "failed_requests": {"value": failed, "limit": 0}}


def read_metrics(entries: list, run, bench_dir: str) -> dict:
    out = {}
    for m in entries:
        value = spec.reader(m["name"], bench_dir)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(args, *, devices=None, sizes=None, rates=None, quant=None,
             root=ROOT, bm=None, start=T_START) -> dict:
    """One run of a cell; returns the result. ``devices`` skips the look
    for a TPU; ``sizes`` and ``rates`` override the configuration's and
    the mix's numbers, ``quant`` a column's storage type in the store but
    not in the reference (tests, controls and the rate sweep); ``root`` is
    the checkout whose ``BENCHMARK.json`` (or ``bm`` in its place) and
    ``bench/`` files define the cell; ``start`` is the instant set-up is
    timed from."""
    bench_dir = os.path.join(root, "bench")
    bm = bm or spec.load(root)
    cell = spec.cell(bm, args.workload)
    cfg = {**generate.load_config(spec.config_file(cell["config"],
                                                   bench_dir)),
           **(sizes or {})}
    # the store may hold a column in another type; the reference keeps
    # the one the configuration states
    stored_cfg = {**cfg, "columns": [
        {**c, "quant": (quant or {}).get(c["name"], c.get("quant"))}
        for c in cfg["columns"]]}
    mix = {**traffic.load_mix(spec.traffic_file(cell["traffic"],
                                                bench_dir)),
           **(rates or {})}
    run = types.SimpleNamespace(
        cell=cell, cfg=cfg, mix=mix, seed=args.seed, seconds=args.seconds,
        rows_total=generate.num_rows(cfg), bench_dir=bench_dir, spans=[],
        trace=None)
    from repro.serve import DatasetServer

    ctx = multiprocessing.get_context("spawn")
    work = tempfile.mkdtemp(prefix="bullion-bench-")
    workers = max(1, min(int(cfg["shards"]), (os.cpu_count() or 2) - 1))
    pool = ctx.Pool(workers)
    pipe, child_end = ctx.Pipe()
    gen = ctx.Process(target=loadgen.main, args=(child_end, SRC),
                      name="bench-loadgen")
    server = None
    done = False
    try:
        shards = write_table(pool, stored_cfg, args.seed, work, bench_dir)
        gen.start()
        child_end.close()
        if devices is None:
            devices = require_tpu(int(cell["chips"]))
        compiles = watch_compiles()
        cache_dir = enable_compile_cache()
        run.device_kind = devices[0].device_kind
        t = time.perf_counter()
        on_disk = sum(f.get() for f in shards)
        pool.close()
        log(f"bench: {cell['name']}: {devices[0].device_kind}; compile cache "
            f"{cache_dir}; table {generate.num_rows(cfg):,} rows, "
            f"{on_disk:,} bytes, written {time.perf_counter() - start:.3f}"
            f" s after start ({time.perf_counter() - t:.3f} s waited)")

        keys = {c: generate.generate_table(cfg, args.seed, [c], bench_dir)[c]
                for c in traffic.key_columns(mix)}
        run.plan = plan = traffic.plan(mix, args.seed, args.seconds,
                                       keys.__getitem__, bench_dir)
        del keys
        run.ops = ops_of(plan, bench_dir)
        server = DatasetServer({"table": os.path.join(work, "table")},
                               **SERVER)
        sock = server.serve(os.path.join(work, "serve.sock"))
        warm_up(server, plan, run.ops)
        pipe.send({"socket": sock, "plan": plan, "seconds": args.seconds,
                   "timeout": CLIENT_TIMEOUT_S, "bench_dir": bench_dir})
        if pipe.recv() != "ready":
            raise RuntimeError("load generator failed to connect")

        tracer = None
        if args.trace:
            from repro.obs import trace as program_trace
            tracer = program_trace.enable(max_spans=2_000_000)
        io_before = server.stats()["datasets"]["table"]["io"]
        t0 = time.perf_counter() + 0.005
        run.setup_s = t0 - start
        pipe.send(t0)
        prof = None
        if args.trace:
            prof_dir = os.path.join(work, "profile")
            prof = profile_window(t0, args.seconds, prof_dir)
        if not pipe.poll(args.seconds + 2 * CLIENT_TIMEOUT_S):
            raise RuntimeError("load generator sent no records")
        recs = pipe.recv()
        if isinstance(recs, dict):
            raise RuntimeError(f"load generator failed:\n{recs['error']}")
        run.records = [dict(zip(loadgen.FIELDS, r)) for r in recs]
        t_end = max([t0] + [r["done"] for r in run.records])
        run.t0, run.t_end = t0, t_end
        run.io = {k: v - io_before[k] for k, v in
                  server.stats()["datasets"]["table"]["io"].items()}
        if tracer is not None:
            from repro.obs import trace as program_trace
            program_trace.disable()
            run.spans = span_dicts(tracer)
            run.dropped_spans = tracer.dropped
        in_window = sum(1 for c in compiles if t0 <= c <= t_end)
        log(f"bench: {len(run.records)} requests in {t_end - t0:.3f} s; "
            f"compilations inside the window: {in_window}; set-up "
            f"{run.setup_s:.3f} s")
        stats_dev = devices[0].memory_stats() or {}
        peak = int(stats_dev.get("peak_bytes_in_use", 0))
        breakdown = None
        if prof is not None:
            breakdown = reduce_trace(run, prof_dir, *prof)
        server.close()
        server = None

        entries = spec.per_layer(bm, cell["name"]) if args.trace \
            else spec.end_to_end(bm, cell["name"])
        metrics = read_metrics(entries, run, bench_dir)
        checks = check_answers(run)
        done = True
    finally:
        if server is not None:
            server.close()
        if gen.is_alive():
            try:
                pipe.send(None)
            except OSError:
                pass
            gen.join(timeout=30)
            if gen.is_alive():
                gen.terminate()
                gen.join()
        if not done:
            pool.terminate()
        pool.join()
        shutil.rmtree(work, ignore_errors=True)

    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": int(cell["chips"]), "memory_peak_bytes": peak}
    if args.trace:
        device["busy_s"] = run.busy_s
        device["window_s"] = run.window_s
    result = {"correct": correct, "attempted": len(run.records),
              "failed": checks["failed_requests"]["value"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["load"] = load_report(run)
    result["checks"] = checks
    return result


def load_report(run) -> dict:
    """How much load was offered and served, and how late the generator
    sent it (ms after each request's due time)."""
    recs = run.records
    span = max(run.t_end - run.t0, 1e-9)
    late = [1e3 * (r["sent"] - r["due"]) for r in recs] or [0.0]
    quarter = max(1, len(recs) // 4)
    return {"requests": len(recs),
            "offered_per_s": (len(run.plan["due"]) / run.seconds
                              if run.plan["loop"] == "open" else None),
            "completed_per_s": sum(r["ok"] for r in recs) / span,
            "late_ms_p50": stats.percentile(late, 50),
            "late_ms_p95": stats.percentile(late, 95),
            "late_ms_max": max(late),
            "late_ms_p50_first_quarter": stats.percentile(late[:quarter], 50),
            "late_ms_p50_last_quarter": stats.percentile(late[-quarter:], 50)}


def main(argv=None) -> int:
    result = run_cell(parse(argv))
    log("bench: load " + json.dumps(result["load"]))
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except NoChip as e:
        print(e, file=sys.stderr)
        sys.exit(3)
