"""The load generator: one process, one thread per connection, no JAX.

The run process starts it with ``multiprocessing``'s spawn method and talks
to it over a pipe: it sends the request plan, the generator opens its
connections and answers "ready", then it is told the start instant ``t0``
on the shared monotonic clock (``time.perf_counter``). Each request goes
out through its op's ``send`` (``bench/ops/<op>.py``). It sends back one
record per request: spec index, due, sent and done instants, whether the
request succeeded, the rows served, the server's ``wall_seconds``, the
answer's digest and any error.

A closed loop sends until ``t0 + seconds`` and lets what is in flight
finish. An open loop sends request ``i`` at ``t0 + due[i]`` on the first
free connection; one that finds none goes late, and its latency still runs
from its due time.
"""

from __future__ import annotations

import threading
import time
import traceback

from bench import spec as bench_spec

FIELDS = ("qid", "due", "sent", "done", "ok", "rows", "wall", "digest",
          "error")


def _send(client, ops: dict, spec: dict, qid: int, due: float) -> tuple:
    sent = time.perf_counter()
    try:
        rows, wall, dig = ops[spec["op"]].send(client, spec)
        return (qid, due, sent, time.perf_counter(), True, rows, wall, dig,
                None)
    except Exception as e:            # a failed request is a record too
        return (qid, due, sent, time.perf_counter(), False, 0, 0.0, None,
                f"{type(e).__name__}: {e}")


def _closed(clients, ops: dict, plan: dict, t0: float,
            seconds: float) -> list:
    out: list = []
    lock = threading.Lock()

    def loop(c: int) -> None:
        seq = plan["sequences"][c]
        mine, j = [], 0
        while True:
            now = time.perf_counter()
            if now >= t0 + seconds:
                break
            qid = seq[j % len(seq)]
            mine.append(_send(clients[c], ops, plan["specs"][qid], qid, now))
            j += 1
        with lock:
            out.extend(mine)

    _run_threads(loop, len(clients))
    return out


def _open(clients, ops: dict, plan: dict, t0: float) -> list:
    due = plan["due"]
    out: list = []
    lock = threading.Lock()
    nxt = [0]

    def loop(c: int) -> None:
        mine = []
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            if i >= len(due):
                break
            at = t0 + due[i]
            wait = at - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            mine.append(_send(clients[c], ops, plan["specs"][i], i, at))
        with lock:
            out.extend(mine)

    _run_threads(loop, len(clients))
    return out


def _run_threads(fn, n: int) -> None:
    threads = [threading.Thread(target=fn, args=(c,), daemon=True)
               for c in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def main(conn, src_path: str) -> None:
    """Process entry (spawned): ``conn`` is the pipe to the run process."""
    import sys
    if src_path not in sys.path:
        sys.path.insert(0, src_path)
    from repro.serve.client import ServeClient
    clients = []
    try:
        job = conn.recv()
        if job is None:
            return
        plan = job["plan"]
        ops = {s["op"]: bench_spec.plugin("ops", s["op"], job["bench_dir"])
               for s in plan["specs"] if s is not None}
        clients = [ServeClient(job["socket"], timeout=job["timeout"])
                   for _ in range(plan["connections"])]
        conn.send("ready")
        t0 = conn.recv()
        if t0 is None:
            return
        if plan["loop"] == "closed":
            recs = _closed(clients, ops, plan, t0, job["seconds"])
        else:
            recs = _open(clients, ops, plan, t0)
        recs.sort(key=lambda r: (r[1], r[0]))
        conn.send(recs)
    except Exception:
        conn.send({"error": traceback.format_exc()})
    finally:
        for c in clients:
            c.close()
        conn.close()
