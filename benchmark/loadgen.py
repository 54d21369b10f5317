"""The load generator: a child process that never imports JAX.

It makes the cell's requests from the seed (``benchmark.traffic``), posts
them to ``POST /parse`` on localhost and times each from when it was due:
in a closed loop a request is due when its client's previous answer
came back, in an open loop at its place in the arrival schedule. Every
request body of the run is made at set-up, by a pool of worker
processes; a closed loop takes its window requests from a pool of the
size its mix gives, and one that uses the pool up stops sending. It
speaks one JSON line per message with the harness (``run.py``) over its
stdin and stdout:

1. ``{"cell": ..., "seed": ..., "seconds": ...}`` → makes every request
   body, answers ``{"ready": ...}``;
2. ``{"port": ...}`` → posts the warm-up requests one at a time, answers
   ``{"warm": ...}``;
3. ``{"run": true}`` → runs the window; answers ``{"closed": ...}`` when
   it closes, then, once every request sent has come back (or a minute
   after the close has passed), ``{"records": ...}``: one record per
   request with its times, status and served events.
"""

from __future__ import annotations

import gc
import http.client
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import context_digest  # noqa: E402
from benchmark.traffic import WARMUP, WINDOW, Traffic, make_bodies, request_id  # noqa: E402

# a request still out this long after the close has not come back
LATE_S = 60.0
OPEN_LOOP_SENDERS = 256


def _send(msg: dict) -> None:
    sys.stdout.write(json.dumps(msg) + "\n")
    sys.stdout.flush()


def _recv() -> dict:
    line = sys.stdin.readline()
    if not line:
        sys.exit(0)
    return json.loads(line)


def body_workers() -> int:
    return max(1, min(8, (os.cpu_count() or 2) // 2))


def post(port: int, rid: str, body: bytes) -> tuple[int, bytes | None, str | None]:
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
        try:
            conn.request("POST", "/parse", body=body, headers={
                "Content-Type": "application/json", "X-Request-Id": rid,
            })
            resp = conn.getresponse()
            return resp.status, resp.read(), None
        finally:
            conn.close()
    except (OSError, http.client.HTTPException) as exc:
        return 0, None, f"{type(exc).__name__}: {exc}"


def served_events(raw: bytes | None) -> tuple[int | None, list]:
    """(metadata.totalLines, [[line, pattern id, score, context digest]])."""
    if raw is None:
        return None, []
    doc = json.loads(raw)
    out = []
    for e in doc.get("events") or []:
        ctx = e.get("context") or {}
        out.append([
            e.get("lineNumber"),
            (e.get("matchedPattern") or {}).get("id"),
            e.get("score"),
            context_digest(ctx.get("linesBefore"), ctx.get("matchedLine"),
                           ctx.get("linesAfter")),
        ])
    return (doc.get("metadata") or {}).get("totalLines"), out


class Recorder:
    """One record per request sent; one that never came back keeps
    status 0 and no ``done``."""

    def __init__(self):
        self.lock = threading.Lock()
        self.records: list[dict] = []
        self.raw: dict[str, bytes | None] = {}

    def post(self, port, rid, lines, due, body, client=None) -> None:
        sent = time.perf_counter()
        r = {"id": rid, "lines": lines, "due": due, "sent": sent,
             "done": None, "status": 0, "error": "no answer", "client": client}
        with self.lock:
            self.records.append(r)
        status, raw, error = post(port, rid, body)
        done = time.perf_counter()
        with self.lock:
            r.update(done=done, status=status, error=error)
            self.raw[rid] = raw if status == 200 else None


class Pool:
    """The window's request bodies, each handed out once: a closed loop
    takes the next, an open loop the one its schedule names."""

    def __init__(self, bodies: list[bytes]):
        self.bodies = bodies
        self.next = 0
        self.lock = threading.Lock()

    def take_next(self) -> int | None:
        with self.lock:
            if self.next >= len(self.bodies):
                return None
            self.next += 1
            return self.next - 1

    def body(self, i: int) -> bytes:
        body, self.bodies[i] = self.bodies[i], b""
        return body

    @property
    def left(self) -> int:
        return len(self.bodies) - self.next


def closed_loop(port, pool, sizes, clients, t0, t_end, rec) -> list:
    def client(c):
        due = t0
        while time.perf_counter() < t_end:
            i = pool.take_next()
            if i is None:
                return
            rec.post(port, request_id(WINDOW, i), sizes[i], due, pool.body(i), c)
            due = time.perf_counter()

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    return threads


def open_loop(port, pool, sizes, due, t0, rec):
    senders = ThreadPoolExecutor(max_workers=OPEN_LOOP_SENDERS)

    def one(i, due_abs):
        rec.post(port, request_id(WINDOW, i), sizes[i], due_abs, pool.body(i))

    futures = []

    def dispatch():
        for i, d in enumerate(due):
            due_abs = t0 + d
            delay = due_abs - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            futures.append(senders.submit(one, i, due_abs))

    th = threading.Thread(target=dispatch, daemon=True)
    th.start()
    return th, senders, futures


def main() -> int:
    setup = _recv()
    cell, seed, seconds = setup["cell"], setup["seed"], float(setup["seconds"])
    spec = cell["traffic"]
    traffic = Traffic(spec, cell["config"], seed)
    warm_sizes = traffic.warmup_sizes()
    if traffic.open_loop:
        due, sizes = traffic.open_plan(seconds)
    else:
        sizes = [traffic.size(k) for k in range(traffic.pool_size(seconds))]
    plan = [(WARMUP, k, n) for k, n in enumerate(warm_sizes)]
    plan += [(WINDOW, k, n) for k, n in enumerate(sizes)]
    bodies = make_bodies(spec, cell["config"], seed, plan, body_workers())
    warm_bodies, pool = bodies[:len(warm_sizes)], Pool(bodies[len(warm_sizes):])
    del bodies  # a body sent is then freed
    _send({"ready": len(warm_sizes)})

    port = int(_recv()["port"])
    rec = Recorder()
    for k, (n, body) in enumerate(zip(warm_sizes, warm_bodies)):
        rec.post(port, request_id(WARMUP, k), n, time.perf_counter(), body)
    del warm_bodies
    _send({"warm": len(warm_sizes),
           "failed": sum(r["status"] != 200 for r in rec.records)})

    _recv()  # run
    # no collector pause in the generator while it keeps time
    gc.collect()
    gc.disable()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    if traffic.open_loop:
        th, senders, futures = open_loop(port, pool, sizes, due, t0, rec)
        th.join()
        rest = t_end - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
        _send({"closed": time.perf_counter() - t0})
        deadline = t_end + LATE_S
        for f in futures:
            try:
                f.result(timeout=max(0.0, deadline - time.perf_counter()))
            except TimeoutError:
                break
        senders.shutdown(wait=False, cancel_futures=True)
        attempted = len(sizes)
    else:
        threads = closed_loop(port, pool, sizes, int(spec["loop"]["clients"]),
                              t0, t_end, rec)
        time.sleep(max(0.0, t_end - time.perf_counter()))
        _send({"closed": time.perf_counter() - t0})
        for t in threads:
            t.join(timeout=max(0.0, t_end + LATE_S - time.perf_counter()))
        attempted = None

    gc.enable()
    with rec.lock:
        records = list(rec.records)
        raw = dict(rec.raw)
    for r in records:
        r["total_lines"], r["events"] = served_events(raw.get(r["id"]))
        for key in ("due", "sent", "done"):
            if r[key] is not None:
                r[key] -= t0
        r["warmup"] = r["id"].startswith("w")
    _send({"records": records, "attempted": attempted,
           "pool_left": None if traffic.open_loop else pool.left})
    # senders still waiting on an answer past the minute are abandoned
    os._exit(0)


if __name__ == "__main__":
    main()
