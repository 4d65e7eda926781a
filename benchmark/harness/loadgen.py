"""Load generation: one process, a few threads, real HTTP.

Open loop: requests are due on a schedule fixed before the window and are
sent when due, whatever the server does; latency runs from the due time.
Closed loop: each client sends its next request when the last came back,
until the window's end, then lets the one in flight finish.
Every record: due, sent, done (seconds on `time.perf_counter`), ok, status,
queries, and the request with its answer's `data`.
"""

from __future__ import annotations

import json
import queue
import threading
import time


def exponential_gaps(count: int, rate: float, rng) -> list:
    """Inter-arrival gaps of a Poisson process at `rate`, as the same set
    of quantiles for every seed, in an order the seed picks: the offered
    load is the same from run to run, only its sequence differs."""
    import numpy as np
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate
    return gaps[rng.permutation(count)].tolist()


def _send(server, req: dict, rec: dict) -> None:
    rec["sent"] = time.perf_counter()
    try:
        status, body = server.post(req["path"], req["body"], req["ctype"])
    except OSError as e:
        status, body = 0, repr(e).encode()
    rec["done"] = time.perf_counter()
    rec["status"] = status
    data = None
    if status == 200:
        try:
            data = json.loads(body).get("data")
        except ValueError:
            data = None
    ok = data is not None and not body.startswith(b'{"errors"')
    if ok and isinstance(data, list):
        ok = not any(isinstance(o, dict) and "errors" in o for o in data)
    rec["ok"], rec["data"] = ok, data
    rec["bytes"] = len(body)


def open_loop(server, requests: list, offsets: list, workers: int):
    """Send requests[i] at t_open + offsets[i]. Returns (t_open, records)."""
    recs = [{"req": r, "queries": r["queries"]} for r in requests]
    todo: queue.Queue = queue.Queue()

    def work():
        while True:
            rec = todo.get()
            if rec is None:
                return
            _send(server, rec["req"], rec)

    threads = [threading.Thread(target=work, daemon=True)
               for _ in range(workers)]
    for t in threads:
        t.start()
    t_open = time.perf_counter()
    for rec, off in zip(recs, offsets):
        rec["due"] = t_open + off
        delay = rec["due"] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        todo.put(rec)
    for _ in threads:
        todo.put(None)
    for t in threads:
        t.join()
    return t_open, recs


def closed_loop(server, streams: list, seconds: float):
    """One thread a client; `streams[k]` yields client k's requests."""
    recs: list = []
    lock = threading.Lock()
    t_open = time.perf_counter() + 0.05

    def client(stream):
        mine = []
        time.sleep(max(t_open - time.perf_counter(), 0))
        for req in stream:
            if time.perf_counter() - t_open >= seconds:
                break
            rec = {"req": req, "queries": req["queries"]}
            _send(server, req, rec)
            rec["due"] = rec["sent"]
            mine.append(rec)
        with lock:
            recs.extend(mine)

    threads = [threading.Thread(target=client, args=(s,), daemon=True)
               for s in streams]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs.sort(key=lambda r: r["sent"])
    return t_open, recs
