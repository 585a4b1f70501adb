#!/usr/bin/env python3
"""The open-loop load generator of the ``door`` traffic kind: a process
of its own that never imports JAX (the node's process holds the chip),
sending a prepared schedule of JSON-RPC requests over keep-alive HTTP
connections, each request at the time it is due whatever the answers do.

``doorgen.py <schedule.jsonl> <results.json> <port> <connections>``. The
schedule has one request a line: ``{"t": seconds after go, "kind": ...,
"body": <the JSON-RPC body, as a string>}``, in order of ``t``. The
generator prints ``ready`` when its connections are open, waits for a
line on standard input, runs the schedule, and writes for every request
``[index, late_ms, latency_ms, outcome]``: ``late_ms`` is sent minus
due, ``latency_ms`` is answered minus DUE (a stall delays the requests
behind it, and that wait counts), ``outcome`` is what the answer said
(the engine result of a submit, ``ok`` for a read that found its
object, else the error).
"""

from __future__ import annotations

import http.client
import json
import sys
import threading
import time

TIMEOUT_S = 10.0


def outcome_of(kind: str, status: int, payload: bytes) -> str:
    if status != 200:
        return f"http-{status}"
    try:
        result = json.loads(payload)["result"]
    except (ValueError, KeyError, TypeError):
        return "unparseable"
    if result.get("error"):
        return f"error:{result['error']}"
    if kind == "submit":
        return str(result.get("engine_result"))
    if kind == "account_info":
        return "ok" if "account_data" in result else "no-account_data"
    if kind == "tx":
        meta = result.get("meta") or {}
        good = meta.get("TransactionResult") in (0, "tesSUCCESS")
        return "ok" if good and result.get("hash") else "no-result"
    return "ok"


def main(argv) -> int:
    schedule_path, results_path, port, connections = (
        argv[1], argv[2], int(argv[3]), int(argv[4]))
    with open(schedule_path) as fh:
        schedule = [json.loads(line) for line in fh if line.strip()]
    bodies = [s["body"].encode() for s in schedule]
    n = len(schedule)
    results: list = [None] * n
    cursor = [0]
    lock = threading.Lock()
    go = [0.0]
    headers = {"Content-Type": "application/json",
               "Connection": "keep-alive"}

    def connect():
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=TIMEOUT_S)
        conn.connect()
        return conn

    def worker(conn) -> None:
        while True:
            with lock:
                i = cursor[0]
                cursor[0] += 1
            if i >= n:
                return
            due = go[0] + schedule[i]["t"]
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            sent = time.perf_counter()
            try:
                conn.request("POST", "/", bodies[i], headers)
                resp = conn.getresponse()
                payload = resp.read()
                outcome = outcome_of(schedule[i]["kind"], resp.status,
                                     payload)
            except (OSError, http.client.HTTPException) as exc:
                outcome = f"transport:{type(exc).__name__}"
                try:
                    conn.close()
                    conn = connect()
                except OSError:
                    pass
            done = time.perf_counter()
            results[i] = [i, (sent - due) * 1000.0, (done - due) * 1000.0,
                          outcome]

    conns = [connect() for _ in range(connections)]
    print("ready", flush=True)
    if not sys.stdin.readline():
        return 1  # the driver went away
    go[0] = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(c,), daemon=True)
               for c in conns]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for c in conns:
        c.close()
    with open(results_path, "w") as fh:
        json.dump({"results": results,
                   "elapsed_s": time.perf_counter() - go[0]}, fh)
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
