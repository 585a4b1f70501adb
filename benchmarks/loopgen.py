#!/usr/bin/env python3
"""The closed-loop load generator of the ``quorum`` traffic kind: a
process of its own that never imports JAX (the measured validator's
process holds the chip), sending a prepared stream of signed
transactions as ``submit`` with ``tx_blob`` over keep-alive HTTP
connections to ONE door, one unanswered request a connection: the next
is sent when the last is answered, so the door sets the pace
(``doorgen.py`` is the open-loop counterpart: its requests go out when
they are due, whatever the answers do).

``loopgen.py <stream.txt> <results.json> <port> <connections>
[think_ms]``. The stream has one transaction a line, as hex, in the
order to send; ``think_ms`` is how long a connection waits behind an
answer before its next request (0 in every cell; the CPU rehearsal's toy
net has a think time, so that three doors do not outrun it). The
generator prints ``ready`` when its connections are open, waits for a
line on standard input, sends until the stream is out or a second line
(or end of input) arrives, and writes for every request it sent
``[index, sent, answered, outcome]``: both times on this machine's
monotonic clock (``time.perf_counter``, which the driver's process
shares), ``outcome`` the engine result of the answer, else the error.
"""

from __future__ import annotations

import http.client
import json
import sys
import threading
import time

TIMEOUT_S = 30.0


def outcome_of(status: int, payload: bytes) -> str:
    if status != 200:
        return f"http-{status}"
    try:
        result = json.loads(payload)["result"]
    except (ValueError, KeyError, TypeError):
        return "unparseable"
    if result.get("error"):
        return f"error:{result['error']}"
    return str(result.get("engine_result"))


def main(argv) -> int:
    stream_path, results_path, port, connections = (
        argv[1], argv[2], int(argv[3]), int(argv[4]))
    think_s = float(argv[5]) / 1000.0 if len(argv) > 5 else 0.0
    with open(stream_path) as fh:
        bodies = [
            ('{"method":"submit","params":[{"tx_blob":"%s"}]}'
             % line.strip()).encode()
            for line in fh if line.strip()]
    n = len(bodies)
    results: list = []
    cursor = [0]
    lock = threading.Lock()
    stop = threading.Event()
    headers = {"Content-Type": "application/json",
               "Connection": "keep-alive"}

    def connect():
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=TIMEOUT_S)
        conn.connect()
        return conn

    def worker(conn) -> None:
        while not stop.is_set():
            with lock:
                i = cursor[0]
                cursor[0] += 1
            if i >= n:
                return
            sent = time.perf_counter()
            try:
                conn.request("POST", "/", bodies[i], headers)
                resp = conn.getresponse()
                outcome = outcome_of(resp.status, resp.read())
            except (OSError, http.client.HTTPException) as exc:
                outcome = f"transport:{type(exc).__name__}"
                try:
                    conn.close()
                    conn = connect()
                except OSError:
                    pass
            row = [i, sent, time.perf_counter(), outcome]
            with lock:
                results.append(row)
            if think_s:
                stop.wait(think_s)

    conns = [connect() for _ in range(connections)]
    print("ready", flush=True)
    if not sys.stdin.readline():
        return 1  # the driver went away
    threads = [threading.Thread(target=worker, args=(c,), daemon=True)
               for c in conns]
    for t in threads:
        t.start()
    threading.Thread(target=lambda: (sys.stdin.readline(), stop.set()),
                     daemon=True).start()
    for t in threads:
        t.join()
    for c in conns:
        c.close()
    with open(results_path, "w") as fh:
        json.dump({"results": sorted(results), "exhausted": cursor[0] >= n
                   and not stop.is_set()}, fh)
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
