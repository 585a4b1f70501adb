"""Traffic kind ``door``: an open loop over the HTTP RPC door at a fixed
rate, from a generator process of its own (``doorgen.py``), while this
process, which holds the chip, runs the node and closes a ledger every
``close_interval_s`` on the clock.

Each ``submit`` of a signed ``tx_blob`` is followed by an
``account_info`` of its destination and a ``tx`` of a transaction
acknowledged ``tx_lag_s`` earlier (one write to two reads). Arrivals are
a seeded Poisson process at ``rate_rps`` requests a second in all, or,
with ``profile`` (a list of ``[seconds, rate]`` steps, for a sweep or a
burst), at each step's rate in turn. The first ``warmup_s`` are sent but
not measured. Every request is timed from when it was due; a refusal,
an error or no answer within the generator's time limit is a miss and
counts as ``miss_ms``. For every step the driver prints what a sweep is
judged by: the rate offered and the rate answered inside the step, the
backlog (requests due and not yet answered) where the step ends, and the
latency from due over the step and over its last third. The tail is
taken over the whole window, every request counted; beside it the driver
keeps the 99th percentile of each close interval alone (a sample of its
own for a per-layer metric), whose median is the tail the closes make
when no stall of the interpreter's collector is near.
``device_check_sigs``: see ``nodedrive.check_device_path``. A traced run
captures from the generator's start to behind that check; what the
per-layer metrics read from the trace is cut to the window.
"""

from __future__ import annotations

import functools
import json
import math
import os
import random
import subprocess
import sys
import threading
import time

from yardstick import nodedrive, stats, workload
from yardstick.capture import WINDOW

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def arrivals(rng: random.Random, steps: list) -> list[tuple[float, int]]:
    """Poisson arrival times over the rate steps -> [(t, step index)]."""
    out, t0 = [], 0.0
    for k, (seconds, rate) in enumerate(steps):
        t = t0
        while True:
            t += rng.expovariate(rate)
            if t >= t0 + seconds:
                break
            out.append((t, k))
        t0 += seconds
    return out


def stalls(rows: list, over_ms: float = 250.0, gap_s: float = 0.25,
           most: int = 6) -> list[tuple[float, float, int]]:
    """Episodes of slow answers, for the reader of a run's log: runs of
    requests (by due time) that took over ``over_ms``, split where none
    was due for ``gap_s``. -> [(first due, seconds, requests)]."""
    out: list[list] = []
    for t, ms in sorted(rows):
        if ms <= over_ms:
            continue
        if out and t - out[-1][1] <= gap_s:
            out[-1][1] = t
            out[-1][2] += 1
        else:
            out.append([t, t, 1])
    out.sort(key=lambda e: -e[2])
    return [(a, b - a, n) for a, b, n in out[:most]]


def step_report(steps: list, rows: list) -> list[str]:
    """One line a step. ``rows`` are (due, answered, late_ms, latency_ms,
    step index), in seconds from go. A step is sustained when what was
    answered inside it keeps up with what was offered (at least 97%) and
    the backlog where it ends is what one stall leaves (under 2 s of the
    step's rate), not what grew all step: the rule by which a sweep
    finds the rate that a cell's traffic file then fixes."""
    out, start = [], 0.0
    for k, (seconds, rate) in enumerate(steps):
        end = start + seconds
        mine = [r for r in rows if r[4] == k]
        if mine:
            answered = sum(1 for r in rows if start <= r[1] < end)
            backlog = sum(1 for r in rows if r[0] < end <= r[1])
            tail = [r for r in mine if r[0] >= end - seconds / 3.0]
            lat = [r[3] for r in mine]
            sustained = (answered >= 0.97 * len(mine)
                         and backlog < 2.0 * rate)
            out.append(
                f"step {k}: {rate:.0f} req/s for {seconds:.0f}s, "
                f"{'sustained' if sustained else 'NOT sustained'}: offered "
                f"{len(mine) / seconds:.1f}/s, answered inside "
                f"{answered / seconds:.1f}/s, backlog at its end {backlog}; "
                f"from due p50 {stats.percentile(lat, 50):.2f} p99 "
                f"{stats.percentile(lat, 99):.2f} max {max(lat):.2f} ms; "
                f"last third p50 "
                f"{stats.percentile([r[3] for r in tail], 50):.2f} ms, "
                f"sent late p50 "
                f"{stats.percentile([r[2] for r in tail], 50):.2f} p99 "
                f"{stats.percentile([r[2] for r in mine], 99):.2f} ms")
        start = end
    return out


def body(method: str, params: dict) -> str:
    return json.dumps({"method": method, "params": [params]})


def run(ctx) -> dict:
    tr, cfg = ctx.traffic, ctx.config
    pop = cfg["population"]
    warmup_s = float(tr["warmup_s"])
    steps = [[warmup_s, float(tr["rate_rps"])]]
    if tr.get("profile"):
        steps += [[float(s), float(r)] for s, r in tr["profile"]]
    else:
        steps.append([ctx.seconds, float(tr["rate_rps"])])
    rng = random.Random(ctx.seed ^ 0xD00E)
    times = arrivals(rng, steps)
    n_submits = math.ceil(len(times) / 3)
    prefill = int(tr["prefill_txs"])
    problems: list[str] = []

    cap = ctx.capture()
    node, pump, ini, entries = nodedrive.start_funded_node(
        ctx, 96,
        lambda: workload.payment_stream(
            seed=ctx.seed, pop=pop, params=tr, count=prefill + n_submits))
    gen = None
    try:
        libs_ok, libs = nodedrive.host_libraries_ok()
        if not libs_ok:
            problems.append(f"host libraries: {libs}")
        from stellard_tpu.protocol.keys import encode_account_id
        from stellard_tpu.protocol.sttx import SerializedTransaction

        # transactions closed before the generator starts: what the first
        # `tx` reads ask for. Planted copies are held back from this part.
        model = workload.BalanceModel(int(pop["funded_drops"]),
                                      int(tr["fee_drops"]))
        pos = 0
        closed_txids = []
        while len(closed_txids) < prefill:
            blob, planted, s, d, txid = entries[pos]
            pos += 1
            if planted:
                continue
            pump.submit(SerializedTransaction.from_bytes(blob))
            model.applied(s, d, int(tr["amount_drops"]))
            closed_txids.append(txid)
        pump.close()
        node.close_pipeline.flush(timeout=300)
        if any(pump.outcomes[t] != (nodedrive.TES_SUCCESS, True)
               for t in closed_txids):
            problems.append("a prefill payment failed")

        # the schedule: submit, account_info of its destination, tx of a
        # transaction submitted tx_lag_s earlier
        ids = workload.population_keys(
            pop["name"], sorted({e[3] for e in entries}))
        submit_rate = max(r for _s, r in steps) / 3.0
        lag = max(1, math.ceil(submit_rate * float(tr["tx_lag_s"])))
        schedule, sent_valid = [], []
        last_dest = entries[0][3]
        for k, (t, step) in enumerate(times):
            kind = ("submit", "account_info", "tx")[k % 3]
            if kind == "submit":
                blob, planted, s, d, txid = entries[pos]
                pos += 1
                last_dest = d
                if not planted:
                    sent_valid.append(txid)
                req = body("submit", {"tx_blob": blob.hex().upper()})
                ref = [planted, s, d, txid.hex()]
            elif kind == "account_info":
                req = body("account_info", {
                    "account": encode_account_id(ids[last_dest].account_id)})
                ref = None
            else:
                past = len(sent_valid) - lag
                txid = (sent_valid[past] if past >= 0
                        else closed_txids[k % len(closed_txids)])
                req = body("tx", {"transaction": txid.hex().upper()})
                ref = None
            schedule.append({"t": t, "kind": kind, "body": req,
                             "step": step, "ref": ref})
        os.makedirs(ctx.work_root, exist_ok=True)
        sched_path = os.path.join(ctx.work_root, "schedule.jsonl")
        out_path = os.path.join(ctx.work_root, "door-results.json")
        with open(sched_path, "w") as fh:
            for row in schedule:
                fh.write(json.dumps({k: row[k] for k in ("t", "kind", "body")})
                         + "\n")

        # the generator imports no JAX; the pin is belt and braces
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        gen = subprocess.Popen(
            [sys.executable, os.path.join(BENCH, "doorgen.py"), sched_path,
             out_path, str(node.http_server.port), str(int(tr["connections"]))],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
        if gen.stdout.readline().strip() != "ready":
            raise SystemExit("benchmark: the door generator did not start")

        snap = functools.partial(nodedrive.counters, node.verify_plane,
                                 node.hasher, node)
        total_s = sum(s for s, _r in steps)
        interval = float(tr["close_interval_s"])
        closes: list[tuple[float, float]] = []  # (started at, ms)
        stop_closing = threading.Event()

        def closer(t_go: float) -> None:
            due = t_go + interval
            while not stop_closing.is_set():
                now = time.perf_counter()
                if now < due:
                    time.sleep(min(0.02, due - now))
                    continue
                with cap.annotate("accept_ledger"):
                    _closed, _results, ms = pump.close()
                closes.append((now - t_go, ms))
                cap.collect_spans(node.tracer)
                due += interval

        cap.start()
        gen.stdin.write("go\n")
        gen.stdin.flush()
        t_go = time.perf_counter()
        closing = threading.Thread(target=closer, args=(t_go,), daemon=True)
        closing.start()
        try:
            time.sleep(max(0.0, t_go + warmup_s - time.perf_counter()))
            cap.collect_spans(node.tracer)
            cap.spans.clear()
            before = snap()
            # ---- the measured window: to the generator's last answer ----
            with cap.annotate(WINDOW):
                gen.wait(timeout=total_s + 120)
                t_end = time.perf_counter()
        finally:
            stop_closing.set()
            closing.join(timeout=120)
        if gen.returncode != 0:
            raise SystemExit(f"benchmark: the door generator exited "
                             f"{gen.returncode}")
        pump.close()
        node.close_pipeline.flush(timeout=300)
        after = snap()
        cap.collect_spans(node.tracer)
        nodedrive.check_device_path(ctx, node, entries, cap, problems)
        cap.finish()  # writing the trace out: behind the window
        with open(out_path) as fh:
            results = json.load(fh)["results"]

        # what the answers said
        miss_ms = float(tr["miss_ms"])
        latency, late, due = [], [], []
        attempted = misses = planted_n = refused = 0
        good_txids = []
        for row, res in zip(schedule, results):
            _i, late_ms, latency_ms, outcome = res
            planted = bool(row["ref"] and row["ref"][0])
            if row["kind"] == "submit":
                if planted:
                    planted_n += 1
                    if outcome in ("tesSUCCESS", "None") or outcome.startswith(
                            ("http", "transport", "unparseable")):
                        problems.append(f"planted submit answered {outcome}")
                    else:
                        refused += 1
                    ok = True  # refused, as it must be: not a miss
                else:
                    ok = outcome == "tesSUCCESS"
                    if ok:
                        _p, s, d, txid = row["ref"]
                        model.applied(s, d, int(tr["amount_drops"]))
                        good_txids.append(bytes.fromhex(txid))
            else:
                ok = outcome == "ok"
            if row["t"] < warmup_s:
                continue
            attempted += 1
            misses += 0 if ok else 1
            if not ok and misses <= 5:
                ctx.say(f"miss: {row['kind']} at {row['t']:.2f}s: {outcome}")
            value = latency_ms if ok else max(latency_ms, miss_ms)
            latency.append(value)
            late.append(late_ms)
            due.append(row["t"] - warmup_s)
        for at, dur_s, n in stalls(
                [(row["t"], res[2]) for row, res in zip(schedule, results)
                 if row["t"] >= warmup_s]):
            ctx.say(f"stall: {n} requests over 250 ms, due from "
                    f"{at - warmup_s:.2f}s of the window for {dur_s:.2f}s")
        for at, ms in closes:
            if ms > 300:
                ctx.say(f"close at {at - warmup_s:.2f}s of the window took "
                        f"{ms:.0f} ms")
        for line in step_report(steps, [
                (row["t"], row["t"] + res[2] / 1000.0, res[1], res[2],
                 row["step"]) for row, res in zip(schedule, results)]):
            ctx.say(line)

        reclose = nodedrive.read_back(
            ctx, node, model, good_txids,
            pump.ledgers[-(len(closes) + 1):], problems)
    finally:
        if gen is not None and gen.poll() is None:
            gen.kill()
        if gen is not None:
            gen.wait()
        node.stop()
    nodedrive.reclose_from_disk(ini, reclose, problems)

    close_ms = [ms for at, ms in closes if at >= warmup_s]
    # the tail of each close interval alone: its median is the tail the
    # closes make, whichever of the collector's stalls the window caught
    interval_p99 = stats.sliced_percentiles(
        zip(due, latency), interval, total_s - warmup_s, 99.0)
    window = nodedrive.delta(after, before)
    window_s = t_end - t_go - warmup_s
    window.update({"window_s": window_s, "attempted": attempted,
                   "txs": len(good_txids), "closes": len(close_ms)})
    ctx.say(f"{attempted} requests due in {window_s:.1f}s "
            f"({stats.samples_beyond(attempted, 99):.0f} beyond p99), "
            f"{misses} missed, {len(close_ms)} closes, planted "
            f"{refused}/{planted_n}, generator late p99 "
            f"{stats.percentile(late, 99):.2f} ms, p99 of a close interval "
            f"alone {stats.median(interval_p99) or 0.0:.2f} ms (median of "
            f"{len(interval_p99)})")
    return {
        "correct": not problems,
        "problems": problems,
        "attempted": attempted,
        "failed": misses,
        "t_first_measured": t_go + warmup_s,
        "annotations": ["accept_ledger", "check_device_path"],
        "end_to_end": {
            "door_p99_ms": stats.percentile(latency, 99.0),
            "close_p50_ms": stats.median(close_ms),
        },
        "sources": {
            "counters": window,
            "samples": {"door.latency_ms": latency, "door.late_ms": late,
                        "door.interval_p99_ms": interval_p99,
                        "close_ms": close_ms},
            "spans": cap.spans,
            "capture": cap,
        },
    }
