"""Traffic kind ``flood``: a closed loop of clients on the asynchronous
intake (``node.ops.submit_transaction``, the overlay's entry), a close
every ``close_every`` valid payments, durable before it counts.

Parameters (the traffic file): ``window`` unacknowledged submissions,
``senders``, ``amount_drops``, ``zipf_theta``, ``planted_per_1024``,
``close_every``, ``warmup_closes``, ``presign_tx_per_s`` (how much of
the stream set-up signs for each second of the window),
``device_check_sigs`` (the width of the one batch that checks the device
path behind the window, see ``nodedrive.check_device_path``),
``account_sample``, ``tx_sample``, ``reclose_ledgers``. A traced run
captures the measured window and that check; what the per-layer metrics
read from the trace is cut to the window.
"""

from __future__ import annotations

import functools
import math
import time

from yardstick import nodedrive, stats, workload
from yardstick.capture import WINDOW


def run(ctx) -> dict:
    tr, cfg = ctx.traffic, ctx.config
    pop = cfg["population"]
    close_every = int(tr["close_every"])
    warm = int(tr["warmup_closes"])
    measured_closes = math.ceil(
        float(tr["presign_tx_per_s"]) * ctx.seconds / close_every
    ) + 1
    count = (warm + measured_closes) * close_every

    problems: list[str] = []
    cap = ctx.capture()
    node, pump, ini, entries = nodedrive.start_funded_node(
        ctx, int(tr["window"]),
        lambda: workload.payment_stream(
            seed=ctx.seed, pop=pop, params=tr, count=count))
    try:
        libs_ok, libs = nodedrive.host_libraries_ok()
        if not libs_ok:
            problems.append(f"host libraries: {libs}")

        from stellard_tpu.protocol.sttx import SerializedTransaction
        from stellard_tpu.protocol.ter import TER

        parse = SerializedTransaction.from_bytes

        # warm-up: closes of the same traffic, unmeasured
        pos = 0
        for _ in range(warm):
            valid = 0
            while valid < close_every:
                blob, planted = entries[pos][0], entries[pos][1]
                pump.submit(parse(blob))
                valid += 0 if planted else 1
                pos += 1
            pump.close()
        node.close_pipeline.flush(timeout=300)
        warm_end = pos
        closes_before = len(pump.ledgers)

        snap = functools.partial(nodedrive.counters, node.verify_plane,
                                 node.hasher, node)
        cap.start()
        cap.collect_spans(node.tracer)
        cap.spans.clear()
        before = snap()

        # ---- the measured window ----
        with cap.annotate(WINDOW):
            t0 = time.perf_counter()
            valid = 0
            exhausted = True
            while pos < len(entries):
                blob, planted = entries[pos][0], entries[pos][1]
                with cap.annotate("submit"):
                    pump.submit(parse(blob))
                pos += 1
                valid += 0 if planted else 1
                if valid >= close_every:
                    with cap.annotate("accept_ledger"):
                        pump.close()
                    cap.collect_spans(node.tracer)
                    valid = 0
                    if time.perf_counter() - t0 >= ctx.seconds:
                        exhausted = False
                        break
            if valid:
                pump.close()
            with cap.annotate("close_pipeline.flush"):
                node.close_pipeline.flush(timeout=300)
            t1 = time.perf_counter()
        # ---- end of the window ----
        after = snap()
        cap.collect_spans(node.tracer)
        nodedrive.check_device_path(ctx, node, entries, cap, problems)
        cap.finish()  # writing the trace out: behind the window
        window_s = t1 - t0
        if exhausted:
            ctx.say(f"the signed stream ran out after {window_s:.1f}s: "
                    f"raise presign_tx_per_s")

        model = workload.BalanceModel(int(pop["funded_drops"]),
                                      int(tr["fee_drops"]))
        attempted = validated = planted_n = refused = 0
        good_txids = []
        for k, (_blob, planted, s, d, txid) in enumerate(entries[:pos]):
            ter, applied = pump.outcomes[txid]
            if planted:
                planted_n += 1
                if ter == int(TER.temINVALID) and not applied:
                    refused += 1
                else:
                    problems.append(
                        f"planted signature {txid.hex()[:16]} got ter={ter}")
                continue
            ok = ter == nodedrive.TES_SUCCESS and applied
            if ok:  # warm-up payments moved balances too
                model.applied(s, d, int(tr["amount_drops"]))
            if k >= warm_end:
                attempted += 1
                if ok:
                    validated += 1
                    good_txids.append(txid)
        window = nodedrive.delta(after, before)
        if window["ops.shed"]:  # not validated: they count as failed
            ctx.say(f"{window['ops.shed']} submissions were shed")
        if refused != planted_n or node.ops.stats.get("bad_sig", 0) != planted_n:
            problems.append(
                f"refused {refused} of {planted_n} planted signatures "
                f"(bad_sig={node.ops.stats.get('bad_sig', 0)})")

        window_ledgers = pump.ledgers[closes_before:]
        reclose = nodedrive.read_back(ctx, node, model, good_txids,
                                      window_ledgers, problems)
    finally:
        node.stop()
    nodedrive.reclose_from_disk(ini, reclose, problems)

    close_ms = pump.close_ms[closes_before:closes_before + len(window_ledgers)]
    ctx.say(f"window {window_s:.2f}s, {len(close_ms)} closes, "
            f"{validated}/{attempted} validated, planted {refused}/{planted_n}")
    window.update({
        "window_s": window_s, "attempted": attempted, "txs": validated,
        "closes": len(close_ms),
    })
    return {
        "correct": not problems,
        "problems": problems,
        "attempted": attempted,
        "failed": attempted - validated,
        "t_first_measured": t0,
        "annotations": ["submit", "accept_ledger", "close_pipeline.flush",
                        "check_device_path"],
        "end_to_end": {
            "validated_tx_per_s": stats.rate(validated, window_s),
            "close_p50_ms": stats.median(close_ms),
        },
        "sources": {
            "counters": window,
            "samples": {"close_ms": close_ms},
            "spans": cap.spans,
            "capture": cap,
        },
    }
