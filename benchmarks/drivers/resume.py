"""Traffic kind ``resume``: the flood's closed loop (``drivers/flood.py``)
on a node that did not build its state but RESUMED it: booted with
``start_up=load`` on the run's copy of a prepared store
(``yardstick/prepared_state.py``), so its state tree is open lazily and
every account the traffic touches for the first time faults from the
segstore through the byte-bounded hot-node cache.

Parameters (the traffic file): those of ``flood`` (``window``,
``senders``, ``amount_drops``, ``zipf_theta``, ``planted_per_1024``,
``close_every``, ``warmup_closes``, ``presign_tx_per_s``,
``device_check_sigs``, ``account_sample``, ``tx_sample``,
``reclose_ledgers``) and ``slide``, the accounts the sender window moves
on by at every close (``yardstick/sliding.py``). The window's counters
also hold what the hot cache and the seal did (``cache.*``, ``seal.*``),
where the program under test counts them.

Behind the window the cell is held to its guarantees as the flood is,
and one sampled ledger of the window is re-closed from disk on the plain
path by ``replay_ledger``, which loads the ledger and its PARENT eagerly
(whole trees in memory, no stubs): the lazy node and the eager plain
path must close to the same hash.
"""

from __future__ import annotations

import functools
import math
import os
import time

from yardstick import nodedrive, prepared, prepared_state, sliding, stats
from yardstick import workload
from yardstick.capture import WINDOW

CACHE_COUNTERS = ("hits", "misses", "faults", "fault_s", "evictions",
                  "evicted_bytes")
CACHE_LEVELS = ("resident_bytes", "limit_bytes")
SEAL_COUNTERS = ("closes", "incremental_seals", "building_fold_failures")


def state_counters(node) -> tuple[dict, dict]:
    """-> (counters, levels) of the hot-node cache and the seal as the
    program reads them now: a window takes the difference of the
    counters and the levels as they stand at its end. A counter the
    program under test lacks is left out (and the metric that reads it
    finds nothing)."""
    from stellard_tpu.state.shamap import inner_node_cache

    cj = inner_node_cache().get_json()
    dj = node.ledger_master.delta_replay_json()
    counters = {f"cache.{k}": cj[k] for k in CACHE_COUNTERS if k in cj}
    counters.update({f"seal.{k}": dj[k] for k in SEAL_COUNTERS if k in dj})
    return counters, {f"cache.{k}": cj[k] for k in CACHE_LEVELS}


def opens_without_a_walk() -> bool:
    """True when the program under test builds a ``Ledger`` over a
    lazily opened state tree without faulting the tree in: tried on a
    tree of 64 items in memory. A program that walks it (before PR 26
    ``Ledger.__init__`` asked the map for its truth, which is its
    length) faults every node of the state for every ledger it opens,
    the boot included: at this cell's size minutes a close, so the cell
    cannot run there and says so at once instead of being killed."""
    from stellard_tpu.nodestore.core import NodeObjectType, make_database
    from stellard_tpu.state.ledger import Ledger
    from stellard_tpu.state.shamap import (
        SHAMap, SHAMapItem, inner_node_cache,
    )

    db = make_database(type="memory")
    tree = SHAMap()
    tree.bulk_update(sets=[SHAMapItem(bytes([i]) * 32, b"probe")
                           for i in range(64)])
    tree.flush(db.store_fn(NodeObjectType.ACCOUNT_NODE), db.flushed)

    def fetch(h):
        o = db.fetch(h)
        return o.data if o else None

    lazy = SHAMap.from_store(tree.get_hash(), fetch, lazy=True)
    cache = inner_node_cache()
    before = cache.faults
    Ledger(seq=2, state_map=lazy)
    walked = cache.faults - before
    cache.clear()
    return walked == 0


def start_resumed_node(ctx, window: int, sign_traffic):
    """A node of the cell's configuration booted on the run's own copy
    of the prepared store: -> (node, pump, ini, whatever
    ``sign_traffic()`` returned). Signing runs while the device prewarm
    loads its program, as in ``nodedrive.start_funded_node``."""
    prepared_dir = prepared_state.ensure(
        ctx.config, ctx.ini_template, ctx.cache_dir)
    t0 = time.perf_counter()
    workdir, meta = prepared.copy_for_run(prepared_dir, ctx.work_root)
    marks = [("copied", time.perf_counter() - t0)]
    ini = nodedrive.ini_text(
        ctx.ini_template, workdir=os.path.join(workdir, "db"),
        start_up="load")
    node = nodedrive.boot(ini, serve=True)
    marks.append(("boot", time.perf_counter() - t0))
    try:
        resumed = node.ledger_master.closed_ledger()
        if resumed.hash().hex() != meta["last_ledger"]["hash"]:
            raise SystemExit(
                f"benchmark: the node resumed ledger {resumed.seq} "
                f"{resumed.hash().hex()[:16]}, the prepared store ends at "
                f"{meta['last_ledger']['seq']} "
                f"{meta['last_ledger']['hash'][:16]}")
        pump = nodedrive.Pump(node, window, closes_done=meta["closes_done"])
        traffic = sign_traffic()
        marks.append(("signed", time.perf_counter() - t0))
        nodedrive.wait_warm(node)
        marks.append(("warm", time.perf_counter() - t0))
        ctx.say(f"store {meta['store_bytes']} bytes (built in "
                f"{meta['build_s']}s); set-up, seconds from the copy: "
                + ", ".join(f"{k} {v:.1f}" for k, v in marks))
    except BaseException:
        node.stop()
        raise
    return node, pump, ini, traffic


def run(ctx) -> dict:
    tr, cfg = ctx.traffic, ctx.config
    pop = cfg["population"]
    close_every = int(tr["close_every"])
    warm = int(tr["warmup_closes"])
    measured_closes = math.ceil(
        float(tr["presign_tx_per_s"]) * ctx.seconds / close_every
    ) + 1
    count = (warm + measured_closes) * close_every

    if not opens_without_a_walk():
        raise SystemExit(
            "benchmark: this program faults the whole state tree in for "
            "every ledger it opens over a lazily resumed state; the cell "
            "cannot run on it")
    problems: list[str] = []
    cap = ctx.capture()
    node, pump, ini, entries = start_resumed_node(
        ctx, int(tr["window"]),
        lambda: sliding.sliding_stream(
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
            ctx.say(f"warm-up close: {pump.close()[2]:.0f} ms")
        node.close_pipeline.flush(timeout=300)
        warm_end = pos
        closes_before = len(pump.ledgers)

        snap = functools.partial(nodedrive.counters, node.verify_plane,
                                 node.hasher, node)
        cap.start()
        cap.collect_spans(node.tracer)
        cap.spans.clear()
        before, (state_before, _levels) = snap(), state_counters(node)

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
        after, (state_after, levels) = snap(), state_counters(node)
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
        window.update({k: v - state_before[k]
                       for k, v in state_after.items()})
        window.update(levels)
        if window["ops.shed"]:  # not validated: they count as failed
            ctx.say(f"{window['ops.shed']} submissions were shed")
        if refused != planted_n or node.ops.stats.get("bad_sig", 0) != planted_n:
            problems.append(
                f"refused {refused} of {planted_n} planted signatures "
                f"(bad_sig={node.ops.stats.get('bad_sig', 0)})")
        # the largest node the cache admits: an inner (1,200 + 516 bytes)
        resident, limit = (levels[f"cache.{k}"] for k in CACHE_LEVELS)
        if resident > limit + 2048:
            problems.append(f"the hot cache holds {resident} bytes, its "
                            f"budget is {limit}")

        window_ledgers = pump.ledgers[closes_before:]
        reclose = nodedrive.read_back(ctx, node, model, good_txids,
                                      window_ledgers, problems)
    finally:
        node.stop()
    t_reclose = time.perf_counter()
    nodedrive.reclose_from_disk(ini, reclose, problems)
    ctx.say(f"re-closed {len(reclose)} ledger(s) from disk on the plain "
            f"path, eager loads, {time.perf_counter() - t_reclose:.1f}s")

    close_ms = pump.close_ms[closes_before:closes_before + len(window_ledgers)]
    ctx.say(f"window {window_s:.2f}s, {len(close_ms)} closes, "
            f"{validated}/{attempted} validated, planted {refused}/{planted_n}")
    ctx.say("state: " + ", ".join(
        f"{k} {window[k]}" for k in sorted(window)
        if k.startswith(("cache.", "seal."))))
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
