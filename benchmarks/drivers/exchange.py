"""Traffic kind ``exchange``: the flood's closed loop (``drivers/flood.py``)
on a node RESUMED onto the exchange deployment's store
(``yardstick/prepared_exchange.py``: gateways, trust lines, standing
order books, made through the node's own engine), fed the six-way mix
of ``yardstick/exchange.offer_stream``: quotes that rest and are
replaced, marketable limits that cross, cancels, payments across
currencies through a book, through an issuer, and of STR.

Parameters (the traffic file): the loop's (``window``, ``close_every``,
``warmup_closes``, ``planted_per_1024``, ``presign_tx_per_s``,
``fee_drops``, ``device_check_sigs``, ``tx_sample``,
``reclose_ledgers``), the generator's (see ``offer_stream``) and
``book_sample`` (books asked over RPC), ``depth_pairs`` (top-ranked
pairs whose depth is read at the window's end).

A transaction counts as validated when its close returned
``tesSUCCESS`` for it; a ``tec`` is a claimed fee and counts as failed.

Behind the window the cell is held to ``standalone-fsync``'s checks and
to its own: the live book index against a full scan and against the
benchmark's count of the last ledger's offers, ``book_offers`` over RPC
against a direct walk of the book's directories, and
``yardstick/exchangecheck.py``'s arithmetic over the last ledger
(currencies conserved to the last digit, coins, owner counts, no
crossed book). After ``node.stop()`` sampled window ledgers re-close
from the store on the plain path.
"""

from __future__ import annotations

import functools
import math
import os
import time
from fractions import Fraction

from yardstick import exchange, exchangecheck, nodedrive, prepared
from yardstick import prepared_exchange, stats
from yardstick.capture import WINDOW

TES, TEC_LO, TEC_HI = 0, 100, 200


def program_counters(node) -> dict:
    """What the program counts of the deployment's own work, as it
    stands now: delta replay's splices and fallbacks (by reason where
    the program says), the offer and flow transactors' counters, the
    book index's. A counter the program under test lacks is left out,
    and the metric that reads it finds nothing."""
    lm = node.ledger_master
    dj = lm.delta_replay_json()
    out = {f"replay.{k}": dj[k] for k in ("spliced", "fallback") if k in dj}
    for reason, n in (dj.get("fallback_by_reason") or {}).items():
        out[f"replay.fallback.{reason}"] = n
    engine_json = getattr(lm, "engine_json", None)
    if engine_json is not None:
        for block, values in engine_json().items():
            out.update({f"{block}.{k}": v for k, v in values.items()})
    plane = getattr(node, "path_plane", None)
    if plane is not None:
        for k, v in plane.index.counters().items():
            if isinstance(v, int) and not isinstance(v, bool) and k != "seq":
                out[f"index.{k}"] = v
    return out


def start_node(ctx, window: int, sign_traffic):
    """A node of the cell's configuration booted on the run's own copy
    of the prepared store: -> (node, pump, ini, meta, whatever
    ``sign_traffic()`` returned). Signing runs while the device prewarm
    loads its program."""
    prepared_dir = prepared_exchange.ensure(
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
    return node, pump, ini, meta, traffic


def check_book_index(node, last, snap, problems: list) -> None:
    """The live book index at the last closed ledger against the full
    scan of that ledger and against the benchmark's own count."""
    from stellard_tpu.paths.orderbook import Book, OrderBookDB

    plane = node.path_plane
    live = plane.books_if_current(last) if plane is not None else None
    if live is None:
        problems.append("the live book index is not at the last closed "
                        "ledger")
        return
    counted = {Book(*key): n
               for key, n in exchangecheck.book_counts(snap).items()}
    scanned = OrderBookDB().setup(last).books
    if not live.books == scanned == set(counted):
        problems.append(
            f"books: the live index has {len(live.books)}, the full scan "
            f"{len(scanned)}, the benchmark's walk {len(counted)}; "
            f"{len(live.books ^ scanned)} differ")
    book_counts = getattr(plane.index, "book_counts", None)
    if book_counts is not None and book_counts() != counted:
        mine = book_counts()
        wrong = [b for b in set(mine) | set(counted)
                 if mine.get(b) != counted.get(b)]
        problems.append(f"the live index counts {len(wrong)} book(s) "
                        f"otherwise than a walk of the ledger does")


def check_book_offers(ctx, node, last, snap, problems: list) -> None:
    """``book_offers`` over RPC for a seeded sample of books against a
    direct walk of each book's directories, in order."""
    from stellard_tpu.protocol.keys import encode_account_id

    def side(currency: bytes, issuer: bytes) -> dict:
        out = {"currency": currency.hex().upper()}
        if any(issuer):
            out["issuer"] = encode_account_id(issuer)
        return out

    books = sorted(exchangecheck.book_counts(snap))
    port = node.http_server.port
    for key in nodedrive.seeded_sample(ctx.seed + 5, books,
                                       int(ctx.traffic["book_sample"])):
        want = [i.hex().upper()
                for i in exchangecheck.book_offers_in_order(snap, key)]
        res = nodedrive.rpc(port, "book_offers", {
            "taker_pays": side(key[0], key[1]),
            "taker_gets": side(key[2], key[3]),
            "ledger_index": last.seq, "limit": 512})
        got = [o.get("index") for o in res.get("offers") or []]
        if got != want[:512] or not got:
            problems.append(
                f"book_offers {key[0][12:15]!r}/{key[2][12:15]!r}: the "
                f"door returned {len(got)} offers, a walk of the book's "
                f"directories {len(want)}, "
                f"{'in another order' if sorted(got) == sorted(want) else 'others'}"
                f" ({res.get('error')})")


def run(ctx) -> dict:
    tr, cfg = ctx.traffic, ctx.config
    market = exchange.Market(cfg["population"])
    close_every = int(tr["close_every"])
    warm = int(tr["warmup_closes"])
    measured_closes = math.ceil(
        float(tr["presign_tx_per_s"]) * ctx.seconds / close_every
    ) + 1
    count = (warm + measured_closes) * close_every

    problems: list[str] = []
    cap = ctx.capture()
    node, pump, ini, meta, entries = start_node(
        ctx, int(tr["window"]),
        lambda: exchange.offer_stream(
            seed=ctx.seed, market=market, params=tr, count=count))
    try:
        libs_ok, libs = nodedrive.host_libraries_ok()
        if not libs_ok:
            problems.append(f"host libraries: {libs}")

        from stellard_tpu.protocol.sttx import SerializedTransaction
        from stellard_tpu.protocol.ter import TER

        parse = SerializedTransaction.from_bytes
        final: dict[bytes, int] = {}  # txid -> what its close returned

        def close() -> float:
            _closed, results, ms = pump.close()
            final.update((t, int(r)) for t, r in results.items())
            return ms

        # warm-up: closes of the same traffic, unmeasured
        pos = 0
        for _ in range(warm):
            valid = 0
            while valid < close_every:
                blob, planted = entries[pos][0], entries[pos][1]
                pump.submit(parse(blob))
                valid += 0 if planted else 1
                pos += 1
            ctx.say(f"warm-up close: {close():.0f} ms")
        node.close_pipeline.flush(timeout=300)
        warm_end = pos
        closes_before = len(pump.ledgers)

        snap = functools.partial(nodedrive.counters, node.verify_plane,
                                 node.hasher, node)
        cap.start()
        cap.collect_spans(node.tracer)
        cap.spans.clear()
        before, mine_before = snap(), program_counters(node)

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
                        close()
                    cap.collect_spans(node.tracer)
                    valid = 0
                    if time.perf_counter() - t0 >= ctx.seconds:
                        exhausted = False
                        break
            if valid:
                close()
            with cap.annotate("close_pipeline.flush"):
                node.close_pipeline.flush(timeout=300)
            t1 = time.perf_counter()
        # ---- end of the window ----
        after, mine_after = snap(), program_counters(node)
        cap.collect_spans(node.tracer)
        nodedrive.check_device_path(ctx, node, entries, cap, problems)
        cap.finish()  # writing the trace out: behind the window
        window_s = t1 - t0
        if exhausted:
            ctx.say(f"the signed stream ran out after {window_s:.1f}s: "
                    f"raise presign_tx_per_s")

        attempted = validated = planted_n = refused = tec = 0
        sent = dict.fromkeys(exchange.KINDS, 0)
        good_txids = []
        for k, (_blob, planted, kind, _sender, txid) in enumerate(
                entries[:pos]):
            if planted:
                ter, applied = pump.outcomes[txid]
                planted_n += 1
                if ter == int(TER.temINVALID) and not applied:
                    refused += 1
                else:
                    problems.append(
                        f"planted signature {txid.hex()[:16]} got ter={ter}")
                continue
            if k < warm_end:
                continue
            attempted += 1
            sent[kind] += 1
            ter = final.get(txid)
            if ter == TES:
                validated += 1
                good_txids.append(txid)
            elif ter is not None and TEC_LO <= ter < TEC_HI:
                tec += 1
        window = nodedrive.delta(after, before)
        window.update({k: v - mine_before.get(k, 0)
                       for k, v in mine_after.items()})
        window.update({f"sent.{k}": n for k, n in sent.items()})
        if window["ops.shed"]:  # not validated: they count as failed
            ctx.say(f"{window['ops.shed']} submissions were shed")
        if refused != planted_n or node.ops.stats.get("bad_sig", 0) != planted_n:
            problems.append(
                f"refused {refused} of {planted_n} planted signatures "
                f"(bad_sig={node.ops.stats.get('bad_sig', 0)})")

        # the read-backs and the benchmark's own arithmetic, over the
        # last closed ledger
        window_ledgers = pump.ledgers[closes_before:]
        nodedrive.check_transactions(
            node.http_server.port,
            nodedrive.seeded_sample(ctx.seed + 3, good_txids,
                                    int(tr["tx_sample"])),
            {seq for seq, _h, _n in window_ledgers}, problems)
        last = node.ledger_master.closed_ledger()
        t_walk = time.perf_counter()
        ledger = exchangecheck.snapshot(last)
        fee = int(tr["fee_drops"])
        applied = sum(1 for ter in final.values()
                      if ter == TES or TEC_LO <= ter < TEC_HI)
        issued = {
            (market.currency_bytes(c),
             market.account_id(market.gateway_of(c))): Fraction(units)
            for c, units in enumerate(market.issued())}
        problems += exchangecheck.conservation(ledger, issued)
        problems += exchangecheck.coins(
            ledger, int(meta["genesis_coins"]),
            int(meta["fees_burned"]) + applied * fee)
        problems += exchangecheck.owner_counts(ledger)[:8]
        problems += exchangecheck.crossed_books(ledger)[:8]
        check_book_index(node, last, ledger, problems)
        check_book_offers(ctx, node, last, ledger, problems)
        # depth a side of the top-ranked pairs, as the window leaves it
        depth = exchangecheck.depth_by_side(
            ledger, market,
            exchange.pair_ranking(ctx.seed, market)[:int(tr["depth_pairs"])])
        window["book.depth_mean"] = sum(depth) / len(depth)
        window["book.seed_depth"] = market.levels
        ctx.say(f"last ledger {last.seq}: {ledger.entries} entries, "
                f"{len(ledger.offers)} offers, {len(ledger.lines)} lines, "
                f"walked and checked in {time.perf_counter() - t_walk:.1f}s; "
                f"depth a side of the top {len(depth) // 2} pairs: mean "
                f"{window['book.depth_mean']:.1f}, least {min(depth)}, "
                f"most {max(depth)} (set-up left {market.levels})")
        full = [l for l in window_ledgers if l[2] > 0]
        reclose = [h for _seq, h, _n in nodedrive.seeded_sample(
            ctx.seed + 4, full, int(tr["reclose_ledgers"]))]
    finally:
        node.stop()
    t_reclose = time.perf_counter()
    nodedrive.reclose_from_disk(ini, reclose, problems)
    ctx.say(f"re-closed {len(reclose)} ledger(s) from disk on the plain "
            f"path, {time.perf_counter() - t_reclose:.1f}s")

    close_ms = pump.close_ms[closes_before:closes_before + len(window_ledgers)]
    ctx.say(f"window {window_s:.2f}s, {len(close_ms)} closes, "
            f"{validated}/{attempted} validated, {tec} tec "
            f"({100.0 * tec / max(attempted, 1):.3f}%), planted "
            f"{refused}/{planted_n}; sent " + ", ".join(
                f"{k} {n}" for k, n in sent.items()))
    stages = {}
    for ev in cap.spans:
        if ev.get("ph") == "X" and ev["name"].startswith(
                ("close.", "persist.", "paths.index.")):
            stages[ev["name"]] = stages.get(ev["name"], 0.0) + ev["dur"]
    if stages and close_ms:
        ctx.say("spans, ms a close: " + ", ".join(
            f"{k} {v / 1000.0 / len(close_ms):.1f}"
            for k, v in sorted(stages.items())))
    ctx.say("program: " + ", ".join(
        f"{k} {window[k]}" for k in sorted(window)
        if k.startswith(("replay.", "offers.", "flow.", "index."))))
    window.update({
        "window_s": window_s, "attempted": attempted, "txs": validated,
        "closes": len(close_ms), "tec": tec,
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
