"""Offline ledger tooling: dump, transaction streams, and replay.

Reference: src/ripple_app/main/LedgerDump.cpp — `--dump_ledger` (:68),
`--dump_transactions` (:86), `--load_transactions` (:267) — plus the
`--ledger N --replay` path (Main.cpp:325-332): load a stored ledger and
re-close it from its parent, verifying the rebuilt hash bit-for-bit.

Replay is BASELINE config #5's harness: it re-runs the full pipeline —
canonical apply, metadata, level-batched tree re-hash — against known
good output, and times it.
"""

from __future__ import annotations

import json
import time
from typing import Callable, Iterator, Optional, TextIO

from ..nodestore.core import Database
from ..protocol.sttx import SerializedTransaction
from ..protocol.stobject import STObject
from ..protocol.ter import TER
from ..state.ledger import Ledger
from .heapaging import HEAP_AGING
from .ledgermaster import CanonicalTXSet, LedgerMaster
from .tracer import GC_PROBE, Tracer, get_tracer

# A replayed transaction has no tree to join (no submit, no open
# window): the ledger master that re-applies it marks no `close.tx`.
# One in eight would be 2,048 orphans a span of 16,384 in the ring that
# the `replay.*` spans are read from.
_NO_TX_MARKS = Tracer(enabled=False)

__all__ = [
    "dump_ledger",
    "dump_transactions",
    "load_transactions",
    "replay_ledger",
    "replay_range",
]


def dump_ledger(ledger: Ledger) -> dict:
    """Full JSON image of one closed ledger (reference: dumpLedger,
    LedgerDump.cpp:68 — header, state entries, transactions)."""
    out = {
        "ledger_index": ledger.seq,
        "ledger_hash": ledger.hash().hex().upper(),
        "parent_hash": ledger.parent_hash.hex().upper(),
        "close_time": ledger.close_time,
        "close_time_resolution": ledger.close_resolution,
        "close_flags": ledger.close_flags,
        "total_coins": str(ledger.tot_coins),
        "fee_pool": str(ledger.fee_pool),
        "inflation_seq": ledger.inflation_seq,
        "account_hash": ledger.state_map.get_hash().hex().upper(),
        "transaction_hash": ledger.tx_map.get_hash().hex().upper(),
        "accountState": [],
        "transactions": [],
    }
    for item in ledger.state_map.items():
        sle = STObject.from_bytes(item.data)
        j = sle.to_json()
        j["index"] = item.tag.hex().upper()
        out["accountState"].append(j)
    for txid, blob, meta in ledger.tx_entries():
        tx = SerializedTransaction.from_bytes(blob)
        j = tx.obj.to_json()
        j["hash"] = txid.hex().upper()
        out["transactions"].append(j)
    return out


def dump_transactions(
    ledgers: Iterator[Ledger], fh: TextIO
) -> int:
    """Stream every transaction of a ledger range as JSON lines
    (reference: dumpTransactions, LedgerDump.cpp:86). Format per line:
    {"ledger": seq, "close_time": t, "blob": hex}."""
    n = 0
    for ledger in ledgers:
        for txid, blob, _meta in ledger.tx_entries():
            fh.write(
                json.dumps(
                    {
                        "ledger": ledger.seq,
                        "close_time": ledger.close_time,
                        "hash": txid.hex(),
                        "blob": blob.hex(),
                    }
                )
                + "\n"
            )
            n += 1
    return n


def load_transactions(
    fh: TextIO,
    lm: LedgerMaster,
    close_every: Optional[int] = None,
) -> tuple[int, int]:
    """Re-drive dumped transactions through a fresh chain (reference:
    loadTransactions, LedgerDump.cpp:267 — the bulk-import harness).
    Closes the open ledger whenever the source ledger seq changes (or
    every `close_every` txns). Returns (applied, failed)."""
    from ..engine.engine import TxParams

    applied = failed = 0
    last_src_ledger: Optional[int] = None
    last_close_time = 0
    pending = 0
    for line in fh:
        line = line.strip()
        if not line:
            continue
        rec = json.loads(line)
        if last_src_ledger is not None and (
            rec["ledger"] != last_src_ledger
            or (close_every and pending >= close_every)
        ):
            # close with the batch's OWN close time (the previous
            # record's), not the next ledger's — time-dependent txns must
            # see the same clock they saw in the source chain
            lm.close_and_advance(last_close_time, 30)
            pending = 0
        last_src_ledger = rec["ledger"]
        last_close_time = rec["close_time"]
        tx = SerializedTransaction.from_bytes(bytes.fromhex(rec["blob"]))
        ter, ok = lm.do_transaction(tx, TxParams.OPEN_LEDGER | TxParams.RETRY)
        if ok or int(ter) == 0:
            applied += 1
        else:
            failed += 1
        pending += 1
    if pending:
        lm.close_and_advance(last_close_time, 30)
    return applied, failed


def _reverify_memoized(txs: list, verify_many: Callable) -> None:
    """Re-verify a tx list's signatures in ONE batched call and memoize
    the verdicts (the HashRouter SF_SIGGOOD seam) — the single shape of
    the catch-up trust model, shared by per-ledger replay and bulk
    replay_range."""
    if not txs:
        return
    from ..crypto.backend import VerifyRequest

    flags = verify_many([
        VerifyRequest(tx.signing_pub_key, tx.signing_hash(), tx.signature)
        for tx in txs
    ])
    for tx, good in zip(txs, flags):
        tx.set_sig_verdict(bool(good))


def _runtime_marks() -> tuple:
    """What the interpreter's collector and the hot-node cache's victim
    scans have cost so far: a replay span carries the difference over
    its own length (``gc_pause_s``, ``evict_scan_s``), and what the
    replaying thread and the whole process ran of it (``cpu_s``,
    ``process_cpu_s``: the rest of the span the thread waited, on the
    chip, the disk or the interpreter's lock)."""
    from ..state.shamap import inner_node_cache

    return (GC_PROBE.pause_total_s(), inner_node_cache().evict_scan_s,
            time.thread_time(), time.process_time())


def replay_ledger(
    db: Database,
    ledger_hash: bytes,
    hash_batch: Optional[Callable] = None,
    verify_many: Optional[Callable] = None,
    tracer=None,
) -> dict:
    """Re-close a stored ledger from its parent and verify the result
    hashes identically (reference: --ledger N --replay, Main.cpp:325-332).

    Loads ledger L and parent P from the NodeStore, both whole and
    eagerly (every node of both trees fetched and content-checked: this
    is the plain path, the one other replays are compared with),
    re-applies L's tx set to P in canonical order through the full
    engine, re-hashes both trees through the (device) BatchHasher, and
    compares against L's recorded hashes. Returns timing/throughput
    stats.

    With `verify_many` (a VerifyPlane-style batched verifier), every tx
    signature in the ledger is re-verified in ONE batch up front and the
    verdicts memoized into the txs — the HashRouter SF_SIGGOOD seam — so
    the per-tx engine path skips its inline host verify. This is the
    catch-up trust model: replayed history is re-verified, batched.

    One ``replay.ledger`` span a call (``tracer`` defaults to the
    process tracer; ``parent_from`` is always ``"store"`` here), with
    the two loads (``ledger.load``), ``replay.parse``,
    ``replay.verify``, ``replay.apply`` and ``replay.close`` (the close
    and both tree hashes) under it."""
    tr = tracer if tracer is not None else get_tracer()
    kw = {"hash_batch": hash_batch} if hash_batch else {}
    probed = GC_PROBE.install(tr)
    HEAP_AGING.acquire()
    try:
        with tr.span("replay.ledger", "replay") as span:
            target = Ledger.load(db, ledger_hash, tracer=tr, **kw)
            parent = Ledger.load(db, target.parent_hash, tracer=tr, **kw)
            with tr.span("replay.parse", "replay"):
                txs = _parse_txs(target)
            stats, _closed = _reclose(target, parent, txs, verify_many, kw,
                                      tr, span, "store")
            return stats
    finally:
        HEAP_AGING.release()
        if probed:
            GC_PROBE.remove(tr)


def _parse_txs(target: Ledger) -> list[SerializedTransaction]:
    return [
        SerializedTransaction.from_bytes(blob)
        for _txid, blob, _meta in target.tx_entries()
    ]


def _reclose(target: Ledger, parent: Ledger, txs: list, verify_many, kw,
             tr, span, parent_from: str) -> tuple[dict, Ledger]:
    """Re-apply `txs` to `parent` and close under `target`'s header ->
    (the stats of `replay_ledger`, the ledger it re-closed). Of `target`
    it reads the header and the two root hashes, never a node."""
    ledger_hash = target.hash()
    t0 = time.perf_counter()
    if verify_many is not None:
        with tr.span("replay.verify", "replay", sigs=len(txs)):
            _reverify_memoized(txs, verify_many)
    verify_s = time.perf_counter() - t0
    with tr.span("replay.apply", "replay", txs=len(txs)):
        replay = parent.open_successor()
        txset = CanonicalTXSet(parent.hash())
        for tx in txs:
            txset.insert(tx)
        lm = LedgerMaster(tracer=_NO_TX_MARKS, **kw)
        results = lm._apply_transactions(replay, txset)
    with tr.span("replay.close", "replay"):
        replay.close(
            target.close_time,
            target.close_resolution,
            correct_close_time=(target.close_flags & 1) == 0,
        )
        replay.close_flags = target.close_flags
        replay_hash = replay.hash()
    elapsed = time.perf_counter() - t0
    if span is not None:
        span.attrs = {"seq": target.seq, "txs": len(txs),
                      "parent_from": parent_from}

    ok = replay_hash == ledger_hash
    return {
        "ok": ok,
        "ledger_seq": target.seq,
        "tx_count": len(txs),
        "elapsed_s": elapsed,
        # the batched re-verification alone (on a cold process this is
        # where a device program compiles, or loads from the cache)
        "verify_s": verify_s,
        "tx_per_s": len(txs) / elapsed if elapsed > 0 else 0.0,
        "expected_hash": ledger_hash.hex(),
        "replayed_hash": replay_hash.hex(),
        "state_hash_ok": replay.state_map.get_hash()
        == target.state_map.get_hash(),
        "tx_hash_ok": replay.tx_map.get_hash() == target.tx_map.get_hash(),
        "results": {k.hex(): int(v) for k, v in results.items()},
    }, replay


def replay_range(
    db: Database,
    ledger_hashes: list[bytes],
    hash_batch: Optional[Callable] = None,
    verify_many: Optional[Callable] = None,
    tracer=None,
) -> dict:
    """Bulk catch-up over a chain of stored ledgers.

    The reference re-verifies acquired history per ledger because its
    verify is a per-call host library (LedgerMaster/LedgerCleaner checks,
    libsodium); on a latency-flat batch device the TPU-native formulation
    verifies EVERY transaction signature across the whole range in ONE
    kernel invocation up front, then re-applies ledger by ledger with the
    verdicts memoized (the SF_SIGGOOD seam) — the bigger the catch-up
    span, the further the batch rides up the device's throughput curve.

    What it reads from the store: of every target the header and the
    transaction tree (a lazy open: the header hash is checked against
    the hash asked for, every transaction node is content-checked as it
    faults, and no node of a target's state tree below its root is
    fetched: the root hashes under the header are what ``state_hash_ok``
    and ``tx_hash_ok`` compare with); of the state, ONE ledger whole and
    eagerly, the first target's parent. Each later ledger's parent is
    the ledger re-closed before it, taken from the chain when and only
    when that ledger replayed to its stored hash (``ok``) and this
    target's ``parent_hash`` is that hash: the re-closed ledger then IS
    the stored parent. After a ledger that failed, and across a gap in
    the list, the parent is loaded from the store as `replay_ledger`
    loads it. Verdict semantics are therefore identical to per-ledger
    replay: a bad historic signature still fails its own ledger's hash
    check, no other's, because a ledger that failed is never anybody's
    parent.

    One ``replay.span`` span a call (``tracer`` defaults to the process
    tracer): a lazy ``ledger.load`` for every target, ``replay.parse``
    (the transaction trees fault here), ``replay.verify`` (the plane's
    ``verify.batch`` nests under it), then a ``replay.ledger`` per
    ledger (``parent_from`` ``"chain"`` or ``"store"``; an eager
    ``ledger.load`` under it where it is the store). It ends with the
    ledgers and transactions it covered, how many took their parent
    from the chain (``chained``; the result carries it too), the eager
    loads it made (``state_loads``), what the collector
    (``gc_pause_s``) and the hot cache's victim scans
    (``evict_scan_s``) took of it, and what the replaying thread
    (``cpu_s``) and the process (``process_cpu_s``) ran of it."""
    tr = tracer if tracer is not None else get_tracer()
    probed = GC_PROBE.install(tr)
    HEAP_AGING.acquire()
    try:
        with tr.span("replay.span", "replay") as span:
            marks = _runtime_marks()
            out = _replay_range(db, ledger_hashes, hash_batch, verify_many,
                                tr)
            if span is not None:
                gc_s, scan_s, cpu_s, proc_s = _runtime_marks()
                span.attrs = {
                    "ledgers": out["ledger_count"], "txs": out["tx_count"],
                    "chained": out["chained"],
                    "state_loads": out["ledger_count"] - out["chained"],
                    "gc_pause_s": round(gc_s - marks[0], 6),
                    "evict_scan_s": round(scan_s - marks[1], 6),
                    "cpu_s": round(cpu_s - marks[2], 6),
                    "process_cpu_s": round(proc_s - marks[3], 6),
                }
            return out
    finally:
        HEAP_AGING.release()
        if probed:
            GC_PROBE.remove(tr)


def _replay_range(db, ledger_hashes, hash_batch, verify_many, tr) -> dict:
    kw = {"hash_batch": hash_batch} if hash_batch else {}
    t0 = time.perf_counter()
    # opened, not loaded: the header and two roots now, the transaction
    # tree as `replay.parse` walks it
    targets = [Ledger.load(db, h, lazy=True, tracer=tr, **kw)
               for h in ledger_hashes]
    with tr.span("replay.parse", "replay"):
        per_ledger = [_parse_txs(target) for target in targets]
    if verify_many is not None:
        with tr.span("replay.verify", "replay",
                     sigs=sum(len(txs) for txs in per_ledger)):
            _reverify_memoized(
                [tx for txs in per_ledger for tx in txs], verify_many
            )
    stats = []
    chained = 0
    # the ledger re-closed last, while it replayed to its stored hash:
    # the one name that keeps a state alive between two ledgers
    parent = None
    for txs, target in zip(per_ledger, targets):
        with tr.span("replay.ledger", "replay") as span:
            if parent is not None and target.parent_hash == parent.hash():
                parent_from = "chain"
                chained += 1
            else:
                parent_from = "store"
                parent = Ledger.load(db, target.parent_hash, tracer=tr, **kw)
            s, parent = _reclose(target, parent, txs, None, kw, tr, span,
                                 parent_from)
        if not s["ok"]:
            parent = None  # a ledger that failed is nobody's parent
        stats.append(s)
        # behind the ledger's span: what it left alive (its re-closed
        # ledger, the next one's parent; the first time, every opened
        # target of the range) is aged once
        HEAP_AGING.age()
    elapsed = time.perf_counter() - t0
    total = sum(s["tx_count"] for s in stats)
    return {
        "ok": all(s["ok"] for s in stats),
        "ledger_count": len(stats),
        "tx_count": total,
        "chained": chained,
        "elapsed_s": elapsed,
        "tx_per_s": total / elapsed if elapsed > 0 else 0.0,
        "ledgers": stats,
    }
