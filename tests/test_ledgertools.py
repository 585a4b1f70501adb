"""Dump / transaction-stream / replay tooling tests
(reference coverage: LedgerDump.cpp modes, --replay)."""

from __future__ import annotations

import gc
import io
import weakref

import numpy as np
import pytest

from stellard_tpu.engine.engine import TxParams
from stellard_tpu.node.ledgermaster import LedgerMaster
from stellard_tpu.node.ledgertools import (
    replay_range,
    dump_ledger,
    dump_transactions,
    load_transactions,
    replay_ledger,
)
from stellard_tpu.nodestore.core import make_database
from stellard_tpu.protocol.formats import TxType
from stellard_tpu.protocol.keys import KeyPair
from stellard_tpu.protocol.sfields import sfAmount, sfBalance, sfDestination
from stellard_tpu.protocol.stamount import STAmount
from stellard_tpu.protocol.sttx import SerializedTransaction
from stellard_tpu.node.tracer import Tracer
from stellard_tpu.state.ledger import Ledger
from stellard_tpu.state.shamap import SHAMap, TNType, inner_node_cache

XRP = 1_000_000
MASTER = KeyPair.from_passphrase("masterpassphrase")


def payment(key, seq, dest, drops):
    tx = SerializedTransaction.build(
        TxType.ttPAYMENT, key.account_id, seq, 10,
        {sfAmount: STAmount.from_drops(drops), sfDestination: dest},
    )
    tx.sign(key)
    return tx


@pytest.fixture()
def chain():
    """A 4-ledger chain with payments, persisted to a memory NodeStore."""
    lm = LedgerMaster()
    lm.start_new_ledger(MASTER.account_id, close_time=1000)
    db = make_database(type="memory")
    accounts = [KeyPair.from_passphrase(f"lt-{i}") for i in range(3)]
    ledgers = []
    mseq = 1
    for i, acct in enumerate(accounts):
        tx = payment(MASTER, mseq, acct.account_id, (1000 + i) * XRP)
        mseq += 1
        ter, _ = lm.do_transaction(tx, TxParams.OPEN_LEDGER)
        assert int(ter) == 0
        closed, _ = lm.close_and_advance(2000 + i * 10, 30)
        closed.save(db)
        ledgers.append(closed)
    return lm, db, ledgers, accounts


class TestDumpLedger:
    def test_dump_round_numbers(self, chain):
        _lm, _db, ledgers, accounts = chain
        j = dump_ledger(ledgers[-1])
        assert j["ledger_index"] == ledgers[-1].seq
        assert j["ledger_hash"] == ledgers[-1].hash().hex().upper()
        assert len(j["transactions"]) == 1
        # all three paid accounts plus master are in state
        assert len(j["accountState"]) >= 4


class TestTxStreams:
    def test_dump_then_load_reproduces_balances(self, chain):
        _lm, _db, ledgers, accounts = chain
        buf = io.StringIO()
        n = dump_transactions(iter(ledgers), buf)
        assert n == 3
        buf.seek(0)
        lm2 = LedgerMaster()
        lm2.start_new_ledger(MASTER.account_id, close_time=1000)
        applied, failed = load_transactions(buf, lm2)
        assert (applied, failed) == (3, 0)
        led = lm2.current_ledger()
        for i, acct in enumerate(accounts):
            root = led.account_root(acct.account_id)
            assert root[sfBalance].drops() == (1000 + i) * XRP


class TestReplay:
    def test_replay_reproduces_exact_hash(self, chain):
        _lm, db, ledgers, _accounts = chain
        for target in ledgers[1:]:
            stats = replay_ledger(db, target.hash())
            assert stats["ok"], stats
            assert stats["state_hash_ok"] and stats["tx_hash_ok"]
            assert stats["tx_count"] == 1

    def test_replay_detects_divergence(self, chain):
        """A corrupted parent state must fail the hash comparison, not
        silently pass — replay is a correctness oracle."""
        _lm, db, ledgers, accounts = chain
        target = ledgers[-1]
        stats = replay_ledger(db, target.hash())
        assert stats["ok"]
        # sanity: replaying with the wrong target hash raises (missing key)
        with pytest.raises((KeyError, ValueError)):
            replay_ledger(db, b"\x13" * 32)

    def test_replay_batched_reverify_seam(self, chain):
        """Replay re-verifies every tx signature in ONE batched
        verify_many call and memoizes the verdicts (catch-up trust
        model, HashRouter SF_SIGGOOD role). A refused verdict makes the
        replay diverge instead of silently trusting stored history."""
        _lm, db, ledgers, _accounts = chain
        target = ledgers[-1]

        calls = []

        def spy_ok(reqs):
            calls.append(len(reqs))
            import numpy as np

            return np.ones(len(reqs), bool)

        stats = replay_ledger(db, target.hash(), verify_many=spy_ok)
        assert stats["ok"]
        assert calls == [stats["tx_count"]], "one batch for the whole set"

        def spy_reject(reqs):
            import numpy as np

            return np.zeros(len(reqs), bool)

        stats = replay_ledger(db, target.hash(), verify_many=spy_reject)
        assert not stats["ok"], "rejected signatures must fail the replay"

    def test_replay_range_one_batch_for_the_whole_span(self, chain):
        """Bulk catch-up (replay_range) verifies EVERY signature across
        the ledger span in ONE verify_many call — the TPU-native
        formulation of the reference's per-ledger history re-check —
        and reproduces every ledger hash."""
        _lm, db, ledgers, _accounts = chain
        hashes = [l.hash() for l in ledgers[1:]]

        calls = []

        def spy_ok(reqs):
            import numpy as np

            calls.append(len(reqs))
            return np.ones(len(reqs), bool)

        stats = replay_range(db, hashes, verify_many=spy_ok)
        assert stats["ok"], stats
        assert stats["ledger_count"] == len(hashes)
        assert calls == [stats["tx_count"]], "one batch for the whole SPAN"
        assert stats["tx_count"] == sum(
            s["tx_count"] for s in stats["ledgers"]
        )

    def test_replay_range_bad_sig_fails_only_its_ledger(self, chain):
        """A rejected historic signature fails its own ledger's replay,
        not the whole span — identical verdict semantics to per-ledger
        replay."""
        _lm, db, ledgers, _accounts = chain
        hashes = [l.hash() for l in ledgers[1:]]

        seen = {"n": 0}

        def reject_first(reqs):
            import numpy as np

            out = np.ones(len(reqs), bool)
            if seen["n"] == 0:
                out[0] = False  # first tx of the span = first ledger's tx
            seen["n"] += 1
            return out

        stats = replay_range(db, hashes, verify_many=reject_first)
        assert not stats["ok"]
        per = stats["ledgers"]
        assert not per[0]["ok"], "the corrupted ledger fails"
        assert all(s["ok"] for s in per[1:]), "later ledgers unaffected"


# -- replay_range: what a span reads, and where each parent comes from ------

SPAN_LEDGERS = 5
SPAN_TXS = 4


@pytest.fixture()
def span_chain():
    """Genesis and 5 ledgers of 4 payments to new accounts each, all in
    a memory NodeStore: a contiguous span whose first parent is stored."""
    lm = LedgerMaster()
    lm.start_new_ledger(MASTER.account_id, close_time=1000)
    db = make_database(type="memory")
    lm.closed_ledger().save(db)
    ledgers = []
    seq = 1
    for i in range(SPAN_LEDGERS):
        for k in range(SPAN_TXS):
            dest = KeyPair.from_passphrase(f"span-{i}-{k}").account_id
            ter, _ = lm.do_transaction(
                payment(MASTER, seq, dest, (1000 + seq) * XRP),
                TxParams.OPEN_LEDGER)
            assert int(ter) == 0
            seq += 1
        closed, _ = lm.close_and_advance(2000 + i * 10, 30)
        closed.save(db)
        ledgers.append(closed)
    return db, ledgers


def tree_nodes(db, root_hash: bytes, leaf_type) -> set:
    """Every node hash of a stored tree, its root among them."""
    seen = set()

    def fetch(h):
        seen.add(h)
        return db.fetch(h).data

    SHAMap.from_store(root_hash, fetch, leaf_type, use_cache=False)
    return seen


def counted_fetches(db, monkeypatch) -> list:
    """Every hash asked of `db` from here on, in order."""
    asked = []
    real = db.fetch

    def fetch(h, **kw):
        asked.append(h)
        return real(h, **kw)

    monkeypatch.setattr(db, "fetch", fetch)
    return asked


def ledger_spans(tr) -> tuple[list, list]:
    """-> (the `replay.ledger` spans in order, every complete span)."""
    events = [ev for ev in tr.chrome_trace()["traceEvents"]
              if ev["ph"] == "X"]
    return sorted((ev for ev in events if ev["name"] == "replay.ledger"),
                  key=lambda ev: ev["ts"]), events


VERDICT_KEYS = ("ok", "ledger_seq", "tx_count", "expected_hash",
                "replayed_hash", "state_hash_ok", "tx_hash_ok", "results")


class TestReplayRangeChain:
    def test_a_contiguous_span_reads_one_state(self, span_chain,
                                               monkeypatch):
        """The same verdicts, hashes and per-transaction results as
        `replay_ledger` ledger by ledger, from ONE eager load (the first
        target's parent) and a lazy open of each target: no node of a
        target's state tree below its root is asked of the store."""
        db, ledgers = span_chain
        hashes = [l.hash() for l in ledgers]
        one_by_one = [replay_ledger(db, h, tracer=Tracer(enabled=False))
                      for h in hashes]
        assert all(s["ok"] for s in one_by_one)

        genesis_hash = ledgers[0].parent_hash
        genesis = Ledger.load(db, genesis_hash)
        allowed = {genesis_hash} | set(hashes)
        allowed |= tree_nodes(db, genesis.state_map.get_hash(),
                              TNType.ACCOUNT_STATE)
        allowed |= tree_nodes(db, genesis.tx_map.get_hash(), TNType.TX_MD)
        below_a_state_root = set()
        for l in ledgers:
            allowed |= tree_nodes(db, l.tx_map.get_hash(), TNType.TX_MD)
            root = l.state_map.get_hash()
            allowed.add(root)
            below_a_state_root |= tree_nodes(
                db, root, TNType.ACCOUNT_STATE) - {root}
        below_a_state_root -= allowed  # what genesis shares is its own
        assert len(below_a_state_root) > SPAN_LEDGERS * SPAN_TXS

        inner_node_cache().clear()  # a hit would hide a fetch
        asked = counted_fetches(db, monkeypatch)
        tr = Tracer(sample=1.0)
        out = replay_range(db, hashes, tracer=tr)

        assert out["ok"] and out["ledger_count"] == SPAN_LEDGERS
        assert out["chained"] == SPAN_LEDGERS - 1
        for got, want in zip(out["ledgers"], one_by_one):
            assert {k: got[k] for k in VERDICT_KEYS} \
                == {k: want[k] for k in VERDICT_KEYS}
        assert set(asked) <= allowed
        assert not set(asked) & below_a_state_root
        assert set(hashes) <= set(asked)  # every header was read
        per_ledger, events = ledger_spans(tr)
        loads = [ev for ev in events if ev["name"] == "ledger.load"]
        assert [ev["args"]["lazy"] for ev in loads].count(False) == 1
        assert [ev["args"]["lazy"] for ev in loads].count(True) \
            == SPAN_LEDGERS
        assert [ev["args"]["parent_from"] for ev in per_ledger] \
            == ["store"] + ["chain"] * (SPAN_LEDGERS - 1)
        root, = [ev for ev in events if ev["name"] == "replay.span"]
        assert (root["args"]["chained"], root["args"]["state_loads"]) \
            == (SPAN_LEDGERS - 1, 1)

    @pytest.mark.parametrize("picked,rejected,parent_from,ok", [
        # the rejected signature fails its own ledger; the next takes
        # its parent from the store and passes; the chain resumes
        pytest.param([0, 1, 2, 3, 4], 0,
                     ["store", "store", "chain", "chain", "chain"],
                     [False, True, True, True, True], id="bad-sig-first"),
        pytest.param([0, 1, 2, 3, 4], 2 * SPAN_TXS + 1,
                     ["store", "chain", "chain", "store", "chain"],
                     [True, True, False, True, True], id="bad-sig-middle"),
        # a list that skips a ledger falls back to the store at the gap
        pytest.param([0, 1, 3, 4], None,
                     ["store", "chain", "store", "chain"],
                     [True] * 4, id="gap"),
        # newest first: nobody's parent is the ledger before it
        pytest.param([4, 3, 2], None, ["store"] * 3, [True] * 3,
                     id="newest-first"),
        pytest.param([2], None, ["store"], [True], id="one-ledger"),
    ])
    def test_where_the_chain_breaks_the_store_is_asked(
            self, span_chain, picked, rejected, parent_from, ok):
        """A ledger that failed its replay is never anybody's parent,
        and a ledger whose `parent_hash` is not the hash just re-closed
        gets its parent from the store: verdicts as per-ledger replay."""
        db, ledgers = span_chain
        hashes = [ledgers[i].hash() for i in picked]

        def verify_many(reqs):
            flags = np.ones(len(reqs), bool)
            if rejected is not None:
                flags[rejected] = False
            return flags

        tr = Tracer(sample=1.0)
        out = replay_range(db, hashes, verify_many=verify_many, tracer=tr)
        per = out["ledgers"]
        assert [s["ok"] for s in per] == ok
        assert out["ok"] == all(ok)
        assert [s["ledger_seq"] for s in per] \
            == [ledgers[i].seq for i in picked]
        per_ledger, events = ledger_spans(tr)
        assert [ev["args"]["parent_from"] for ev in per_ledger] \
            == parent_from
        assert out["chained"] == parent_from.count("chain")
        root, = [ev for ev in events if ev["name"] == "replay.span"]
        assert root["args"]["state_loads"] == parent_from.count("store")
        eager = [ev for ev in events if ev["name"] == "ledger.load"
                 and not ev["args"]["lazy"]]
        assert len(eager) == parent_from.count("store")
        # the refused payment is in neither tree of its ledger
        for s, good in zip(per, ok):
            assert s["tx_hash_ok"] == s["state_hash_ok"] == good

    def test_a_span_keeps_one_state_alive(self, span_chain, monkeypatch):
        """A re-closed ledger is alive while it is the next one's
        parent, and dies by reference count when its child has closed:
        nothing of it waits for a collector (`HEAP_AGING.age()` has
        frozen it by then, so no collection would ever find it)."""
        db, ledgers = span_chain
        hashes = [l.hash() for l in ledgers]
        off = Tracer(enabled=False)
        assert replay_range(db, hashes, tracer=off)["ok"]  # imports
        reclosed: list = []
        alive_at_open: list = []
        real = Ledger.open_successor

        def open_successor(self):
            alive_at_open.append(sum(r() is not None for r in reclosed))
            child = real(self)
            reclosed.append(weakref.ref(child))
            return child

        monkeypatch.setattr(Ledger, "open_successor", open_successor)
        gc.collect()
        gc.disable()
        try:
            assert replay_range(db, hashes, tracer=off)["ok"]
            assert alive_at_open == [0] + [1] * (SPAN_LEDGERS - 1)
            assert [r() for r in reclosed] == [None] * SPAN_LEDGERS
        finally:
            gc.enable()
