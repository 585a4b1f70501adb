"""Stellar CLF plane: SQL entry mirror + atomic LCL state + crash resume.

Reference behaviors (SURVEY §2.4 'stellar CLF layer', VERDICT r2 Missing
#6 — /root/reference/src/ledger/):
- every close commits the entry-row delta + LCL pointer in ONE SQL
  transaction (LedgerDatabase ScopedTransaction, LedgerMaster::
  commitLedgerClose),
- the typed tables (accounts/trustlines/offers) mirror the state tree
  (AccountEntry/TrustLine/OfferEntry),
- a process killed between closes resumes via the CLF pointer to the
  identical ledger hash (loadLastKnownCLF), with the mirror intact,
- a mirror that is out of lockstep rebuilds via the full import path
  (importLedgerState).
"""

from __future__ import annotations

import os

import pytest

from stellard_tpu.node.config import Config
from stellard_tpu.node.node import Node
from stellard_tpu.protocol.formats import TxType
from stellard_tpu.protocol.keys import KeyPair
from stellard_tpu.protocol.sfields import (
    sfAmount,
    sfDestination,
    sfLimitAmount,
    sfTakerGets,
    sfTakerPays,
)
from stellard_tpu.protocol.stamount import STAmount, currency_from_iso
from stellard_tpu.protocol.sttx import SerializedTransaction
from stellard_tpu.state.clf import CLFMirror, LedgerSqlDatabase

XRP = 1_000_000
USD = currency_from_iso("USD")


def make_node(tmp_path, start_up="fresh") -> Node:
    cfg = Config(
        standalone=True,
        signature_backend="cpu",
        start_up=start_up,
        database_path=str(tmp_path / "tx.db"),
        node_db_type="sqlite",
        node_db_path=str(tmp_path / "nodestore.db"),
    )
    return Node(cfg).setup()


def stop_node(n: Node) -> None:
    n.stop()


def payment(key, seq, dest, drops, fee=10):
    tx = SerializedTransaction.build(
        TxType.ttPAYMENT, key.account_id, seq, fee,
        {sfAmount: STAmount.from_drops(drops), sfDestination: dest},
    )
    tx.sign(key)
    return tx


class TestMirror:
    def test_rows_follow_closes_and_deltas(self, tmp_path):
        node = make_node(tmp_path)
        master = node.master_keys
        alice = KeyPair.from_passphrase("alice")
        bob = KeyPair.from_passphrase("bob")
        try:
            node.submit(payment(master, 1, alice.account_id, 1000 * XRP))
            node.submit(payment(master, 2, bob.account_id, 500 * XRP))
            node.close_ledger()
            assert node.clf.db.count("accounts") == 3
            # trust line + offer land in their tables
            trust = SerializedTransaction.build(
                TxType.ttTRUST_SET, alice.account_id, 1, 10,
                {sfLimitAmount: STAmount.from_iou(
                    USD, master.account_id, 100, 0)},
            )
            trust.sign(alice)
            node.submit(trust)
            offer = SerializedTransaction.build(
                TxType.ttOFFER_CREATE, bob.account_id, 1, 10,
                {sfTakerPays: STAmount.from_iou(
                    USD, master.account_id, 5, 0),
                 sfTakerGets: STAmount.from_drops(5 * XRP)},
            )
            offer.sign(bob)
            node.submit(offer)
            node.close_ledger()
            assert node.clf.db.count("trustlines") == 1
            assert node.clf.db.count("offers") == 1
            # sanity: the account row carries live values
            row = node.clf.db.query(
                "SELECT balance, sequence FROM accounts WHERE account_id=?",
                (alice.account_id.hex(),),
            )[0]
            assert row[0] < 1000 * XRP  # paid trust-set fee
            assert row[1] == 2
            # LCL pointer tracks the chain
            assert node.clf.last_closed_hash == (
                node.ledger_master.closed_ledger().hash()
            )
            assert node.clf.commits >= 1
        finally:
            stop_node(node)

    def test_crash_resume_identical_hash(self, tmp_path):
        node = make_node(tmp_path)
        master = node.master_keys
        alice = KeyPair.from_passphrase("alice")
        try:
            node.submit(payment(master, 1, alice.account_id, 777 * XRP))
            node.close_ledger()
            node.submit(payment(master, 2, alice.account_id, 111 * XRP))
            node.close_ledger()
            want_hash = node.ledger_master.closed_ledger().hash()
            want_seq = node.ledger_master.closed_ledger().seq
        finally:
            # abrupt stop: no graceful save beyond the per-close commits
            stop_node(node)

        node2 = make_node(tmp_path, start_up="load")
        try:
            got = node2.ledger_master.closed_ledger()
            assert got.hash() == want_hash
            assert got.seq == want_seq
            # mirror survived too and matches the resumed chain
            assert node2.clf.last_closed_hash == want_hash
            assert node2.clf.db.count("accounts") == 2
            # and the chain keeps going
            node2.submit(payment(node2.master_keys, 3, alice.account_id, XRP))
            node2.close_ledger()
            assert node2.ledger_master.closed_ledger().seq == want_seq + 1
        finally:
            stop_node(node2)

    def test_resume_seeds_the_book_index_from_the_mirrors_offer_keys(
            self, tmp_path):
        """A node resumed with start_up=load has its book index at the
        resumed ledger before its first close, read from the offers the
        mirror lists (in lockstep with the pointer it resumed from), not
        from a walk of the state."""
        from stellard_tpu.paths.orderbook import OrderBookDB

        node = make_node(tmp_path)
        master = node.master_keys
        bob = KeyPair.from_passphrase("bob")
        try:
            node.submit(payment(master, 1, bob.account_id, 500 * XRP))
            node.close_ledger()
            for seq, usd in ((1, 5), (2, 7)):
                offer = SerializedTransaction.build(
                    TxType.ttOFFER_CREATE, bob.account_id, seq, 10,
                    {sfTakerPays: STAmount.from_iou(
                        USD, master.account_id, usd, 0),
                     sfTakerGets: STAmount.from_drops(usd * XRP)},
                )
                offer.sign(bob)
                node.submit(offer)
            node.close_ledger()
            lcl = node.ledger_master.closed_ledger()
            keys = node.clf.offer_keys(lcl)
            assert len(keys) == 2
            assert all(lcl.state_map.get(k) is not None for k in keys)
            # not this ledger's mirror: no list at all
            older = node.ledger_master.get_ledger_by_seq(lcl.seq - 1)
            assert node.clf.offer_keys(older) is None
        finally:
            stop_node(node)

        node2 = make_node(tmp_path, start_up="load")
        try:
            got = node2.ledger_master.closed_ledger()
            idx = node2.path_plane.index
            assert (idx.seeded, idx.full_rebuilds) == (1, 0)
            assert idx.state_offers_scanned == 2
            books = node2.path_plane.books_if_current(got)
            assert books is not None
            assert books.books == OrderBookDB().setup(got).books
            assert len(books.books) == 1
            node2.close_ledger()  # continuity holds: no scan now either
            assert idx.full_rebuilds == 0
            assert idx.seq == got.seq + 1
        finally:
            stop_node(node2)

    def test_atomicity_on_failed_commit(self, tmp_path):
        """A failure mid-commit must roll back rows AND state pointer."""
        db = LedgerSqlDatabase(str(tmp_path / "clf.db"))
        db.set_state("LastClosedLedger", b"\x01" * 32)
        try:
            with db.transaction():
                db.set_state("LastClosedLedger", b"\x02" * 32)
                raise RuntimeError("crash mid-commit")
        except RuntimeError:
            pass
        assert db.get_state("LastClosedLedger") == b"\x01" * 32
        db.close()

    def test_out_of_lockstep_triggers_full_import(self, tmp_path):
        node = make_node(tmp_path)
        master = node.master_keys
        alice = KeyPair.from_passphrase("alice")
        try:
            node.submit(payment(master, 1, alice.account_id, 1000 * XRP))
            node.close_ledger()
            # wreck the mirror pointer to simulate divergence
            node.clf.db.set_state("LastClosedLedger", b"\x99" * 32)
            before = node.clf.full_imports
            node.submit(payment(master, 2, alice.account_id, 10 * XRP))
            node.close_ledger()
            assert node.clf.full_imports == before + 1
            assert node.clf.last_closed_hash == (
                node.ledger_master.closed_ledger().hash()
            )
            assert node.clf.db.count("accounts") == 2
        finally:
            stop_node(node)
