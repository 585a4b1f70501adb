"""Conflict seam of the speculative delta-replay close.

Every test here pins the one property the optimization must never trade
away: a delta-replay close produces BYTE-IDENTICAL ledgers (hash +
per-tx results) to the full serial re-apply, on exactly the workloads
engineered to stress the splice/fallback boundary — same-account bursts
under the canonical shuffle, cross-account conflicts on shared entries,
offers crossing one book, tec fee claims and terPRE_SEQ holds promoted
mid-flood, and a close against a different parent than the open pass
saw (which must force 100% fallback via the parent gate).
"""

from __future__ import annotations

import pytest

from stellard_tpu.engine.engine import TxParams
from stellard_tpu.node.config import Config
from stellard_tpu.node.ledgermaster import CanonicalTXSet, LedgerMaster
from stellard_tpu.protocol.formats import TxType
from stellard_tpu.protocol.keys import KeyPair
from stellard_tpu.protocol.sfields import (
    sfAmount,
    sfDestination,
    sfLimitAmount,
    sfOfferSequence,
    sfTakerGets,
    sfTakerPays,
)
from stellard_tpu.protocol.stamount import STAmount
from stellard_tpu.protocol.ter import TER
from stellard_tpu.protocol.sttx import SerializedTransaction

MASTER = KeyPair.from_passphrase("masterpassphrase")
USD = b"USD" + b"\x00" * 17
OPEN = TxParams.OPEN_LEDGER | TxParams.RETRY


def build(tx_type, kp, seq, fields, fee=10):
    tx = SerializedTransaction.build(tx_type, kp.account_id, seq, fee, fields)
    tx.sign(kp)
    return tx


def fresh(tx):
    """Re-parse so memoized per-object state never leaks across modes."""
    return SerializedTransaction.from_bytes(tx.serialize())


def run_workload(phases, delta_replay):
    """Drive `phases` (list of tx lists, one close per phase) through a
    fresh chain; -> (per-close hashes, per-close sorted results, stats)."""
    lm = LedgerMaster()
    lm.delta_replay = delta_replay
    lm.start_new_ledger(MASTER.account_id, close_time=1000)
    hashes, results_log = [], []
    for i, phase in enumerate(phases):
        for tx in phase:
            ter, ok = lm.do_transaction(fresh(tx), OPEN)
            if ter == TER.terPRE_SEQ:
                lm.add_held_transaction(fresh(tx))
        closed, results = lm.close_and_advance(2000 + i * 30, 30)
        hashes.append(closed.hash())
        results_log.append(sorted(
            (txid.hex(), int(ter)) for txid, ter in results.items()
        ))
    return hashes, results_log, dict(lm.delta_stats)


def assert_identical(phases):
    """Run both modes; byte-identity is the contract. Returns the
    delta-mode stats for workload-specific assertions."""
    h1, r1, stats = run_workload(phases, delta_replay=True)
    h0, r0, _ = run_workload(phases, delta_replay=False)
    assert h1 == h0, "delta-replay close diverged from serial re-apply"
    assert r1 == r0, "per-tx results diverged from serial re-apply"
    return stats


def payment(kp, seq, dest, drops=250_000_000):
    return build(TxType.ttPAYMENT, kp, seq,
                 {sfAmount: STAmount.from_drops(drops), sfDestination: dest})


class TestByteIdentity:
    def test_same_account_burst_splices(self):
        """One account's seq chain: CanonicalTXSet preserves per-account
        order, so every record must splice — and still match serial."""
        dests = [KeyPair.from_passphrase(f"dr-d{i}").account_id
                 for i in range(4)]
        phases = [
            [payment(MASTER, 1 + i, dests[i % 4]) for i in range(20)],
            [payment(MASTER, 21 + i, dests[i % 4]) for i in range(20)],
        ]
        stats = assert_identical(phases)
        assert stats["spliced"] == 40
        assert stats["fallback"] == 0

    def test_cross_account_shared_destination_conflicts(self):
        """Independent senders all paying ONE hot account: the canonical
        shuffle reorders them against submission order, so records
        conflict on the shared destination root and must fall back —
        byte-identically."""
        senders = [KeyPair.from_passphrase(f"dr-s{i}") for i in range(6)]
        hot = KeyPair.from_passphrase("dr-hot").account_id
        fund = [payment(MASTER, 1 + i, s.account_id, 2_000_000_000)
                for i, s in enumerate(senders)]
        work = []
        for rnd in range(3):
            for s in senders:
                work.append(payment(s, 1 + rnd, hot, 210_000_000))
        stats = assert_identical([fund, work])
        total = stats["spliced"] + stats["fallback"]
        assert total == len(fund) + len(work)
        # the shuffle makes SOME conflict order-dependent; the exact
        # split is salt-dependent, but a zero-fallback run would mean
        # the workload exercised nothing
        assert stats["fallback"] > 0
        assert stats["invalidated"] > 0

    def test_offers_crossing_one_book(self):
        """Asks and crossing bids from many accounts on one USD/XRP book
        (plus cancels): book-dir succ walks, partial fills, offer
        deletions — the densest conflict surface we have."""
        gateway = KeyPair.from_passphrase("dr-gw")
        traders = [KeyPair.from_passphrase(f"dr-t{i}") for i in range(5)]
        fund = [payment(MASTER, 1 + i, who.account_id, 1_500_000_000)
                for i, who in enumerate([gateway] + traders)]
        trust = [
            build(TxType.ttTRUST_SET, t, 1,
                  {sfLimitAmount: STAmount.from_iou(
                      USD, gateway.account_id, 10**9, 0)})
            for t in traders
        ]
        seqs = {gateway.account_id: 1}
        for t in traders:
            seqs[t.account_id] = 2
        work, live = [], []
        for i in range(40):
            if i % 7 == 6 and live:
                kp, oseq = live.pop(0)
                tx = build(TxType.ttOFFER_CANCEL, kp, seqs[kp.account_id],
                           {sfOfferSequence: oseq})
            elif i % 2 == 0:
                price = 50 + (i % 15)
                tx = build(
                    TxType.ttOFFER_CREATE, gateway,
                    seqs[gateway.account_id],
                    {sfTakerPays: STAmount.from_drops(price * 1_000_000),
                     sfTakerGets: STAmount.from_iou(
                         USD, gateway.account_id, 100, 0)},
                )
                live.append((gateway, seqs[gateway.account_id]))
            else:
                kp = traders[i % len(traders)]
                price = 40 + (i % 20)  # overlaps the asks -> crossings
                tx = build(
                    TxType.ttOFFER_CREATE, kp, seqs[kp.account_id],
                    {sfTakerPays: STAmount.from_iou(
                        USD, gateway.account_id, 100, 0),
                     sfTakerGets: STAmount.from_drops(price * 1_000_000)},
                )
                live.append((kp, seqs[kp.account_id]))
            seqs[tx.account] = tx.sequence + 1
            work.append(tx)
        stats = assert_identical([fund, trust, work])
        assert stats["spliced"] + stats["fallback"] > 0

    def test_tec_claim_and_held_promotion_mid_flood(self):
        """A below-reserve payment tec's (fee claim on the final pass
        only — splicing it early would renumber every later meta), and a
        seq-gap hold promotes after the close."""
        d = [KeyPair.from_passphrase(f"dr-h{i}").account_id for i in range(3)]
        phase1 = [
            payment(MASTER, 1, d[0]),
            payment(MASTER, 2, d[1], drops=1_000_000),  # below reserve: tec
            payment(MASTER, 3, d[2]),
            payment(MASTER, 5, d[0]),  # GAP: held as terPRE_SEQ
            payment(MASTER, 4, d[1]),  # fills the gap
        ]
        stats = assert_identical([phase1, []])  # close 2 applies the hold
        assert stats["closes"] == 2

    def test_spliced_deletions_offer_create_then_cancel(self):
        """One account creates offers then cancels them in the same
        ledger: the cancel's record carries entry DELETIONS (offer +
        directory pages) that must splice byte-identically."""
        maker = KeyPair.from_passphrase("dr-maker")
        fund = [payment(MASTER, 1, maker.account_id, 2_000_000_000)]
        work = []
        for i in range(4):
            work.append(build(
                TxType.ttOFFER_CREATE, maker, 1 + i,
                {sfTakerPays: STAmount.from_iou(
                    USD, MASTER.account_id, 10, 0),
                 sfTakerGets: STAmount.from_drops(5_000_000)},
            ))
        for i in range(4):
            work.append(build(TxType.ttOFFER_CANCEL, maker, 5 + i,
                              {sfOfferSequence: 1 + i}))
        stats = assert_identical([fund, work])
        # a single account's chain rides the canonical order untouched:
        # creates AND cancels (deletions) all splice
        assert stats["fallback"] == 0
        assert stats["spliced"] == len(fund) + len(work)

    def test_empty_and_repeat_closes(self):
        dests = [KeyPair.from_passphrase("dr-e").account_id]
        stats = assert_identical([[], [payment(MASTER, 1, dests[0])], []])
        # only the close that had open-accepted txs carries a spec state
        # (it is created lazily on first accept), so exactly one close
        # ran the replay context
        assert stats["closes"] == 1
        assert stats["spliced"] == 1


class TestParentGate:
    def test_close_against_different_parent_forces_full_fallback(self):
        """Records speculated against parent P must never splice into a
        close whose parent is P' (consensus moved the chain under us):
        the parent gate forces 100% fallback, and the result still
        matches a from-scratch serial apply."""
        dests = [KeyPair.from_passphrase(f"dr-p{i}").account_id
                 for i in range(3)]
        lm = LedgerMaster()
        lm.start_new_ledger(MASTER.account_id, close_time=1000)
        txs = [payment(MASTER, 1 + i, dests[i % 3]) for i in range(9)]
        for tx in txs:
            ter, ok = lm.do_transaction(fresh(tx), OPEN)
            assert ok, ter
        spec = lm.current._spec_state
        assert spec is not None and len(spec.records) == 9

        # a DIFFERENT parent with the same state: one empty close ahead
        lm2 = LedgerMaster()
        lm2.start_new_ledger(MASTER.account_id, close_time=1000)
        lm2.close_and_advance(2000, 30)
        parent = lm2.closed_ledger()
        assert parent.hash() != lm.closed_ledger().hash()

        def apply_onto(spec_arg):
            target = parent.open_successor()
            txset = CanonicalTXSet(parent.hash())
            for tx in txs:
                txset.insert(fresh(tx))
            results = lm2._apply_transactions(target, txset, spec=spec_arg)
            return target, sorted(
                (txid.hex(), int(ter)) for txid, ter in results.items()
            )

        led_replay, res_replay = apply_onto(spec)
        led_serial, res_serial = apply_onto(None)
        assert led_replay.state_map.get_hash() == led_serial.state_map.get_hash()
        assert led_replay.tx_map.get_hash() == led_serial.tx_map.get_hash()
        assert res_replay == res_serial
        assert lm2.delta_stats["spliced"] == 0
        assert lm2.delta_stats["fallback"] == 9
        assert lm2.last_close["parent_ok"] is False


class TestKnobAndCounters:
    def test_config_knob(self):
        cfg = Config.from_ini("[close]\ndelta_replay=0\n")
        assert cfg.close_delta_replay is False
        cfg = Config.from_ini("[close]\ndelta_replay=1\n")
        assert cfg.close_delta_replay is True
        assert Config().close_delta_replay is True

    def test_server_state_and_get_counts_expose_split(self):
        from stellard_tpu.node.node import Node
        from stellard_tpu.rpc.handlers import Context, Role, dispatch

        n = Node(Config(standalone=True, signature_backend="cpu")).setup()
        try:
            dest = KeyPair.from_passphrase("dr-rpc").account_id
            for i in range(5):
                ter, ok = n.submit(fresh(payment(MASTER, 1 + i, dest)))
                assert ok, ter
            n.close_ledger()

            state = dispatch(
                Context(n, {}, Role.ADMIN), "server_state"
            )["state"]
            assert state["delta_replay"]["enabled"] is True
            assert state["delta_replay"]["spliced"] == 5
            assert state["delta_replay"]["fallback"] == 0
            assert "apply_p50_ms" in state["delta_replay"]

            counts = dispatch(Context(n, {}, Role.ADMIN), "get_counts")
            assert counts["delta_replay"]["closes"] == 1
            assert "invalidated" in counts["delta_replay"]
        finally:
            n.stop()

    def test_disabled_knob_records_nothing(self):
        lm = LedgerMaster()
        lm.delta_replay = False
        lm.start_new_ledger(MASTER.account_id, close_time=1000)
        dest = KeyPair.from_passphrase("dr-off").account_id
        ter, ok = lm.do_transaction(fresh(payment(MASTER, 1, dest)), OPEN)
        assert ok, ter
        assert getattr(lm.current, "_spec_state", None) is None
        lm.close_and_advance(2000, 30)
        assert lm.delta_stats["closes"] == 0
