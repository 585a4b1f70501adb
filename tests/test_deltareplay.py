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

import os
import sys

import pytest

from stellard_tpu.engine import deltareplay
from stellard_tpu.engine.deltareplay import SpecPolicy
from stellard_tpu.engine.engine import TxParams
from stellard_tpu.node.config import Config
from stellard_tpu.node.ledgermaster import CanonicalTXSet, LedgerMaster
from stellard_tpu.node.tracer import Tracer
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
        # `last_close` is the LAST close's: read before the serial twin
        assert lm2.last_close["parent_ok"] is False
        led_serial, res_serial = apply_onto(None)
        assert led_replay.state_map.get_hash() == led_serial.state_map.get_hash()
        assert led_replay.tx_map.get_hash() == led_serial.tx_map.get_hash()
        assert res_replay == res_serial
        assert lm2.delta_stats["spliced"] == 0
        assert lm2.delta_stats["fallback"] == 9


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


# -- the open window stops speculating what the close throws away -----------


class TestSpecPolicy:
    """Shares in, speculate or not out."""

    def test_speculates_until_told_otherwise(self):
        policy = SpecPolicy()
        assert [policy.open_window() for _ in range(5)] == [True] * 5
        assert policy.get_json() == {
            "speculating": True, "futile_streak": 0, "last_share": None}

    @pytest.mark.parametrize("shares, want", [
        # futile -> three skipped -> probe; a futile probe starts again
        ([0.06, 0.9, 0.9, 0.9, 0.05, 0.9, 0.9],
         [False, False, False, True, False, False, False]),
        # a good probe restores every window
        ([0.06, 0.06, 0.06, 0.06, 0.44, 0.37, 0.58],
         [False, False, False, True, True, True, True]),
        # the payment deployments never skip
        ([0.37, 0.53, 0.58, 0.44, 0.47], [True] * 5),
    ])
    def test_futile_three_skipped_then_a_probe(self, shares, want):
        # a skipped window's close consults nothing and says nothing:
        # only the closes of speculating windows are fed
        policy = SpecPolicy()
        got = []
        speculating = True
        for share in shares:
            if speculating:
                policy.note_close(round(share * 2048), 2048)
            speculating = policy.open_window()
            got.append(speculating)
        assert got == want

    def test_a_close_under_64_records_changes_nothing(self):
        policy = SpecPolicy()
        policy.note_close(0, deltareplay.MIN_CONSULTED - 1)
        assert policy.open_window() is True
        assert policy.last_share is None
        policy.note_close(0, deltareplay.MIN_CONSULTED)
        assert policy.futile_streak == 1 and policy.last_share == 0.0
        # ... nor does an idle ledger lift a skip that is under way
        policy.note_close(8, 8)
        got = [policy.open_window() for _ in range(4)]
        assert got == [False, False, False, True]
        assert policy.futile_streak == 1

    @pytest.mark.parametrize("spliced, futile", [
        (255, True), (256, False), (257, False)])
    def test_the_constants_edge(self, spliced, futile):
        """One record in eight: 256 of 2,048 is not futile, 255 is."""
        assert deltareplay.FUTILE_SHARE == 0.125
        assert deltareplay.SKIP_WINDOWS == 3
        policy = SpecPolicy()
        policy.note_close(spliced, 2048)
        assert policy.open_window() is not futile
        assert policy.get_json()["speculating"] is not futile
        assert policy.futile_streak == int(futile)


GATEWAY = KeyPair.from_passphrase("pol-gw")
TRADERS = [KeyPair.from_passphrase(f"pol-t{i}") for i in range(16)]
PAYERS = [KeyPair.from_passphrase(f"pol-p{i}") for i in range(64)]
ONE_USD = STAmount.from_iou(USD, GATEWAY.account_id, 1, 0)


class Traffic:
    """Two mixes over one set of accounts: `offers` all meet in one
    book (bids at one price and the gateway's asks that cross them: in
    canonical order nearly every record reads a page another writer
    wrote, and falls back), `payments` are disjoint (every record
    splices)."""

    def __init__(self):
        everyone = [GATEWAY] + TRADERS + PAYERS
        self.seqs = {kp.account_id: 1 for kp in everyone}
        self.setup = [
            [payment(MASTER, 1 + i, who.account_id, 5_000_000_000)
             for i, who in enumerate(everyone)],
            [self._tx(TxType.ttTRUST_SET, t, {
                sfLimitAmount: STAmount.from_iou(
                    USD, GATEWAY.account_id, 10**9, 0)})
             for t in TRADERS],
        ]

    def _tx(self, tx_type, kp, fields):
        tx = build(tx_type, kp, self.seqs[kp.account_id], fields)
        self.seqs[kp.account_id] += 1
        return tx

    def offers(self, n=80):
        out = []
        for i in range(n):
            if i % 5 == 4:
                out.append(self._tx(TxType.ttOFFER_CREATE, GATEWAY, {
                    sfTakerPays: STAmount.from_drops(50_000_000),
                    sfTakerGets: ONE_USD}))
            else:
                out.append(self._tx(
                    TxType.ttOFFER_CREATE, TRADERS[i % len(TRADERS)], {
                        sfTakerPays: ONE_USD,
                        sfTakerGets: STAmount.from_drops(50_000_000)}))
        return out

    def payments(self, n=64):
        return [
            self._tx(TxType.ttPAYMENT, kp, {
                sfAmount: STAmount.from_drops(250_000_000),
                sfDestination: KeyPair.from_passphrase(
                    f"pol-d{i}").account_id})
            for i, kp in enumerate(PAYERS[:n])
        ]


def closed_as(closed, results):
    """What a close is held to: hash, results, every transaction's
    metadata."""
    return (
        closed.hash(),
        sorted((txid, int(ter)) for txid, ter in results.items()),
        sorted((txid, meta) for txid, _blob, meta in closed.tx_entries()),
    )


def close_apply_spans(tracer):
    return [ev["args"] for ev in tracer.chrome_trace()["traceEvents"]
            if ev["name"] == "close.apply"]


class TestWindowsThatDoNotSpeculate:
    def test_skipped_probing_and_speculating_windows_close_alike(self):
        """Offers and payments in alternating stretches: every ledger
        equals its `delta_replay=0` twin's, a skipped window has no
        SpecState and leaves the consulted closes' counters alone, and
        `close.apply` says what each close had to consult."""
        traffic = Traffic()
        o, p = traffic.offers, traffic.payments
        # the window's mix, and whether it speculates: futile offers ->
        # three skipped -> a futile probe -> three skipped -> a good
        # probe -> every window, until the offers are back
        plan = [(o, True), (o, False), (o, False), (o, False),
                (o, True), (p, False), (p, False), (o, False),
                (p, True), (p, True), (o, True), (p, False)]
        phases = traffic.setup + [mix() for mix, _ in plan]
        want = [True, True] + [speculates for _, speculates in plan]

        tracer = Tracer(sample=1.0)
        lm = LedgerMaster(tracer=tracer)
        twin = LedgerMaster(tracer=Tracer(enabled=False))
        twin.delta_replay = False
        for chain in (lm, twin):
            chain.start_new_ledger(MASTER.account_id, close_time=1000)
        for i, (phase, speculates) in enumerate(zip(phases, want)):
            assert lm.spec_policy.speculating is speculates, i
            before = dict(lm.delta_stats)
            for tx in phase:
                for chain in (lm, twin):
                    ter, ok = chain.do_transaction(fresh(tx), OPEN)
                    assert ok, (i, ter)
            spec = getattr(lm.current, "_spec_state", None)
            if speculates:
                assert len(spec.records) == len(phase), i
            else:
                assert spec is None, i
            mine = lm.close_and_advance(2000 + i * 30, 30)
            theirs = twin.close_and_advance(2000 + i * 30, 30)
            assert closed_as(*mine) == closed_as(*theirs), i
            after = dict(lm.delta_stats)
            if not speculates:
                assert after.pop("txs_unspeculated") \
                    == before.pop("txs_unspeculated") + len(phase)
                # (`windows_skipped` moves where a window OPENS)
                assert after.pop("windows_skipped") \
                    >= before.pop("windows_skipped")
                assert after == before, i
                assert set(lm.last_close) == {
                    "txs", "speculated", "apply_ms", "seal_ms", "total_ms"}
            else:
                assert after["closes"] == before["closes"] + 1
                assert after["spliced"] + after["fallback"] \
                    == before["spliced"] + before["fallback"] + len(phase)
        # ... and the window open behind the last close is the second
        # of its three
        assert lm.delta_stats["windows_skipped"] == want.count(False) + 1
        assert twin.delta_stats["windows_skipped"] == 0
        assert twin.delta_stats["txs_unspeculated"] == 0
        assert [(a["txs"], a["speculated"])
                for a in close_apply_spans(tracer)] == [
            (len(phase), len(phase) if speculates else 0)
            for phase, speculates in zip(phases, want)]
        policy = lm.delta_replay_json()["policy"]
        assert policy == {"speculating": False, "futile_streak": 1,
                          "last_share": policy["last_share"]}
        assert policy["last_share"] < deltareplay.FUTILE_SHARE

    @pytest.mark.parametrize("skip_left, speculates", [(1, False),
                                                       (0, True)])
    def test_leftovers_of_a_consensus_close(self, skip_left, speculates):
        """`close_with_txset` re-applies what missed the agreed set to
        the NEXT open ledger: into a SpecState where that window
        speculates, into none where it does not."""
        traffic = Traffic()
        chains = []
        for delta_replay in (True, False):
            lm = LedgerMaster(tracer=Tracer(enabled=False))
            lm.delta_replay = delta_replay
            lm.start_new_ledger(MASTER.account_id, close_time=1000)
            chains.append(lm)
        lm, twin = chains
        phase = traffic.setup[0]
        agreed, left = phase[:40], phase[40:]
        lm.spec_policy.skip_left = skip_left
        closes = []
        for chain in chains:
            for tx in phase:
                ter, ok = chain.do_transaction(fresh(tx), OPEN)
                assert ok, ter
            closes.append([
                closed_as(*chain.close_with_txset(
                    [fresh(tx) for tx in agreed], 2000, 30)),
                closed_as(*chain.close_and_advance(2030, 30)),
            ])
        assert closes[0] == closes[1]
        assert len(closes[0][1][1]) == len(left)
        stats = dict(lm.delta_stats)
        assert stats["windows_skipped"] == skip_left
        assert stats["txs_unspeculated"] == (0 if speculates else len(left))
        # the first close consulted its 40; the second the leftovers'
        # records, where their window made any
        assert stats["closes"] == 1 + speculates
        assert stats["spliced"] == len(agreed) + speculates * len(left)

    def test_promoted_transactions_of_a_window_that_does_not_speculate(self):
        """The TxQ's deferred speculation (`origin="promote"`) passes
        the same gate: what it promotes into a skipped window is applied
        at the close on the plain path, and closes as on a node that
        never speculates."""
        from stellard_tpu.node.node import Node

        senders = [KeyPair.from_passphrase(f"pol-q{i}") for i in range(12)]
        hashes = []
        for delta_replay in (True, False):
            node = Node(Config(txq_min_cap=4, txq_max_cap=4,
                               close_delta_replay=delta_replay)).setup()
            try:
                node.txq.spec_dispatch = None  # inline: deterministic
                closes = [0]
                node.ops.network_time = \
                    lambda: 900_000_000 + closes[0] * 30
                lm = node.ledger_master
                for i, s in enumerate(senders):
                    # a fee that beats any escalation of so small a cap
                    ter, ok = node.submit(build(
                        TxType.ttPAYMENT, MASTER, 1 + i,
                        {sfAmount: STAmount.from_drops(2_000_000_000),
                         sfDestination: s.account_id}, fee=10_000_000))
                    assert ok, ter
                mine = []
                for rnd in range(5):
                    if rnd == 1:
                        # 4 enter the open ledger, 8 queue; the next
                        # two windows (their promotions) do not speculate
                        for i, s in enumerate(senders):
                            node.submit(payment(
                                s, 1, KeyPair.from_passphrase(
                                    f"pol-qd{i}").account_id, 250_000_000))
                        lm.spec_policy.skip_left = 2
                    closes[0] += 1
                    closed, _results = node.ops.accept_ledger()
                    node.txq.quiesce()
                    mine.append(closed.hash())
                hashes.append(mine)
                j = node.txq.get_json()
                assert j["promoted"] == 8
                if delta_replay:
                    stats = dict(lm.delta_stats)
                    assert stats["windows_skipped"] == 2
                    assert stats["txs_unspeculated"] == 8
                    assert j["promote_spliced"] == 0
                    assert j["deferred_specs"] == 8
            finally:
                node.stop()
        assert hashes[0] == hashes[1]


# -- the benchmark's reader of `close.apply` ---------------------------------

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def read_skipped_share(spans):
    sys.path.insert(0, BENCH)
    try:
        from yardstick import manifest, readers

        return readers.read_metric(
            manifest.reader_file(BENCH, "apply.spec_skipped_share"),
            {"counters": {}, "spans": spans})
    finally:
        sys.path.remove(BENCH)


def apply_span(ts, **args):
    return {"ph": "X", "name": "close.apply", "ts": ts, "dur": 1000,
            "tid": 1, "args": {"span": ts, **args}}


@pytest.mark.parametrize("closes, want", [
    ([(2048, 2048), (2048, 2048)], 0.0),
    ([(2048, 0), (2048, 0), (2048, 0), (2048, 2048)], 75.0),
    ([(2048, 0), (100, 0)], 100.0),
    ([(2048, 2040), (0, 0)], pytest.approx(100 * 8 / 2048)),
])
def test_spec_skipped_share_reader(closes, want):
    spans = [apply_span(i, txs=txs, speculated=speculated)
             for i, (txs, speculated) in enumerate(closes)]
    spans.append({"ph": "X", "name": "close.seal", "ts": 99, "dur": 5,
                  "tid": 1, "args": {"span": 99, "txs": 7}})
    assert read_skipped_share(spans) == want


@pytest.mark.parametrize("spans", [
    [],  # an empty window
    [apply_span(0), apply_span(1)],  # the parent's spans: no `txs`
    [apply_span(0, txs=2048, speculated=0), apply_span(1)],
    [apply_span(0, txs=0, speculated=0)],  # nothing closed
])
def test_spec_skipped_share_reader_finds_nothing(spans):
    assert read_skipped_share(spans) is None
