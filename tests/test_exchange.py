"""The exchange deployment (ISSUE 30): the cell ``node.offers``' own files
at the rehearsal's toy size, and what the program records of offers,
path payments, delta replay's fallbacks and the book index.

The plain reference is a ``cpu``/``hashlib`` node over the same stored
ledger loaded eagerly, with no speculation, no delta replay and the
full seal. The node under test resumes the store lazily with
speculation and delta replay on. Both are fed the generator's seeded
stream and must close every ledger to byte-identical hashes and
metadata.
"""

from __future__ import annotations

import json
import os
import sys
import types
from fractions import Fraction

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

from yardstick import exchange, exchangecheck, manifest, nodedrive  # noqa: E402
from yardstick import prepared, prepared_exchange, readers  # noqa: E402

from stellard_tpu.engine.deltareplay import SpecPolicy  # noqa: E402
from stellard_tpu.engine.engine import TxParams  # noqa: E402
from stellard_tpu.engine.flags import tfImmediateOrCancel  # noqa: E402
from stellard_tpu.engine.offers import get_rate  # noqa: E402
from stellard_tpu.node.config import Config  # noqa: E402
from stellard_tpu.node.ledgermaster import LedgerMaster  # noqa: E402
from stellard_tpu.node.node import Node  # noqa: E402
from stellard_tpu.node.tracer import Tracer  # noqa: E402
from stellard_tpu.paths.orderbook import Book, OrderBookDB  # noqa: E402
from stellard_tpu.protocol.formats import TxType  # noqa: E402
from stellard_tpu.protocol.keys import KeyPair  # noqa: E402
from stellard_tpu.protocol.sfields import (  # noqa: E402
    sfAmount, sfDestination, sfFlags, sfLimitAmount, sfOfferSequence,
    sfSendMax, sfTakerGets, sfTakerPays,
)
from stellard_tpu.protocol.stamount import STAmount, currency_from_iso  # noqa: E402
from stellard_tpu.protocol.sttx import SerializedTransaction  # noqa: E402
from stellard_tpu.protocol.ter import TER  # noqa: E402
from stellard_tpu.state import indexes  # noqa: E402
from stellard_tpu.state.ledger import Ledger  # noqa: E402
from stellard_tpu.state.shamap import inner_node_cache  # noqa: E402

SEED = 3000000019
CLOSES = 6


def cell_files():
    m = manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    return manifest.cell_files(m, "node.offers", REPO, rehearsal=True)


FILES = cell_files()
CONFIG, TRAFFIC = FILES["config"], FILES["traffic"]
# the cell's INI on the host arms (no JAX in a tier-1 test)
INI = nodedrive.plain_reference_ini(FILES["ini"])
# the plain reference: serial apply, no speculation, full seal
PLAIN_INI = INI + "\n[tree]\nincremental=0\n\n[close]\ndelta_replay=0\n"


class EveryWindow(SpecPolicy):
    """The tests of delta replay ON an exchange want every close to
    consult records; the node itself stops speculating there (PR 35:
    `tests/test_deltareplay.py` and the driver's rehearsal below hold
    that to the plain path). Steered here, not by an option."""

    def note_close(self, spliced, consulted):
        pass


@pytest.fixture(scope="module")
def market():
    return exchange.Market(CONFIG["population"])


@pytest.fixture(scope="module")
def store(tmp_path_factory, market):
    """-> (the prepared store's directory, the signed stream)."""
    cache = tmp_path_factory.mktemp("prepared")
    directory = prepared_exchange.ensure(CONFIG, INI, str(cache))
    entries = exchange.offer_stream(
        seed=SEED, market=market, params=TRAFFIC,
        count=CLOSES * int(TRAFFIC["close_every"]))
    return directory, entries


def drive(node, meta, entries, each_close=None):
    """Feed the stream, a close every ``close_every`` valid
    transactions -> [(hashes, metadata of every transaction)]."""
    pump = nodedrive.Pump(node, int(TRAFFIC["window"]),
                          closes_done=meta["closes_done"])
    out = []
    valid = 0
    for blob, planted, _kind, _sender, _txid in entries:
        pump.submit(SerializedTransaction.from_bytes(blob))
        valid += 0 if planted else 1
        if valid == int(TRAFFIC["close_every"]):
            led, results, _ms = pump.close()
            out.append(((led.hash(), led.account_hash, led.tx_hash),
                        sorted((t, meta_blob) for t, _b, meta_blob
                               in led.tx_entries()),
                        sorted((t, int(r)) for t, r in results.items())))
            if each_close is not None:
                each_close(led)
            valid = 0
    node.close_pipeline.flush(timeout=120)
    return out, pump


@pytest.fixture(scope="module")
def runs(store, tmp_path_factory):
    """The stream through the node under test and through the plain
    node: what each closed, the first's counters and last ledger."""
    directory, entries = store
    tmp = tmp_path_factory.mktemp("runs")

    workdir, meta = prepared.copy_for_run(directory, str(tmp / "spec"))
    ini = nodedrive.ini_text(INI, workdir=os.path.join(workdir, "db"),
                             start_up="load")
    node = Node(Config.from_ini(ini)).setup()
    node.ledger_master.spec_policy = EveryWindow()
    by_close = []
    try:
        resumed_index = dict(node.path_plane.index.counters())
        seed_spans = [ev for ev in node.tracer.chrome_trace()["traceEvents"]
                      if ev["name"] == "paths.index.seed"]
        spec, _pump = drive(
            node, meta, entries,
            lambda led: by_close.append(
                dict(node.ledger_master.last_close["fallback_by_reason"])))
        last = node.ledger_master.closed_ledger()
        got = types.SimpleNamespace(
            closes=spec, by_close=by_close, resumed_index=resumed_index,
            seed_spans=seed_spans,
            delta=node.ledger_master.delta_replay_json(),
            engine=node.ledger_master.engine_json(),
            index=node.path_plane.index,
            live_books=node.path_plane.books_if_current(last).books,
            scanned_books=OrderBookDB().setup(last).books,
            snapshot=exchangecheck.snapshot(last), meta=meta)
    finally:
        node.stop()
        inner_node_cache().clear()

    workdir, meta = prepared.copy_for_run(directory, str(tmp / "plain"))
    ini = nodedrive.ini_text(PLAIN_INI, workdir=os.path.join(workdir, "db"),
                             start_up="fresh")
    node = Node(Config.from_ini(ini)).setup()
    try:
        led = Ledger.load(node.nodestore,
                          bytes.fromhex(meta["last_ledger"]["hash"]),
                          hash_batch=node.hasher, lazy=False)
        node.ledger_master.load_ledger(led)
        got.plain, _pump = drive(node, meta, entries)
        got.plain_delta = node.ledger_master.delta_replay_json()
        got.plain_engine = node.ledger_master.engine_json()
    finally:
        node.stop()
    got.entries = entries
    return got


class TestToyExchange:
    def test_speculation_and_the_plain_path_close_alike(self, runs):
        assert len(runs.closes) == CLOSES
        for k, (mine, plain) in enumerate(zip(runs.closes, runs.plain)):
            assert mine[0] == plain[0], f"close {k}: hashes differ"
            assert mine[1] == plain[1], f"close {k}: metadata differs"
            assert mine[2] == plain[2], f"close {k}: results differ"
        assert runs.plain_delta["closes"] == 0  # no replay ran there
        assert runs.delta["closes"] == CLOSES

    def test_both_invalidations_fired_in_one_close(self, runs):
        assert any(c["succ_invalidated"] and c["read_invalidated"]
                   for c in runs.by_close), runs.by_close

    def test_fallbacks_by_reason_add_up(self, runs):
        by_reason = runs.delta["fallback_by_reason"]
        assert sum(by_reason.values()) == runs.delta["fallback"]
        assert runs.delta["spliced"] + runs.delta["fallback"] == sum(
            len(c[2]) for c in runs.closes)
        assert by_reason["succ_invalidated"] and by_reason["read_invalidated"]
        assert by_reason["no_record"] == by_reason["disabled"] == 0

    def test_a_maker_sends_several_transactions_in_one_ledger(
            self, runs, market):
        every = int(TRAFFIC["close_every"])
        valid = [e for e in runs.entries if not e[1]]
        first = [e[3] for e in valid[:every] if e[3] < market.first_taker]
        assert max(first.count(m) for m in set(first)) >= 3

    def test_counters_are_of_the_closed_ledgers_not_of_the_runs(self, runs):
        # a fallback runs its transactor twice and counts once: the
        # node that speculates and the plain node count alike
        assert runs.engine == runs.plain_engine
        offers = runs.engine["offers"]
        assert offers["created"] and offers["crossed"] and offers["replaced"]
        assert offers["cancelled"] and offers["book_steps"]
        assert runs.engine["flow"]["payments"]

    def test_the_book_index_is_seeded_at_resume_and_follows(self, runs):
        was = runs.resumed_index
        assert was["seeded"] == 1 and was["full_rebuilds"] == 0
        assert was["offers"] == was["state_offers_scanned"] == sum(
            1 for _ in exchange.Market(CONFIG["population"]).seeds())
        [span] = runs.seed_spans
        assert span["args"]["offers"] == was["offers"]
        assert span["args"]["ok"] is True
        now = runs.index.counters()
        assert now["full_rebuilds"] == 0
        assert now["incremental_advances"] + now["carries"] == CLOSES
        counted = {Book(*k): n for k, n in
                   exchangecheck.book_counts(runs.snapshot).items()}
        assert runs.live_books == runs.scanned_books == set(counted)
        assert runs.index.book_counts() == counted
        assert now["offers"] == sum(counted.values())


def issued_of(market):
    return {(market.currency_bytes(c),
             market.account_id(market.gateway_of(c))): Fraction(units)
            for c, units in enumerate(market.issued())}


class TestOwnArithmetic:
    def test_the_last_ledger_holds(self, runs, market):
        snap = runs.snapshot
        assert exchangecheck.conservation(snap, issued_of(market)) == []
        assert exchangecheck.owner_counts(snap) == []
        assert exchangecheck.crossed_books(snap) == []
        applied = sum(1 for c in runs.closes for _t, ter in c[2]
                      if ter == 0 or 100 <= ter < 200)
        assert exchangecheck.coins(
            snap, runs.meta["genesis_coins"],
            runs.meta["fees_burned"]
            + applied * int(TRAFFIC["fee_drops"])) == []

    def test_one_digit_of_one_balance_breaks_conservation(self, runs, market):
        snap = runs.snapshot
        k = next(i for i, line in enumerate(snap.lines) if line[3].mantissa)
        low, high, cur, bal, lo, hi = snap.lines[k]
        doctored = STAmount.from_iou(bal.currency, bal.issuer,
                                     bal.mantissa + 1, bal.offset,
                                     bal.negative)
        lines = list(snap.lines)
        lines[k] = (low, high, cur, doctored, lo, hi)
        bad = types.SimpleNamespace(**{**vars(snap), "lines": lines})
        problems = exchangecheck.conservation(bad, issued_of(market))
        assert len(problems) == 1 and "set-up issued" in problems[0]

    def test_one_drop_breaks_the_coins(self, runs):
        snap = runs.snapshot
        account, (drops, count) = next(iter(snap.accounts.items()))
        accounts = dict(snap.accounts)
        accounts[account] = (drops + 1, count)
        bad = types.SimpleNamespace(**{**vars(snap), "accounts": accounts})
        assert any("account roots hold" in p for p in exchangecheck.coins(
            bad, snap.coins, 0))
        assert any("genesis" in p for p in exchangecheck.coins(
            snap, snap.coins + 1, 0))

    def test_a_miscounted_owner_is_found(self, runs):
        snap = runs.snapshot
        account, (drops, count) = next(
            (a, v) for a, v in snap.accounts.items() if v[1])
        accounts = dict(snap.accounts)
        accounts[account] = (drops, count - 1)
        bad = types.SimpleNamespace(**{**vars(snap), "accounts": accounts})
        [problem] = exchangecheck.owner_counts(bad)
        assert account.hex()[:12] in problem

    def test_a_planted_crossable_pair_is_found(self, runs, market):
        snap = runs.snapshot
        maker, other = market.first_maker, market.first_maker + 1
        # an ask at 2 ticks UNDER the mid and a bid at 2 ticks OVER it
        ask = market.offer_amounts(0, exchange.ASK, -2, Fraction(10))
        bid = market.offer_amounts(0, exchange.BID, -2, Fraction(10))
        def placed(owner, amounts, seq):
            pays, gets = amounts
            return (market.account_id(owner), pays, gets, seq,
                    indexes.quality_index(
                        indexes.book_base(*exchangecheck.book_key(pays, gets)),
                        get_rate(gets, pays)))

        # alone in their books, so that only the planted pair can cross
        offers = {b"\x01" * 32: placed(maker, ask, 9001),
                  b"\x02" * 32: placed(other, bid, 9002)}
        bad = types.SimpleNamespace(**{**vars(snap), "offers": offers})
        problems = exchangecheck.crossed_books(bad)
        assert len(problems) == 1 and "cross" in problems[0]
        # the same pair with the bid's owner out of funds stands rightly
        poor = KeyPair.from_passphrase("exchange-test-poor").account_id
        accounts = dict(snap.accounts)
        accounts[poor] = (snap.reserve_base, 0)  # nothing over the reserve
        offers[b"\x02" * 32] = (poor, *offers[b"\x02" * 32][1:])
        unfunded = types.SimpleNamespace(
            **{**vars(snap), "offers": offers, "accounts": accounts})
        assert exchangecheck.crossed_books(unfunded) == []

    def test_a_books_walk_is_in_quality_then_entry_order(self, runs):
        snap = runs.snapshot
        key = max(exchangecheck.book_counts(snap).items(),
                  key=lambda kv: kv[1])[0]
        walked = exchangecheck.book_offers_in_order(snap, key)
        assert len(walked) == exchangecheck.book_counts(snap)[key] > 1
        assert {i for i, o in snap.offers.items()
                if exchangecheck.book_key(o[1], o[2]) == key} == set(walked)
        rates = [exchangecheck.rate_of(snap.offers[i][4]) for i in walked]
        assert rates == sorted(rates) and len(set(rates)) > 1


# --------------------------------------------------------------------------
# a hand-counted stream


MASTER = KeyPair.from_passphrase("masterpassphrase")
GW, ANN, BEN, CAT = (KeyPair.from_passphrase(f"exchange-test-{n}")
                     for n in ("gw", "ann", "ben", "cat"))
USD, EUR = currency_from_iso("USD"), currency_from_iso("EUR")
XRP = 1_000_000
OPEN = TxParams.OPEN_LEDGER | TxParams.RETRY


def usd(v):
    return STAmount.from_iou(USD, GW.account_id, v, 0)


def eur(v):
    return STAmount.from_iou(EUR, GW.account_id, v, 0)


def drops(v):
    return STAmount.from_drops(v * XRP)


class Stream:
    def __init__(self):
        self.seqs: dict = {}

    def tx(self, key, tx_type, fields):
        seq = self.seqs.get(key.account_id, 1)
        self.seqs[key.account_id] = seq + 1
        tx = SerializedTransaction.build(tx_type, key.account_id, seq, 10,
                                         fields)
        tx.sign(key)
        return tx

    def offer(self, key, pays, gets, more=None):
        return self.tx(key, TxType.ttOFFER_CREATE,
                       {sfTakerPays: pays, sfTakerGets: gets, **(more or {})})


def hand_counted_phases():
    """-> (phases of transactions, a close behind each; what the offer
    and flow counters must read behind the last)."""
    s = Stream()
    fund = [s.tx(MASTER, TxType.ttPAYMENT, {
        sfAmount: drops(100_000), sfDestination: k.account_id})
        for k in (GW, ANN, BEN, CAT)]
    trust = [s.tx(k, TxType.ttTRUST_SET, {sfLimitAmount: STAmount.from_iou(
        c, GW.account_id, 1_000_000, 0)})
        for k in (ANN, BEN, CAT) for c in (USD, EUR)]
    issue = [s.tx(GW, TxType.ttPAYMENT, {
        sfAmount: amt(10_000), sfDestination: k.account_id})
        for k in (ANN, BEN, CAT) for amt in (usd, eur)]
    # ann quotes three asks: 100 USD for 100, 101, 102 STR  (created 3)
    asks = [s.offer(ANN, drops(100 + i), usd(100)) for i in range(3)]
    # ben takes 150 USD at up to 1.02: the first whole, the second half
    # (crossed 2; one walk of the USD book and nothing left to place)
    take = [s.offer(BEN, usd(150), drops(153))]
    # ann replaces her third ask (sequence 5) (replaced 1, created 1) and
    # cancels the new one (cancelled 1); a cancel of a dead offer counts 0
    shuffle = [
        s.offer(ANN, drops(103), usd(100), {sfOfferSequence: 5}),
        s.tx(ANN, TxType.ttOFFER_CANCEL, {sfOfferSequence: 6}),
        s.tx(ANN, TxType.ttOFFER_CANCEL, {sfOfferSequence: 3}),
    ]
    # cat pays ben 10 USD with STR through the book (flow.payments 1,
    # crossed 1: the rest of ann's second ask), and an
    # immediate-or-cancel that finds its price nowhere (created 0)
    pay = [
        s.tx(CAT, TxType.ttPAYMENT, {
            sfAmount: usd(10), sfDestination: BEN.account_id,
            sfSendMax: drops(20)}),
        s.offer(CAT, usd(10), drops(5), {sfFlags: tfImmediateOrCancel}),
    ]
    # ann sells EUR for STR, ben buys EUR with USD: no USD/EUR book, so
    # he crosses ann's USD... there is none left under 1.01, so he takes
    # the bridge: his USD buy STR? no: nobody BUYS USD for STR. He rests.
    rest = [s.offer(BEN, eur(50), usd(50))]  # created 1
    phases = [fund, trust, issue, asks, take, shuffle, pay, rest]
    want = {"created": 3 + 1 + 1, "crossed": 2 + 1, "replaced": 1,
            "cancelled": 1, "removed_unfunded": 0, "bridged": 0}
    return phases, want


def run_phases(phases, delta_replay, tracer=None):
    lm = LedgerMaster(tracer=tracer) if tracer is not None else LedgerMaster()
    lm.delta_replay = delta_replay
    lm.start_new_ledger(MASTER.account_id, close_time=1000)
    for i, phase in enumerate(phases):
        for tx in phase:
            ter, _ok = lm.do_transaction(
                SerializedTransaction.from_bytes(tx.serialize()), OPEN)
            assert ter == TER.tesSUCCESS, (i, ter)
        _closed, results = lm.close_and_advance(2000 + i * 30, 30)
        assert all(int(t) == 0 for t in results.values()), (i, results)
    return lm


class TestHandCountedStream:
    @pytest.mark.parametrize("delta_replay", [True, False],
                             ids=["speculated", "serial"])
    def test_offer_and_flow_counters(self, delta_replay):
        phases, want = hand_counted_phases()
        lm = run_phases(phases, delta_replay)
        got = lm.engine_json()
        for name, n in want.items():
            assert got["offers"][name] == n, (name, got)
        assert got["flow"]["payments"] == 1
        # every walk ends on the step that finds no further directory
        assert got["offers"]["book_steps"] >= 3
        assert got["flow"]["book_steps"] >= 2

    def test_spans_carry_the_type_and_the_walk(self):
        phases, _want = hand_counted_phases()
        tracer = Tracer(sample=1.0)
        run_phases(phases, True, tracer=tracer)
        events = tracer.chrome_trace()["traceEvents"]
        by_name: dict = {}
        for ev in events:
            by_name.setdefault(ev["name"], []).append(ev)
        n_tx = sum(len(p) for p in phases)
        for name in ("open.apply", "open.speculate", "close.tx"):
            assert len(by_name[name]) == n_tx
            kinds = {ev["args"]["type"] for ev in by_name[name]}
            assert kinds == {"ttPAYMENT", "ttTRUST_SET", "ttOFFER_CREATE",
                             "ttOFFER_CANCEL"}
        crosses = by_name["offer.cross"]
        # one a speculated OfferCreate (7), each under its open.speculate
        assert len(crosses) == 7
        speculate = {ev["args"]["span"] for ev in by_name["open.speculate"]}
        assert all(ev["args"]["parent"] in speculate for ev in crosses)
        took = [ev["args"] for ev in crosses if ev["args"]["consumed"]]
        assert [(a["consumed"], a["bridged"]) for a in took] == [(2, 0)]
        assert all(a["steps"] >= 1 for a in (ev["args"] for ev in crosses))
        [flow] = by_name["flow.payment"]
        assert flow["args"]["strands"] == 1 and flow["args"]["book_steps"] >= 2
        assert flow["args"]["parent"] in speculate


class TestIndexAdvanceSpan:
    def test_one_span_a_close_with_what_moved(self):
        from stellard_tpu.paths.plane import PathPlane

        phases, _want = hand_counted_phases()
        tracer = Tracer(sample=1.0)
        plane = PathPlane(tracer=tracer)
        lm = LedgerMaster()
        lm.start_new_ledger(MASTER.account_id, close_time=1000)
        seen = []
        for i, phase in enumerate(phases):
            for tx in phase:
                lm.do_transaction(tx, OPEN)
            closed, _results = lm.close_and_advance(2000 + i * 30, 30)
            plane.note_close(closed)
            seen.append(plane.index.offers)
        spans = [ev["args"] for ev in tracer.chrome_trace()["traceEvents"]
                 if ev["name"] == "paths.index.advance"]
        assert len(spans) == len(phases)
        assert [a["full_rebuild"] for a in spans] == [1] + [0] * 7
        # offers standing behind each close: three asks; the second's
        # rest of 50 and the third; the rest alone (the third replaced,
        # its replacement cancelled); still it (the payment took 10 of
        # it, which moves no count); it and ben's
        assert seen == [0, 0, 0, 3, 2, 1, 1, 2]
        assert [a["offers_delta"] for a in spans] == [0, 0, 0, 3, -1, -1, 0, 1]
        assert [a["books_reread"] for a in spans] == [0, 0, 0, 1, 1, 1, 0, 1]


# --------------------------------------------------------------------------
# the generator


class TestOfferStream:
    def test_the_mix_the_senders_and_the_seed(self, store, market):
        _directory, entries = store
        valid = [e for e in entries if not e[1]]
        assert len(valid) == CLOSES * int(TRAFFIC["close_every"])
        share = {k: sum(1 for e in valid if e[2] == k) / len(valid)
                 for k in exchange.KINDS}
        mix = TRAFFIC["mix"]
        for kind in exchange.KINDS:
            assert abs(share[kind] - mix[kind] / 100) < 0.06, share
        gap = int(TRAFFIC["sender_gap"])
        senders = [e[3] for e in valid]
        for k in range(len(senders) - gap):
            span = senders[k:k + gap + 1]
            assert span.count(span[-1]) == 1, k
        planted = [e for e in entries if e[1]]
        assert len(planted) == (len(valid) + 1023) // 1024 * int(
            TRAFFIC["planted_per_1024"]) or len(planted) > 0
        one, again, other = (
            [e[4] for e in exchange.offer_stream(
                seed=seed, market=market, params=TRAFFIC, count=64)]
            for seed in (SEED, SEED, SEED + 1))
        assert one == again != other

    def test_every_pair_is_held_by_enough_takers_at_full_size(self):
        with open(os.path.join(BENCH, "configs", "exchange-books.json")) as fh:
            pop = json.load(fh)["population"]
        full = exchange.Market(pop)
        assert len(full.pairs) == 136 and full.books == 272
        assert full.lines() == 40_960
        assert sum(1 for _ in full.seeds()) == 17_408
        assert min(len(full.holders(p)) for p in range(136)) >= 128
        by_maker: dict = {}
        for maker, _p, _s, _l in full.seeds():
            by_maker[maker] = by_maker.get(maker, 0) + 1
        assert set(by_maker.values()) == {34}
        assert full.setup_transactions(full.first_maker) == 16 + 34
        assert full.setup_transactions(full.first_taker) == 4
        assert sum(full.setup_transactions(g) for g in range(8)) == 40_960


# --------------------------------------------------------------------------
# the readers of the cell's per-layer metrics


def read(metric, sources):
    return readers.read_metric(manifest.reader_file(BENCH, metric), sources)


COUNTERS = {
    "replay.spliced": 600, "replay.fallback": 400,
    "replay.fallback.succ_invalidated": 50,
    "replay.fallback.read_invalidated": 300,
    "offers.crossed": 390, "sent.cross": 260,
    "offers.book_steps": 2000, "flow.book_steps": 400,
    "sent.rest": 450, "sent.cross ": 0, "sent.xpay": 90,
    "book.depth_mean": 48.0, "book.seed_depth": 64,
}
WANT = {
    "apply.spliced_share": 60.0,
    "apply.succ_invalidated_share": 5.0,
    "apply.read_invalidated_share": 30.0,
    "offers.crossed_per_taker": 1.5,
    "offers.book_steps_per_tx": 3.0,
    "book.depth_end_share": 75.0,
}


def span(name, sid, ts, dur, parent=None, **args):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur,
            "args": {"span": sid, "parent": parent, **args}}


SPANS = [
    # a sampled OfferCreate: 300 us of open.apply, 1,000 of
    # open.speculate of which 600 in the book walk
    span("open.apply", 1, 0, 300, trace="t1", type="ttOFFER_CREATE"),
    span("open.speculate", 2, 400, 1000, trace="t1", type="ttOFFER_CREATE"),
    span("offer.cross", 3, 500, 600, parent=2, trace="t1"),
    # a payment through a book
    span("open.apply", 4, 2000, 200, trace="t2", type="ttPAYMENT"),
    span("open.speculate", 5, 2300, 900, trace="t2", type="ttPAYMENT"),
    span("flow.payment", 6, 2400, 700, parent=5, trace="t2"),
    # the OfferCreate fell back at the close: that walk is the close's
    span("offer.cross", 7, 9000, 500, trace="t1"),
    span("paths.index.advance", 8, 9600, 2000),
    span("paths.index.advance", 9, 19600, 4000),
]


class TestLayerReaders:
    @pytest.mark.parametrize("metric", sorted(WANT))
    def test_counter_readers(self, metric):
        assert read(metric, {"counters": COUNTERS}) == pytest.approx(
            WANT[metric])
        assert read(metric, {"counters": {}}) is None

    def test_span_readers(self):
        sources = {"spans": SPANS, "counters": {}}
        assert read("offers.ms_per_tx", sources) == pytest.approx(1.3)
        assert read("flow.ms_per_payment", sources) == pytest.approx(0.7)
        assert read("book.index_ms_per_close", sources) == pytest.approx(3.0)

    @pytest.mark.parametrize("metric", [
        "offers.ms_per_tx", "flow.ms_per_payment", "book.index_ms_per_close"])
    def test_a_program_without_the_spans_gives_nothing(self, metric):
        untyped = [dict(ev, args={k: v for k, v in ev["args"].items()
                                  if k != "type"})
                   for ev in SPANS if ev["name"].startswith("open.")]
        assert read(metric, {"spans": untyped, "counters": {}}) is None
        assert read(metric, {"spans": [], "counters": {}}) is None

    def test_the_manifest_lists_the_cell_and_its_metrics(self):
        m = manifest.load(os.path.join(REPO, "BENCHMARK.json"))
        manifest.validate(m, REPO)
        mine = {x["name"] for x in manifest.metrics_of(
            m, "node.offers", "per_layer")}
        assert set(WANT) | {"offers.ms_per_tx", "flow.ms_per_payment",
                            "book.index_ms_per_close"} <= mine
        assert {x["name"] for x in manifest.metrics_of(
            m, "node.offers", "end_to_end")} == {
                "validated_tx_per_s", "setup_s"}


# --------------------------------------------------------------------------
# the cell's driver, at the rehearsal's sizes, on the host arms


class TestDriverRehearsal:
    def test_the_driver_runs_the_cell_and_holds_it_correct(
            self, store, tmp_path):
        import importlib.util

        directory, _entries = store
        spec = importlib.util.spec_from_file_location(
            "exchange_driver", FILES["driver_path"])
        driver = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(driver)
        from yardstick.capture import Capture

        said = []
        ctx = types.SimpleNamespace(
            # four closes in the window: behind the warm-up's futile
            # close three windows do not speculate, the fourth probes,
            # and the three `apply.*_share` metrics have a close to read
            seed=SEED + 7, seconds=3.5, trace=False, rehearsal=True,
            config=CONFIG, ini_template=INI, traffic=TRAFFIC,
            cache_dir=os.path.dirname(os.path.dirname(directory)),
            work_root=str(tmp_path / "work"), say=said.append)
        ctx.capture = lambda: Capture(False, str(tmp_path / "trace"))
        try:
            result = driver.run(ctx)
        finally:
            inner_node_cache().clear()
        assert result["problems"] == []
        assert result["correct"] is True
        counters = result["sources"]["counters"]
        assert result["attempted"] == sum(
            counters[f"sent.{k}"] for k in exchange.KINDS)
        assert result["failed"] == counters["tec"]
        assert counters["tec"] <= 0.02 * result["attempted"]
        for metric in WANT:
            assert read(metric, result["sources"]) is not None, metric
        assert 1.0 <= read("offers.crossed_per_taker",
                           result["sources"]) <= 4.0
        assert result["end_to_end"]["validated_tx_per_s"] > 0
