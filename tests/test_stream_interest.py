"""The streams publish what somebody listens to (ISSUE 33).

`SubscriptionManager` keeps a summary of what its registered subscribers
listen to and builds a transaction's message only where the summary says
somebody receives it, once. These tests hold it to the algorithm it
replaced, which is written out below as plainly as it can be
(`eager_closed` / `eager_proposed`: a message for EVERY transaction,
then every subscriber asked whether it wants it) and shares no code with
`rpc/infosub.py`:

(a) over mixes of subscribers, sharded and inline, every subscriber
    receives exactly the eager builder's list, in order;
(b) with no transaction listener nothing of a transaction is walked,
    parsed or rendered, and `ledgerClosed` still reaches the ring and
    the `ledger` subscribers; with account listeners only, what is
    built is what hits;
(c) the summary cannot go stale: after every way the registry changes
    the NEXT published transaction goes where the registry then says;
(d) the `subs.publish` span and the `subs.fanout.tx` instants.

No node and no chip: the ledgers come from a plain `LedgerMaster`.
"""

from __future__ import annotations

import types

import pytest

from stellard_tpu.engine.engine import TxParams
from stellard_tpu.node.ledgermaster import LedgerMaster
from stellard_tpu.node.tracer import Tracer
from stellard_tpu.protocol.formats import TxType
from stellard_tpu.protocol.keys import KeyPair
from stellard_tpu.protocol.meta import affected_accounts
from stellard_tpu.protocol.sfields import (
    sfAmount, sfDestination, sfLimitAmount, sfTakerGets, sfTakerPays,
)
from stellard_tpu.protocol.stamount import STAmount, currency_from_iso
from stellard_tpu.protocol.stobject import STObject
from stellard_tpu.protocol.sttx import SerializedTransaction
from stellard_tpu.protocol.ter import TER
from stellard_tpu.rpc import infosub
from stellard_tpu.rpc.infosub import InfoSub, SubscriptionManager
from stellard_tpu.state.ledger import Ledger

MASTER = KeyPair.from_passphrase("masterpassphrase")
GW, ANN, BEN, CAT, DAN = (KeyPair.from_passphrase(f"stream-interest-{n}")
                          for n in ("gw", "ann", "ben", "cat", "dan"))
USD = currency_from_iso("USD")
XRP = 1_000_000
OPEN = TxParams.OPEN_LEDGER | TxParams.RETRY


# --------------------------------------------------------------------------
# a small history: payments, trust lines, an offer that is crossed


def _history():
    """-> (LedgerMaster, [(closed ledger, results, [(tx, ter) proposed])]).
    The last ledger holds BEN's OfferCreate crossing ANN's resting ask:
    ANN appears in that transaction's metadata and nowhere in it."""
    seqs: dict = {}

    def tx(key, tx_type, fields):
        seq = seqs.get(key.account_id, 1)
        seqs[key.account_id] = seq + 1
        t = SerializedTransaction.build(tx_type, key.account_id, seq, 10,
                                        fields)
        t.sign(key)
        return t

    def usd(v):
        return STAmount.from_iou(USD, GW.account_id, v, 0)

    def drops(v):
        return STAmount.from_drops(v * XRP)

    phases = [
        [tx(MASTER, TxType.ttPAYMENT, {sfAmount: drops(100_000),
                                       sfDestination: k.account_id})
         for k in (GW, ANN, BEN, CAT)],
        [tx(k, TxType.ttTRUST_SET, {sfLimitAmount: STAmount.from_iou(
            USD, GW.account_id, 1_000_000, 0)}) for k in (ANN, BEN)]
        + [tx(CAT, TxType.ttPAYMENT, {sfAmount: drops(50),
                                      sfDestination: ANN.account_id})],
        [tx(GW, TxType.ttPAYMENT, {sfAmount: usd(10_000),
                                   sfDestination: k.account_id})
         for k in (ANN, BEN)]
        + [tx(ANN, TxType.ttOFFER_CREATE, {sfTakerPays: drops(100),
                                           sfTakerGets: usd(100)})],
        [tx(BEN, TxType.ttOFFER_CREATE, {sfTakerPays: usd(50),
                                         sfTakerGets: drops(51)}),
         tx(CAT, TxType.ttPAYMENT, {sfAmount: drops(5),
                                    sfDestination: BEN.account_id})],
    ]
    lm = LedgerMaster()
    lm.start_new_ledger(MASTER.account_id, close_time=1000)
    out = []
    for i, phase in enumerate(phases):
        proposed = []
        for t in phase:
            t = SerializedTransaction.from_bytes(t.serialize())
            ter, _ok = lm.do_transaction(t, OPEN)
            assert ter == TER.tesSUCCESS, (i, ter)
            proposed.append((t, ter))
        closed, results = lm.close_and_advance(2000 + i * 30, 30)
        assert len(results) == len(phase)
        out.append((closed, results, proposed))
    return lm, out


@pytest.fixture(scope="module")
def history():
    return _history()


def _meta_only_account(history) -> bytes:
    """ANN, checked: in the crossing OfferCreate's metadata, not in the
    transaction."""
    closed, _results, _proposed = history[1][-1]
    for _txid, blob, meta in closed.tx_entries():
        tx = SerializedTransaction.from_bytes(blob)
        if tx.account == BEN.account_id:
            assert tx.obj.get(sfDestination) is None
            assert ANN.account_id in affected_accounts(meta)
            return ANN.account_id
    raise AssertionError("no crossing offer in the last ledger")


# --------------------------------------------------------------------------
# the plain eager builder: the algorithm the interest summary replaced


class Want:
    """One subscriber's interests, as the tests state them."""

    def __init__(self, streams=(), accounts=(), accounts_proposed=()):
        self.streams = set(streams)
        self.accounts = set(accounts)
        self.accounts_proposed = set(accounts_proposed)


def _eager_tx_message(tx, ter, ledger, validated, meta):
    j = tx.obj.to_json()
    j["hash"] = tx.txid().hex().upper()
    msg = {
        "type": "transaction",
        "transaction": j,
        "status": "closed" if validated else "proposed",
        "engine_result": ter.token,
        "engine_result_code": int(ter),
        "engine_result_message": ter.human,
        "validated": validated,
    }
    if ledger is not None:
        msg["ledger_index"] = ledger.seq
        msg["ledger_hash"] = ledger.hash().hex().upper()
    if meta:
        msg["meta"] = STObject.from_bytes(meta).to_json()
    touched = {tx.account}
    if tx.obj.get(sfDestination):
        touched.add(tx.obj.get(sfDestination))
    if meta:
        touched.update(affected_accounts(meta))
    return msg, touched


def _eager_wants(want, validated, touched) -> bool:
    wants = False
    if validated and "transactions" in want.streams:
        wants = True
    if not validated and ("transactions_proposed" in want.streams
                          or "rt_transactions" in want.streams):
        wants = True
    if want.accounts & touched and validated:
        wants = True
    if want.accounts_proposed & touched:
        wants = True
    return wants


def eager_closed(ledger, results, wants) -> list[list]:
    """What each of `wants` receives for one closed ledger."""
    out = [[] for _ in wants]
    closed = {
        "type": "ledgerClosed",
        "ledger_index": ledger.seq,
        "ledger_hash": ledger.hash().hex().upper(),
        "ledger_time": ledger.close_time,
        "fee_base": ledger.base_fee,
        "fee_ref": ledger.reference_fee_units,
        "reserve_base": ledger.reserve_base,
        "reserve_inc": ledger.reserve_increment,
        "txn_count": len(results),
    }
    for i, want in enumerate(wants):
        if "ledger" in want.streams:
            out[i].append(closed)
    for txid, blob, meta in ledger.tx_entries():
        tx = SerializedTransaction.from_bytes(blob)
        msg, touched = _eager_tx_message(
            tx, results.get(txid, TER.tesSUCCESS), ledger, True, meta)
        for i, want in enumerate(wants):
            if _eager_wants(want, True, touched):
                out[i].append(msg)
    return out


def eager_proposed(tx, ter, wants) -> list[list]:
    out = [[] for _ in wants]
    msg, touched = _eager_tx_message(tx, ter, None, False, b"")
    for i, want in enumerate(wants):
        if _eager_wants(want, False, touched):
            out[i].append(msg)
    return out


def eager_history(history, wants) -> list[list]:
    """The whole history as the node would publish it: a ledger's
    transactions as proposed, then the ledger closed."""
    out = [[] for _ in wants]
    for closed, results, proposed in history[1]:
        for tx, ter in proposed:
            for i, msgs in enumerate(eager_proposed(tx, ter, wants)):
                out[i].extend(msgs)
        for i, msgs in enumerate(eager_closed(closed, results, wants)):
            out[i].extend(msgs)
    return out


# --------------------------------------------------------------------------
# the manager under test, with no node around it


def make_manager(history, shards=0, tracer=None) -> SubscriptionManager:
    ops = types.SimpleNamespace(on_ledger_closed=[], on_proposed_tx=[],
                                lm=history[0], jq=None)
    return SubscriptionManager(ops, shards=shards, tracer=tracer)


def attach(mgr, want) -> tuple[InfoSub, list]:
    """Register one subscriber through the manager's own doors."""
    got: list = []
    sub = InfoSub(got.append)
    if want.streams:
        mgr.subscribe_streams(sub, sorted(want.streams))
    if want.accounts:
        mgr.subscribe_accounts(sub, sorted(want.accounts))
    if want.accounts_proposed:
        mgr.subscribe_accounts(sub, sorted(want.accounts_proposed),
                               proposed=True)
    return sub, got


def publish_history(mgr, history) -> None:
    for closed, results, proposed in history[1]:
        for tx, ter in proposed:
            mgr._pub_proposed(tx, ter)
        mgr._pub_ledger(closed, results)
    assert mgr.flush(timeout=10.0)


def as_the_registry_stands(mgr, subs) -> list[Want]:
    """The eager builder's view of the registry as it is NOW: a
    subscriber that is not registered wants nothing."""
    return [
        Want(s.streams, s.accounts, s.accounts_proposed)
        if s.id in mgr._subs else Want() for s in subs
    ]


MIXES = {
    "none": lambda h: [],
    "ledger_only": lambda h: [Want(["ledger"])],
    "server_only": lambda h: [Want(["server"])],
    "transactions": lambda h: [Want(["transactions"])],
    "transactions_proposed": lambda h: [Want(["transactions_proposed"])],
    "rt_transactions": lambda h: [Want(["rt_transactions"])],
    "accounts_sender": lambda h: [Want(accounts=[CAT.account_id])],
    "accounts_destination": lambda h: [Want(accounts=[BEN.account_id])],
    "accounts_metadata_only": lambda h: [
        Want(accounts=[_meta_only_account(h)])],
    "accounts_nobody": lambda h: [Want(accounts=[DAN.account_id])],
    "accounts_proposed": lambda h: [
        Want(accounts_proposed=[ANN.account_id])],
    "two_overlapping": lambda h: [
        Want(["transactions", "ledger"], accounts=[ANN.account_id]),
        Want(["rt_transactions"], accounts=[ANN.account_id, BEN.account_id],
             accounts_proposed=[CAT.account_id]),
        Want(["server"], accounts_proposed=[ANN.account_id]),
    ],
}


class TestEveryMessageIsTheEagerBuilders:
    @pytest.mark.parametrize("shards", [0, 2], ids=["inline", "sharded"])
    @pytest.mark.parametrize("mix", sorted(MIXES))
    def test_each_subscriber_receives_the_eager_list(self, history, mix,
                                                     shards):
        wants = MIXES[mix](history)
        expected = eager_history(history, wants)
        mgr = make_manager(history, shards=shards)
        try:
            received = [attach(mgr, want)[1] for want in wants]
            publish_history(mgr, history)
            for i, want in enumerate(wants):
                assert received[i] == expected[i], (mix, i)
            # the same accounting as a message a subscriber ever gave
            n = sum(len(e) for e in expected)
            stats = mgr.get_json()
            assert stats["published"] == stats["delivered"] == n
        finally:
            mgr.stop()

    def test_the_mixes_exercise_what_they_name(self, history):
        """The eager builder itself: a sender, a destination and the
        metadata-only account each hit some transaction and miss
        another, so the lists compared above are neither empty nor
        everything."""
        total = sum(len(r) for _c, r, _p in history[1])
        for mix in ("accounts_sender", "accounts_destination",
                    "accounts_metadata_only", "accounts_proposed"):
            (got,) = eager_history(history, MIXES[mix](history))
            closed = [m for m in got if m.get("validated")]
            assert 0 < len(closed) < total, mix
        (nobody,) = eager_history(history, MIXES["accounts_nobody"](history))
        assert nobody == []
        # the crossed offer reaches ANN's listener though she is not in it
        (meta_only,) = eager_history(
            history, MIXES["accounts_metadata_only"](history))
        assert any(m["transaction"]["TransactionType"] == "OfferCreate"
                   and m["transaction"]["Account"] == BEN.human_account_id
                   for m in meta_only)

    def test_a_message_is_built_once_for_all_who_take_it(self, history):
        mgr = make_manager(history)
        _a, got_a = attach(mgr, Want(["transactions"]))
        _b, got_b = attach(mgr, Want(accounts=[BEN.account_id]))
        closed, results, _proposed = history[1][-1]
        mgr._pub_ledger(closed, results)
        shared = [m for m in got_b if m["type"] == "transaction"]
        assert shared and all(any(m is n for n in got_a) for m in shared)
        assert mgr.stats["tx_built"] == len(results) == len(got_a)

    def test_registry_order_across_subscribers(self, history):
        """A transaction that reaches an account listener registered
        BEFORE the `transactions` subscriber reaches it first, as the
        loop over the registry did."""
        mgr = make_manager(history)
        order: list = []
        first = InfoSub(lambda m: order.append("accounts"))
        second = InfoSub(lambda m: order.append("transactions"))
        mgr.subscribe_accounts(first, [BEN.account_id])
        mgr.subscribe_streams(second, ["transactions"])
        closed, results, _proposed = history[1][-1]
        mgr._pub_ledger(closed, results)
        assert order[:2] == ["accounts", "transactions"]


# --------------------------------------------------------------------------
# (b) nothing is done for nobody


class Calls:
    """Counts calls of what a transaction message is made of."""

    def __init__(self, monkeypatch):
        self.n = {"tx_entries": 0, "from_bytes": 0, "tx_json": 0}
        real_entries = Ledger.tx_entries
        real_from_bytes = STObject.from_bytes.__func__
        real_json = infosub._tx_json_with_hash

        def tx_entries(ledger):
            self.n["tx_entries"] += 1
            return real_entries(ledger)

        def from_bytes(cls, *a, **k):
            self.n["from_bytes"] += 1
            return real_from_bytes(cls, *a, **k)

        def tx_json(tx):
            self.n["tx_json"] += 1
            return real_json(tx)

        monkeypatch.setattr(Ledger, "tx_entries", tx_entries)
        monkeypatch.setattr(STObject, "from_bytes", classmethod(from_bytes))
        monkeypatch.setattr(infosub, "_tx_json_with_hash", tx_json)


class TestNothingIsBuiltForNobody:
    @pytest.mark.parametrize("mix", ["none", "ledger_only", "server_only",
                                     "transactions_proposed"])
    def test_a_closed_ledger_costs_no_transaction_work(self, history, mix,
                                                       monkeypatch):
        wants = MIXES[mix](history)
        expected = [
            [m for closed, results, _p in history[1]
             for m in eager_closed(closed, results, [want])[0]]
            for want in wants
        ]
        mgr = make_manager(history)
        received = [attach(mgr, want)[1] for want in wants]
        calls = Calls(monkeypatch)
        for closed, results, _proposed in history[1]:
            mgr._pub_ledger(closed, results)
        assert calls.n == {"tx_entries": 0, "from_bytes": 0, "tx_json": 0}
        stats = mgr.get_json()
        assert stats["tx_built"] == 0
        assert stats["tx_considered"] == sum(
            len(r) for _c, r, _p in history[1])
        # ledgerClosed still reaches the ring and the `ledger` subscribers
        assert [seq for seq, _m in mgr._replay] == [
            c.seq for c, _r, _p in history[1]]
        assert received == expected
        if mix == "ledger_only":
            assert [m["type"] for m in received[0]] == (
                ["ledgerClosed"] * len(history[1]))

    @pytest.mark.parametrize("mix", ["none", "ledger_only", "server_only",
                                     "transactions", "accounts_sender"])
    def test_a_proposed_transaction_costs_nothing(self, history, mix,
                                                  monkeypatch):
        mgr = make_manager(history)
        received = [attach(mgr, w)[1] for w in MIXES[mix](history)]
        calls = Calls(monkeypatch)
        n = 0
        for _closed, _results, proposed in history[1]:
            for tx, ter in proposed:
                mgr._pub_proposed(tx, ter)
                n += 1
        assert calls.n == {"tx_entries": 0, "from_bytes": 0, "tx_json": 0}
        stats = mgr.get_json()
        assert stats["proposed_considered"] == n
        assert stats["proposed_built"] == 0
        assert all(got == [] for got in received)

    @pytest.mark.parametrize("mix", ["accounts_sender",
                                     "accounts_destination",
                                     "accounts_metadata_only",
                                     "accounts_nobody",
                                     "accounts_proposed"])
    def test_account_listeners_cost_what_hits(self, history, mix,
                                              monkeypatch):
        wants = MIXES[mix](history)
        (expected,) = eager_history(history, wants)
        hits = [m for m in expected if m["validated"]]
        proposed_hits = [m for m in expected if not m["validated"]]
        mgr = make_manager(history)
        _sub, got = attach(mgr, wants[0])
        calls = Calls(monkeypatch)
        publish_history(mgr, history)
        stats = mgr.get_json()
        assert stats["tx_built"] == len(hits)
        assert stats["proposed_built"] == len(proposed_hits)
        assert calls.n["tx_json"] == len(hits) + len(proposed_hits)
        # the metadata is parsed once a transaction, not twice
        total = sum(len(r) for _c, r, _p in history[1])
        assert calls.n["from_bytes"] <= total
        assert got == expected

    def test_the_metadata_is_parsed_once_for_a_transactions_listener(
            self, history, monkeypatch):
        mgr = make_manager(history)
        attach(mgr, Want(["transactions"], accounts=[ANN.account_id]))
        closed, results, _proposed = history[1][-1]
        for txid, blob, _meta in closed.tx_entries():
            closed.parse_tx(txid, blob)  # as a close has left them
        calls = Calls(monkeypatch)
        mgr._pub_ledger(closed, results)
        assert calls.n["tx_entries"] == 1
        assert calls.n["from_bytes"] == len(results)
        assert calls.n["tx_json"] == len(results)


# --------------------------------------------------------------------------
# (c) the summary follows the registry


def _raise(_msg):
    raise ConnectionError("the subscriber is gone")


def _op_subscribe_streams(mgr, sub, history):
    mgr.subscribe_streams(sub, ["transactions", "rt_transactions"])


def _op_unsubscribe_streams(mgr, sub, history):
    mgr.unsubscribe_streams(sub, ["transactions", "rt_transactions"])


def _op_subscribe_accounts(mgr, sub, history):
    mgr.subscribe_accounts(sub, [BEN.account_id])
    mgr.subscribe_accounts(sub, [CAT.account_id], proposed=True)


def _op_unsubscribe_accounts(mgr, sub, history):
    mgr.unsubscribe_accounts(sub, [BEN.account_id])
    mgr.unsubscribe_accounts(sub, [CAT.account_id], proposed=True)


def _op_add(mgr, sub, history):
    # the door of the path-find subscriptions (`create_path_request`)
    mgr.add(sub)


def _op_remove(mgr, sub, history):
    mgr.remove(sub.id)


def _op_evict(mgr, sub, history):
    mgr._evict(sub, reason="slow_consumer")


def _op_prune_rpc_sub(mgr, sub, history):
    # what `unsubscribe` with a url does: the last interest goes, then
    # the url's entry is pruned
    sub.url = "http://127.0.0.1:9/"
    mgr.rpc_subs[sub.url] = sub
    mgr.unsubscribe_streams(sub, sorted(sub.streams))
    mgr.unsubscribe_accounts(sub, sorted(sub.accounts))
    mgr.unsubscribe_accounts(sub, sorted(sub.accounts_proposed),
                             proposed=True)
    # a publication between the two still counts it as registered
    mgr._pub_proposed(*history[1][0][2][0])
    mgr.prune_rpc_sub(sub)
    assert sub.id not in mgr._subs and sub.url not in mgr.rpc_subs


def _op_resume(mgr, sub, history):
    # a client that dropped (its interests still on the object) comes
    # back through the resume cursor
    assert mgr.resume(sub, mgr._replay[-1][0])["resumed"]


def _op_send_raises(mgr, sub, history):
    sub.send = _raise
    closed, results, _proposed = history[1][0]
    mgr._pub_ledger(closed, results)  # the failing delivery drops it
    assert sub.id not in mgr._subs
    assert mgr.stats["dead_evicted"] == 1


LISTENING = Want(["transactions", "rt_transactions", "ledger"],
                 accounts=[BEN.account_id],
                 accounts_proposed=[CAT.account_id])

# op -> (the subscriber's interests before it, whether it is registered
# before it)
CHANGES = {
    "subscribe_streams": (_op_subscribe_streams, Want(["server"]), True),
    "subscribe_streams_unregistered": (_op_subscribe_streams, Want(), False),
    "unsubscribe_streams": (_op_unsubscribe_streams, LISTENING, True),
    "subscribe_accounts": (_op_subscribe_accounts, Want(["ledger"]), True),
    "subscribe_accounts_unregistered": (
        _op_subscribe_accounts, Want(), False),
    "unsubscribe_accounts": (
        _op_unsubscribe_accounts,
        Want(accounts=[BEN.account_id], accounts_proposed=[CAT.account_id]),
        True),
    "add": (_op_add, LISTENING, False),
    "remove": (_op_remove, LISTENING, True),
    "evict": (_op_evict, LISTENING, True),
    "prune_rpc_sub": (_op_prune_rpc_sub, LISTENING, True),
    "resume": (_op_resume, LISTENING, False),
    "send_raises": (_op_send_raises, LISTENING, True),
}


class TestTheSummaryCannotGoStale:
    @pytest.mark.parametrize("change", sorted(CHANGES))
    def test_the_next_transaction_goes_where_the_registry_says(
            self, history, change):
        op, before, registered = CHANGES[change]
        tracer = Tracer(sample=1.0)
        mgr = make_manager(history, tracer=tracer)
        got: list = []
        sub = InfoSub(got.append)
        sub.streams |= before.streams
        sub.accounts |= before.accounts
        sub.accounts_proposed |= before.accounts_proposed
        if registered:
            mgr.add(sub)
        # a bystander whose deliveries must not change
        _other, other_got = attach(mgr, Want(["transactions"]))
        (closed_a, results_a, proposed_a), (closed_b, results_b,
                                            proposed_b) = history[1][-2:]

        def publish(closed, results, proposed):
            wants = as_the_registry_stands(mgr, [sub])
            expected = []
            for tx, ter in proposed:
                expected.extend(eager_proposed(tx, ter, wants)[0])
            expected.extend(eager_closed(closed, results, wants)[0])
            del got[:]
            for tx, ter in proposed:
                mgr._pub_proposed(tx, ter)
            mgr._pub_ledger(closed, results)
            return expected

        # the summary is built from the registry as it stands ...
        expected = publish(closed_a, results_a, proposed_a)
        assert got == expected, change
        assert bool(expected) == (registered and bool(
            before.accounts or before.streams - {"server"}))
        # ... and follows every change of it
        op(mgr, sub, history)
        if change == "send_raises":
            sub.send = got.append
        expected = publish(closed_b, results_b, proposed_b)
        assert got == expected, change
        last = _events(tracer, "subs.publish")[-1]["args"]
        assert last["listeners"] == len(mgr._subs)
        txs = [m for m in other_got if m["type"] == "transaction"]
        assert len(txs) >= len(results_a) + len(results_b)

    def test_a_dead_subscriber_is_not_tried_again(self, history):
        """The failing send takes the subscriber out of the registry, and
        the very next transaction of the same ledger is not sent to it:
        one `published`, one `dead_evicted`, as the loop over the registry
        counted."""
        mgr = make_manager(history)
        tries: list = []

        def send(msg):
            tries.append(msg)
            raise ConnectionError

        mgr.subscribe_streams(InfoSub(send), ["transactions"])
        _ok, ok_got = attach(mgr, Want(["transactions"]))
        closed, results, _proposed = history[1][0]
        assert len(results) > 1
        mgr._pub_ledger(closed, results)
        assert len(tries) == 1
        assert len(ok_got) == len(results)
        assert mgr.stats["dead_evicted"] == 1
        assert mgr.stats["published"] == 1 + len(results)
        assert mgr.stats["delivered"] == len(results)

    def test_a_newcomer_receives_the_rest_of_a_ledger_under_way(
            self, history):
        """Pinned (the docstring of `_pub_ledger`): a subscriber that
        registers while a ledger's transactions are being published
        receives the remaining ones; one that registers from a
        `ledgerClosed` delivery, before the pass has begun, receives
        them all, though nobody listened to transactions until then."""
        closed, results, _proposed = history[1][0]
        (whole,) = eager_closed(closed, results, [Want(["transactions"])])
        mgr = make_manager(history)
        late_got: list = []
        late = InfoSub(late_got.append)
        seen: list = []

        def first_then_invite(msg):
            seen.append(msg)
            if len(seen) == 2:
                mgr.subscribe_streams(late, ["transactions"])

        mgr.subscribe_streams(InfoSub(first_then_invite), ["transactions"])
        mgr._pub_ledger(closed, results)
        assert seen == whole and len(whole) > 2
        assert late_got == whole[2:]

        mgr = make_manager(history)
        joined_got: list = []
        joined = InfoSub(joined_got.append)
        mgr.subscribe_streams(
            InfoSub(lambda msg: mgr.subscribe_streams(
                joined, ["transactions"])), ["ledger"])
        mgr._pub_ledger(closed, results)
        assert joined_got == whole

    def test_one_who_leaves_mid_ledger_receives_no_more(self, history):
        closed, results, _proposed = history[1][0]
        mgr = make_manager(history)
        got: list = []
        sub = InfoSub(None)

        def send(msg):
            got.append(msg)
            mgr.unsubscribe_streams(sub, ["transactions"])

        sub.send = send
        mgr.subscribe_streams(sub, ["transactions"])
        mgr._pub_ledger(closed, results)
        assert len(got) == 1 and len(results) > 1
        assert mgr.stats["tx_built"] == 1
        assert mgr.stats["tx_considered"] == len(results)


# --------------------------------------------------------------------------
# (d) the span and the instants


def _events(tracer, name):
    return [ev for ev in tracer.chrome_trace()["traceEvents"]
            if ev["name"] == name]


class TestThePublishSpan:
    @pytest.mark.parametrize("mix,built_all", [
        ("none", False), ("ledger_only", False), ("transactions", True),
        ("accounts_metadata_only", False), ("two_overlapping", True)])
    def test_one_span_a_close_with_its_counts(self, history, mix,
                                              built_all):
        wants = MIXES[mix](history)
        tracer = Tracer(sample=1.0)
        mgr = make_manager(history, tracer=tracer)
        for want in wants:
            attach(mgr, want)
        for closed, results, _proposed in history[1]:
            mgr._pub_ledger(closed, results)
        spans = _events(tracer, "subs.publish")
        assert [ev["ph"] for ev in spans] == ["X"] * len(history[1])
        for ev, (closed, results, _p) in zip(spans, history[1]):
            args = ev["args"]
            expected = eager_closed(closed, results, wants)
            txmsgs = [[m for m in e if m["type"] == "transaction"]
                      for e in expected]
            distinct = {m["transaction"]["hash"] for e in txmsgs for m in e}
            assert args["trace"] == f"ledger-{closed.seq}"
            assert args["txs"] == len(results)
            assert args["built"] == len(distinct)
            assert args["delivered"] == sum(len(e) for e in txmsgs)
            assert args["listeners"] == len(wants)
            if built_all:
                assert args["built"] == args["txs"]

    def test_the_fanout_leaf_marks_a_delivered_publication(self, history):
        """`subs.fanout.tx` for a transaction somebody receives, none for
        one nobody does."""
        tracer = Tracer(sample=1.0)
        mgr = make_manager(history, tracer=tracer)
        _sub, got = attach(mgr, Want(accounts=[_meta_only_account(history)]))
        for closed, results, _proposed in history[1]:
            mgr._pub_ledger(closed, results)
        marked = {ev["args"]["trace"].upper()
                  for ev in _events(tracer, "subs.fanout.tx")}
        assert marked == {m["transaction"]["hash"] for m in got}
        total = sum(len(r) for _c, r, _p in history[1])
        assert 0 < len(marked) < total
        assert all(ev["ph"] == "i" and "ledger_seq" in ev["args"]
                   for ev in _events(tracer, "subs.fanout.tx"))

    def test_nobody_listening_leaves_no_leaf(self, history):
        tracer = Tracer(sample=1.0)
        mgr = make_manager(history, tracer=tracer)
        attach(mgr, Want(["ledger"]))
        closed, results, _proposed = history[1][0]
        mgr._pub_ledger(closed, results)
        assert _events(tracer, "subs.fanout.tx") == []
        (span,) = _events(tracer, "subs.publish")
        assert span["args"]["built"] == span["args"]["delivered"] == 0

    def test_an_unsampled_transaction_leaves_no_leaf(self, history):
        tracer = Tracer(sample=0.0)
        mgr = make_manager(history, tracer=tracer)
        attach(mgr, Want(["transactions"]))
        closed, results, _proposed = history[1][0]
        mgr._pub_ledger(closed, results)
        assert _events(tracer, "subs.fanout.tx") == []
        assert len(_events(tracer, "subs.publish")) == 1

    def test_the_counters_reach_get_json(self, history):
        mgr = make_manager(history)
        attach(mgr, Want(["transactions", "transactions_proposed"]))
        publish_history(mgr, history)
        total = sum(len(r) for _c, r, _p in history[1])
        j = mgr.get_json()
        assert (j["tx_considered"], j["tx_built"]) == (total, total)
        assert (j["proposed_considered"], j["proposed_built"]) == (
            total, total)


# --------------------------------------------------------------------------
# the two readers the benchmark gains (files of `benchmarks/layers/`)


def _bench():
    import os
    import sys

    repo = os.path.join(os.path.dirname(__file__), "..")
    bench = os.path.join(repo, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from yardstick import manifest, readers

    return repo, bench, manifest, readers


def _read(metric, spans):
    _repo, bench, manifest, readers = _bench()
    return readers.read_metric(manifest.reader_file(bench, metric),
                               {"counters": {}, "spans": spans})


def _span(name, dur_us, **args):
    return {"ph": "X", "name": name, "ts": 0, "dur": dur_us, "args": args}


class TestTheFanoutReaders:
    @pytest.mark.parametrize("spans,want", [
        # nobody listens: two closes of 2,048, nothing built
        ([_span("subs.publish", 300, txs=2048, built=0),
          _span("subs.publish", 500, txs=2048, built=0)], (0.4, 0.0)),
        # a `transactions` listener, or the eager path come back
        ([_span("subs.publish", 330_000, txs=2048, built=2048)],
         (330.0, 100.0)),
        # account listeners: what hits, weighted by the ledgers' sizes
        ([_span("subs.publish", 1_000, txs=100, built=10),
          _span("subs.publish", 3_000, txs=300, built=90),
          _span("close.seal", 9_000_000)], (2.0, 25.0)),
        # a window of empty ledgers has a time and no share
        ([_span("subs.publish", 200, txs=0, built=0)], (0.2, None)),
        # the parent records no such span: both leave the line
        ([_span("close.seal", 60_000), _span("persist.total", 900_000)],
         (None, None)),
        ([], (None, None)),
        # a span without the counts (not this program's): no share
        ([_span("subs.publish", 1_000)], (1.0, None)),
    ], ids=["nobody", "everybody", "accounts", "empty_ledgers", "parent",
            "no_spans", "no_counts"])
    def test_on_synthetic_spans(self, spans, want):
        ms, share = want
        got_ms = _read("fanout.publish_ms_per_close", spans)
        got_share = _read("fanout.built_share", spans)
        assert got_ms == (None if ms is None else pytest.approx(ms))
        assert got_share == (None if share is None else pytest.approx(share))

    @pytest.mark.parametrize("mix,share", [
        ("none", 0.0), ("ledger_only", 0.0), ("transactions", 100.0)])
    def test_on_the_spans_the_program_records(self, history, mix, share):
        tracer = Tracer(sample=1.0)
        mgr = make_manager(history, tracer=tracer)
        for want in MIXES[mix](history):
            attach(mgr, want)
        for closed, results, _proposed in history[1]:
            mgr._pub_ledger(closed, results)
        spans = tracer.chrome_trace()["traceEvents"]
        assert _read("fanout.built_share", spans) == pytest.approx(share)
        assert _read("fanout.publish_ms_per_close", spans) > 0.0

    def test_the_manifest_holds_both(self):
        repo, bench, manifest, _readers = _bench()
        import os

        m = manifest.load(os.path.join(repo, "BENCHMARK.json"))
        manifest.validate(m, repo)
        want = {"better": "lower", "source": "program_span",
                "layer": "fan-out", "moves": "close_p50_ms",
                "workloads": ["node.flood", "node.door"]}
        entries = {x["name"]: x for x in m["per_layer"]}
        assert entries["fanout.publish_ms_per_close"] == {
            "name": "fanout.publish_ms_per_close", "unit": "ms", **want}
        assert entries["fanout.built_share"] == {
            "name": "fanout.built_share", "unit": "%", **want}
        # appended behind PR 32's last: nothing that was there moved
        # (later PRs append behind these two)
        names = [x["name"] for x in m["per_layer"]]
        at = names.index("quorum.peer_lag_ledgers")
        assert names[at + 1:at + 3] == [
            "fanout.publish_ms_per_close", "fanout.built_share"]
        for cell in ("node.flood", "node.door"):
            names = [x["name"] for x in manifest.metrics_of(
                m, cell, "per_layer")]
            assert "fanout.built_share" in names
            assert "fanout.publish_ms_per_close" in names
        assert manifest.reader_file(bench, "fanout.built_share").endswith(
            "fanout.built_share.py")
