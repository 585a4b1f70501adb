"""Liquidity plane (ISSUE 17): incremental order-book index identity
on adversarial write-set seams, Q16.16 quality flattening, the routed
device evaluator's host/device byte-identity at every mesh width, the
PathPlane scheduling/shedding contract, the path_find result-cache
satellite, and the FEE_PATH_FIND door ladder."""

from __future__ import annotations

import types

import jax
import numpy as np
import pytest

jax.config.update("jax_platforms", "cpu")

from stellard_tpu.crypto.backend import PathQualityEvaluator  # noqa: E402
from stellard_tpu.engine import TransactionEngine  # noqa: E402
from stellard_tpu.node.config import Config  # noqa: E402
from stellard_tpu.node.node import Node  # noqa: E402
from stellard_tpu.ops.pathq_jax import Q16_MAX, Q16_ONE  # noqa: E402
from stellard_tpu.overlay.resource import (  # noqa: E402
    FEE_PATH_FIND,
    ResourceManager,
)
from stellard_tpu.paths import (  # noqa: E402
    LiveBookIndex,
    OrderBookDB,
    find_paths,
)
from stellard_tpu.paths.plane import PathPlane  # noqa: E402
from stellard_tpu.paths.quality import (  # noqa: E402
    MAX_HOPS,
    book_quality_q16,
    build_rate_matrix,
    rate_u64_to_q16,
)
from stellard_tpu.protocol.formats import TxType  # noqa: E402
from stellard_tpu.protocol.keys import KeyPair  # noqa: E402
from stellard_tpu.protocol.sfields import (  # noqa: E402
    sfAmount,
    sfDestination,
    sfOfferSequence,
    sfTakerGets,
    sfTakerPays,
)
from stellard_tpu.protocol.stamount import (  # noqa: E402
    ACCOUNT_ZERO,
    STAmount,
    currency_from_iso,
)
from stellard_tpu.protocol.stobject import PathElement, STPathSet  # noqa: E402
from stellard_tpu.rpc.handlers import (  # noqa: E402
    Context,
    Role,
    charge_rpc_client,
    dispatch,
    rpc_method_fee,
    rpc_warning,
)
from stellard_tpu.rpc.infosub import InfoSub, SubscriptionManager  # noqa: E402
from stellard_tpu.paths.orderbook import Book  # noqa: E402

from test_engine import ALICE, BOB, CAROL, GATEWAY, Net, USD  # noqa: E402

EUR = currency_from_iso("EUR")
XRP = b"\x00" * 20
M = 1_000_000


def iou(v, issuer=GATEWAY, cur=USD):
    return STAmount.from_iou(cur, issuer.account_id, v, 0)


def drops(v):
    return STAmount.from_drops(v)


def close(net: Net):
    """Seal the working ledger and open its successor (one validated
    close); returns the sealed ledger."""
    led = net.ledger
    led.close(led.parent_close_time + 10, 10)
    net.ledger = led.open_successor()
    net.engine = TransactionEngine(net.ledger)
    return led


def full_books(led) -> set:
    return OrderBookDB().setup(led).books


def offer(net: Net, key: KeyPair, pays: STAmount, gets: STAmount):
    """Place an offer; returns the tx sequence (for later cancel)."""
    seq = net.seq(key)
    net.apply(key, TxType.ttOFFER_CREATE,
              fields={sfTakerPays: pays, sfTakerGets: gets})
    return seq


def liquid_net() -> Net:
    net = Net(ALICE, BOB, CAROL, GATEWAY)
    net.trust(ALICE, GATEWAY, 10_000)
    net.trust(BOB, GATEWAY, 10_000)
    net.trust(CAROL, GATEWAY, 10_000)
    net.pay(GATEWAY, ALICE.account_id, iou(1_000))
    net.pay(GATEWAY, BOB.account_id, iou(1_000))
    return net


def check_identity(idx: LiveBookIndex, led):
    """THE contract: the incremental view equals the full scan."""
    db = idx.advance(led)
    assert db.books == full_books(led), f"divergence at seq {led.seq}"
    assert idx.seq == led.seq
    return db


# --------------------------------------------------------------------------
# incremental index identity on the adversarial seams


class TestLiveBookIndexIdentity:
    def test_first_advance_is_full_rebuild(self):
        net = liquid_net()
        offer(net, ALICE, drops(100 * M), iou(100))
        led = close(net)
        idx = LiveBookIndex()
        db = check_identity(idx, led)
        assert idx.full_rebuilds == 1
        assert idx.incremental_advances == 0
        assert len(db.books) == 1

    def test_zero_book_write_close_carries_without_reads(self):
        """Anti-vacuity: a close whose write set touches no books must
        carry the previous view forward without a single state read —
        pinned by the read counters, not just the result."""
        net = liquid_net()
        offer(net, ALICE, drops(100 * M), iou(100))
        idx = LiveBookIndex()
        db1 = idx.advance(close(net))
        # a plain STR payment: no book in the write set
        net.pay(ALICE, CAROL.account_id, drops(5 * M))
        led2 = close(net)
        scanned, rereads = idx.state_offers_scanned, idx.book_rereads
        db2 = idx.advance(led2)
        assert db2 is db1  # literally the same carried-forward object
        assert idx.carries == 1
        assert idx.state_offers_scanned == scanned  # zero offers scanned
        assert idx.book_rereads == rereads  # zero books re-read
        assert db2.books == full_books(led2)
        # a fully empty close carries too
        led3 = close(net)
        assert idx.advance(led3) is db1 and idx.carries == 2

    def test_book_creation_mid_flood(self):
        """New books appearing while other closes flood through: each
        close's delta touches only its own books."""
        net = liquid_net()
        idx = LiveBookIndex()
        idx.advance(close(net))
        assert idx.full_rebuilds == 1

        offer(net, ALICE, drops(10 * M), iou(10))  # USD/XRP book born
        led = close(net)
        check_identity(idx, led)
        assert idx.book_rereads == 1

        # two more offers in the SAME book + one brand-new book
        offer(net, ALICE, drops(20 * M), iou(10))
        offer(net, BOB, drops(30 * M), iou(10))
        # reverse direction, priced NOT to cross the forward book
        offer(net, BOB, iou(20), drops(10 * M))
        led = close(net)
        check_identity(idx, led)
        assert idx.book_rereads == 3  # 1 + exactly the 2 touched books
        assert idx.full_rebuilds == 1  # never fell back
        assert idx.incremental_advances == 2

    def test_crossing_consumes_tier_keeps_book(self):
        """A crossing that eats the best tier deletes offers without
        changing book membership — the incremental count must absorb
        the DeletedNode and keep the book alive."""
        net = liquid_net()
        # two tiers: alice sells USD at 1.0 and at 2.0 XRP/USD
        offer(net, ALICE, drops(100 * M), iou(100))
        offer(net, ALICE, drops(200 * M), iou(100))
        idx = LiveBookIndex()
        idx.advance(close(net))
        # bob crosses exactly the best tier (pays 100 XRP for 100 USD)
        offer(net, BOB, iou(100), drops(100 * M))
        led = close(net)
        db = check_identity(idx, led)
        assert len(db.books) == 1  # second tier keeps the book alive
        assert idx.full_rebuilds == 1  # delta applied, no fallback

    def test_crossing_empties_book(self):
        """Full consumption of a single-offer book: both the crossed
        offer and the taker's are gone, the book must vanish."""
        net = liquid_net()
        offer(net, ALICE, drops(100 * M), iou(100))
        idx = LiveBookIndex()
        db1 = idx.advance(close(net))
        assert len(db1.books) == 1
        offer(net, BOB, iou(100), drops(100 * M))
        led = close(net)
        db = check_identity(idx, led)
        assert len(db.books) == 0
        assert idx.full_rebuilds == 1

    def test_cancel_empties_book(self):
        net = liquid_net()
        seq = offer(net, ALICE, drops(100 * M), iou(100))
        idx = LiveBookIndex()
        assert len(idx.advance(close(net)).books) == 1
        net.apply(ALICE, TxType.ttOFFER_CANCEL,
                  fields={sfOfferSequence: seq})
        led = close(net)
        db = check_identity(idx, led)
        assert len(db.books) == 0
        assert idx.full_rebuilds == 1

    def test_quality_reorder_same_book(self):
        """A better-priced offer reorders the tiers: membership is
        unchanged (delta nets +1 on an existing book) but the quality
        probe must see the new best tier."""
        net = liquid_net()
        offer(net, ALICE, drops(200 * M), iou(100))  # 2.0 XRP per USD
        idx = LiveBookIndex()
        led = close(net)
        db = idx.advance(led)
        book = next(iter(db.books))
        q_before = book_quality_q16(led, book)
        offer(net, BOB, drops(100 * M), iou(100))  # 1.0 — jumps the queue
        led = close(net)
        db = check_identity(idx, led)
        assert db.books == {book}
        q_after = book_quality_q16(led, book)
        assert q_after < q_before  # cheaper best tier surfaced

    def test_kill_switch_full_rebuild_identity(self):
        """[paths] incremental=0: every advance is a full scan, and the
        two modes agree at every close."""
        net = liquid_net()
        inc, full = LiveBookIndex(incremental=True), LiveBookIndex(
            incremental=False)
        seq = None
        for step in range(4):
            if step == 0:
                seq = offer(net, ALICE, drops(100 * M), iou(100))
            elif step == 1:
                offer(net, BOB, iou(50), drops(60 * M))
            elif step == 2:
                net.apply(ALICE, TxType.ttOFFER_CANCEL,
                          fields={sfOfferSequence: seq})
            led = close(net)
            assert inc.advance(led).books == full.advance(led).books
            assert full.advance(led).books == full_books(led)
        assert full.full_rebuilds == 4
        assert full.incremental_advances == 0 and full.carries == 0
        assert inc.full_rebuilds == 1

    def test_gap_forces_rebuild(self):
        """Skipping a close breaks parent-hash continuity: the next
        advance must fall back to the full scan, not guess."""
        net = liquid_net()
        idx = LiveBookIndex()
        idx.advance(close(net))
        offer(net, ALICE, drops(100 * M), iou(100))
        close(net)  # never shown to the index
        offer(net, BOB, iou(10), drops(20 * M))
        led = close(net)
        db = check_identity(idx, led)
        assert idx.full_rebuilds == 2
        assert db.books == full_books(led)

    def test_seed_from_offer_keys_equals_the_full_scan(self):
        """A list of offer keys (a resumed node's CLF mirror) gives the
        same index as the walk of the state, and the next close
        advances from it incrementally."""
        from stellard_tpu.protocol.formats import LedgerEntryType
        from stellard_tpu.protocol.sfields import sfLedgerEntryType
        from stellard_tpu.protocol.stobject import STObject

        net = liquid_net()
        offer(net, ALICE, drops(100 * M), iou(100))
        offer(net, BOB, drops(50 * M), iou(70))
        offer(net, CAROL, iou(500), drops(1 * M))  # too dear to cross
        led = close(net)
        keys = [it.tag for it in led.state_map.items()
                if STObject.from_bytes(it.data).get(sfLedgerEntryType)
                == int(LedgerEntryType.ltOFFER)]
        assert len(keys) == 3
        idx = LiveBookIndex()
        assert idx.seed(led, keys) is True
        assert (idx.seeded, idx.full_rebuilds) == (1, 0)
        assert idx.state_offers_scanned == 3
        assert idx.books_if_current(led).books == full_books(led)
        assert len(full_books(led)) == 2
        offer(net, ALICE, drops(30 * M), iou(20))
        check_identity(idx, close(net))
        assert (idx.incremental_advances, idx.full_rebuilds) == (1, 0)

    @pytest.mark.parametrize("wrong", ["missing", "not_an_offer"])
    def test_seed_refuses_a_list_that_is_not_this_ledgers(self, wrong):
        from stellard_tpu.state import indexes

        net = liquid_net()
        offer(net, ALICE, drops(100 * M), iou(100))
        led = close(net)
        key = (b"\x5a" * 32 if wrong == "missing"
               else indexes.account_root_index(ALICE.account_id))
        idx = LiveBookIndex()
        assert idx.seed(led, [key]) is False
        assert idx.seeded == 0 and idx.books_if_current(led) is None
        check_identity(idx, led)  # the first advance scans, as before
        assert idx.full_rebuilds == 1

    def test_books_if_current_never_mutates(self):
        net = liquid_net()
        idx = LiveBookIndex()
        led1 = close(net)
        assert idx.books_if_current(led1) is None  # cold: no advance
        db = idx.advance(led1)
        assert idx.books_if_current(led1) is db
        offer(net, ALICE, drops(100 * M), iou(100))
        led2 = close(net)
        before = idx.counters()
        assert idx.books_if_current(led2) is None  # current != led2
        assert idx.counters() == before  # ...and nothing moved

    def test_find_paths_identity_incremental_vs_full(self):
        """End to end: find_paths answers are identical whether served
        from the incremental index or a fresh full scan, at every seq."""
        net = liquid_net()
        idx = LiveBookIndex()

        def snapshot(led):
            out = []
            for books in (idx.advance(led), OrderBookDB().setup(led)):
                alts = find_paths(led, ALICE.account_id, CAROL.account_id,
                                  iou(10), books=books)
                out.append([
                    (STPathSet(a["paths"]).to_json(),
                     a["source_amount"].to_json())
                    for a in alts
                ])
            return out

        offer(net, BOB, drops(100 * M), iou(100))  # XRP -> USD liquidity
        led = close(net)
        inc, full = snapshot(led)
        assert inc == full and inc  # non-vacuous: there IS a book path
        offer(net, BOB, iou(100, cur=EUR), iou(100))  # EUR -> USD
        led = close(net)
        inc, full = snapshot(led)
        assert inc == full
        net.pay(ALICE, CAROL.account_id, drops(M))  # carry-forward close
        led = close(net)
        inc, full = snapshot(led)
        assert inc == full
        assert idx.carries >= 1 and idx.incremental_advances >= 1


# --------------------------------------------------------------------------
# Q16.16 flattening


class TestQualityFlattening:
    def test_rate_decode_parity_and_scale(self):
        # canonical STAmount rate 1.0: mantissa 1e15, offset -15
        q_parity = ((100 - 15) << 56) | 10 ** 15
        assert rate_u64_to_q16(q_parity) == Q16_ONE
        q_double = ((100 - 15) << 56) | 2 * 10 ** 15
        assert rate_u64_to_q16(q_double) == 2 * Q16_ONE
        assert rate_u64_to_q16(0) == Q16_ONE  # no quality = parity
        q_huge = ((100 + 20) << 56) | 10 ** 15
        assert rate_u64_to_q16(q_huge) == Q16_MAX  # saturates, not wraps

    def test_book_quality_probe(self):
        net = liquid_net()
        led = close(net)
        book = Book(XRP, ACCOUNT_ZERO, USD, GATEWAY.account_id)
        assert book_quality_q16(led, book) == Q16_MAX  # empty book
        offer(net, ALICE, drops(200 * M), iou(100))
        led = close(net)
        q2 = book_quality_q16(led, book)
        assert q2 < Q16_MAX
        offer(net, BOB, drops(100 * M), iou(100))
        led = close(net)
        assert book_quality_q16(led, book) < q2  # better tier wins

    def test_rate_matrix_shapes_and_saturation(self):
        net = liquid_net()
        offer(net, ALICE, drops(200 * M), iou(100))
        led = close(net)
        deep = [PathElement(account=BOB.account_id)] * (MAX_HOPS + 1)
        candidates = [
            ([], (XRP, ACCOUNT_ZERO)),  # empty path: identity row
            ([PathElement(currency=USD, issuer=GATEWAY.account_id)],
             (XRP, ACCOUNT_ZERO)),  # one book hop
            ([PathElement(account=GATEWAY.account_id)],
             (USD, ALICE.account_id)),  # account hop at parity
            (deep, (USD, GATEWAY.account_id)),  # over-deep: ranks last
        ]
        rows = build_rate_matrix(led, candidates)
        assert rows.shape == (4, MAX_HOPS) and rows.dtype == np.uint32
        assert (rows[0] == Q16_ONE).all()
        book = Book(XRP, ACCOUNT_ZERO, USD, GATEWAY.account_id)
        assert rows[1, 0] == book_quality_q16(led, book)
        assert (rows[1, 1:] == Q16_ONE).all()
        assert rows[2, 0] == Q16_ONE  # no TransferRate = parity
        assert (rows[3] == Q16_MAX).all()


# --------------------------------------------------------------------------
# routed device evaluator


class TestPathQualityEvaluator:
    def _rates(self, n, seed=0):
        rng = np.random.default_rng(seed)
        return rng.integers(1, 2 ** 32, size=(n, MAX_HOPS), dtype=np.uint32)

    @pytest.mark.parametrize("width", [1, 2, 4, 8])
    def test_host_device_byte_identity(self, width):
        """THE device-plane pin: the mesh arm is byte-identical to the
        host arm at every width (virtual 8-device CPU mesh)."""
        ev = PathQualityEvaluator(mesh=str(width), routing="device")
        for n in (1, 3, 37, 128):
            rates = self._rates(n, seed=n)
            host = ev.evaluate_host(rates)
            dev = ev.evaluate(rates)
            assert dev.dtype == np.uint32 and host.dtype == np.uint32
            assert np.array_equal(host, dev), f"width {width} batch {n}"
        assert ev.device_batches > 0 and ev.host_batches == 0
        j = ev.get_json()
        assert width in j["arm_widths"].values()  # honest width provenance

    def test_identity_and_saturation_rows(self):
        ev = PathQualityEvaluator(routing="host")
        rates = np.full((3, MAX_HOPS), Q16_ONE, dtype=np.uint32)
        rates[1, 0] = 2 * Q16_ONE
        rates[2, :] = Q16_MAX
        out = ev.evaluate(rates)
        assert out[0] == Q16_ONE  # identity composes to identity
        assert out[1] == 2 * Q16_ONE
        assert out[2] == Q16_MAX  # saturated stays saturated

    def test_cost_routing_floors_small_batches(self):
        ev = PathQualityEvaluator(mesh="2", routing="cost",
                                 min_device_batch=64)
        ev.evaluate(self._rates(8))
        assert ev.host_batches == 1 and ev.device_batches == 0
        for i in range(4):
            ev.evaluate(self._rates(256, seed=i))
        assert ev.device_batches > 0  # above the floor, arms explored
        assert ev.get_json()["rows_evaluated"] == 8 + 4 * 256

    def test_bad_routing_is_loud(self):
        with pytest.raises(ValueError):
            PathQualityEvaluator(routing="gpu")


# --------------------------------------------------------------------------
# PathPlane: pre-rank floor, budget, staleness, shedding


class TestPathPlane:
    def test_pre_rank_noop_below_floor(self):
        net = liquid_net()
        led = close(net)
        ev = PathQualityEvaluator(routing="host")
        plane = PathPlane(evaluator=ev, prune_floor=8, prune_keep=2)
        pre = plane.make_pre_rank(led)
        cands = [([PathElement(account=BOB.account_id)],
                  (USD, GATEWAY.account_id)) for _ in range(8)]
        assert pre(None, cands) is cands  # at the floor: untouched
        assert plane.prune_batches == 0

    def test_pre_rank_prunes_but_keeps_empty_paths(self):
        net = liquid_net()
        led = close(net)
        ev = PathQualityEvaluator(routing="host")
        plane = PathPlane(evaluator=ev, prune_floor=4, prune_keep=2)
        pre = plane.make_pre_rank(led)
        cands = [([PathElement(account=BOB.account_id)],
                  (USD, GATEWAY.account_id)) for _ in range(9)]
        cands.append(([], (XRP, ACCOUNT_ZERO)))  # the default path
        out = pre(None, cands)
        assert len(out) < len(cands)
        assert ([], (XRP, ACCOUNT_ZERO)) in out  # empty path survives
        # output preserves the original relative order
        idxs = [cands.index(c) for c in out]
        assert idxs == sorted(idxs)
        assert plane.prune_batches == 1
        assert plane.pruned_candidates == len(cands) - len(out)

    def test_no_evaluator_means_no_hook(self):
        assert PathPlane().make_pre_rank(None) is None
        ev = PathQualityEvaluator(routing="host")
        assert PathPlane(evaluator=ev,
                         device_prune=False).make_pre_rank(None) is None

    def test_budget_sheds_and_resets_per_close(self):
        plane = PathPlane(max_updates_per_close=2)
        plane.begin_close(10)
        assert plane.claim_update(("a", 1), 10)
        assert plane.claim_update(("b", 1), 10)
        assert not plane.claim_update(("c", 1), 10)  # shed, not queued
        assert plane.shed_budget == 1
        plane.begin_close(11)  # fresh budget
        assert plane.claim_update(("c", 1), 11)

    def test_stalest_first_ordering_and_staleness_histogram(self):
        plane = PathPlane(max_updates_per_close=8)
        plane.note_created(("a", 1), 5)
        plane.note_created(("b", 1), 5)
        plane.note_ranked(("a", 1), 7)
        # b last ranked at 5, a at 7: b goes first; never-seen first of all
        order = plane.order_keys([("a", 1), ("b", 1), ("z", 9)], 9)
        assert order == [("z", 9), ("b", 1), ("a", 1)]
        plane.note_ranked(("b", 1), 9)
        assert plane.staleness_max == 4  # b waited 9-5 closes
        assert plane.staleness_quantile(0.99) == 4
        plane.sync_live([("a", 1)])
        assert plane.get_json()["subs"] == 1

    def test_throttled_endpoint_is_shed_before_budget(self):
        t = [0.0]
        rm = ResourceManager(clock=lambda: t[0])
        spammer = ("6.6.6.6", 0)
        while not rm.is_throttled(spammer):
            rm.charge(spammer, FEE_PATH_FIND)
        plane = PathPlane(max_updates_per_close=8, resources=rm)
        plane.begin_close(3)
        assert not plane.claim_update(("s", 1), 3, endpoint=spammer)
        assert plane.shed_throttled == 1 and plane.shed_budget == 0
        # a polite client on the same close still gets its update
        assert plane.claim_update(("p", 1), 3, endpoint=("7.7.7.7", 0))
        # ...and the granted update was charged to its endpoint
        assert rm.balance(("7.7.7.7", 0)) > 0


# --------------------------------------------------------------------------
# subscription publishing through the plane (node-level)


@pytest.fixture
def node():
    n = Node(Config(signature_backend="cpu")).setup()
    yield n
    n.stop()


def fund(n: Node, kp: KeyPair, drops_: int = 1_000_000_000) -> None:
    from stellard_tpu.protocol.sfields import sfSequence
    from stellard_tpu.protocol.sttx import SerializedTransaction

    master = n.master_keys
    root = n.ledger_master.current_ledger().account_root(master.account_id)
    tx = SerializedTransaction.build(
        TxType.ttPAYMENT, master.account_id, root[sfSequence], 10,
        {sfAmount: STAmount.from_drops(drops_),
         sfDestination: kp.account_id},
    )
    tx.sign(master)
    ter, applied = n.submit(tx)
    assert applied, ter
    # seal immediately: the root sequence read above goes through the
    # validated state, so back-to-back funds need a close in between
    n.close_ledger()


class TestSubscriptionPlane:
    def test_node_wires_plane_and_close_hook(self, node):
        assert node.path_plane is not None  # [paths] enabled=1 default
        lcl, _ = node.close_ledger()
        # the on_ledger_closed hook advanced the index to the close
        assert node.path_plane.index.seq == lcl.seq
        assert node.path_plane.books_if_current(lcl) is not None
        counts = dispatch(Context(node, {}, Role.ADMIN), "get_counts")
        assert counts["paths"]["index"]["seq"] == lcl.seq

    def test_budget_alternates_stalest_first(self, node):
        """Two subscriptions, budget one: each close serves the stalest
        and SHEDS the other; across two closes both get exactly one
        update (bounded staleness, no queue growth)."""
        alice, carol = KeyPair.from_passphrase(
            "pp-alice"), KeyPair.from_passphrase("pp-carol")
        fund(node, alice)
        fund(node, carol)
        plane = PathPlane(max_updates_per_close=1)
        mgr = SubscriptionManager(node.ops)  # shards=0: inline delivery
        # publish closes by hand below — the constructor's close hook
        # would schedule a second (async) path update per close
        node.ops.on_ledger_closed.remove(mgr._pub_ledger)
        mgr.path_plane = plane
        req = {"src": alice.account_id, "dst": carol.account_id,
               "dst_amount": STAmount.from_drops(1000)}
        got1, got2 = [], []
        sub1, sub2 = InfoSub(got1.append), InfoSub(got2.append)
        mgr.create_path_request(sub1, dict(req))
        mgr.create_path_request(sub2, dict(req))

        lcl, _ = node.close_ledger()
        mgr._pub_path_updates(lcl)
        assert (len(got1), len(got2)) == (1, 0)  # sub1 served, sub2 shed
        assert plane.shed_budget == 1
        lcl, _ = node.close_ledger()
        mgr._pub_path_updates(lcl)
        assert (len(got1), len(got2)) == (1, 1)  # now the stalest went
        assert plane.shed_budget == 2
        assert plane.reranked == 2
        assert got1[0]["type"] == got2[0]["type"] == "path_find"

    def test_throttled_subscriber_shed_in_publish(self, node):
        alice, carol = KeyPair.from_passphrase(
            "pt-alice"), KeyPair.from_passphrase("pt-carol")
        fund(node, alice)
        fund(node, carol)
        rm = node.rpc_resources if node.rpc_resources is not None else (
            ResourceManager())
        plane = PathPlane(max_updates_per_close=8, resources=rm)
        mgr = SubscriptionManager(node.ops)
        node.ops.on_ledger_closed.remove(mgr._pub_ledger)
        mgr.path_plane = plane
        got = []
        sub = InfoSub(got.append, client_ip="6.6.6.6")
        while not rm.is_throttled(("6.6.6.6", 0)):
            rm.charge(("6.6.6.6", 0), FEE_PATH_FIND)
        mgr.create_path_request(sub, {
            "src": alice.account_id, "dst": carol.account_id,
            "dst_amount": STAmount.from_drops(1000)})
        lcl, _ = node.close_ledger()
        mgr._pub_path_updates(lcl)
        assert got == [] and plane.shed_throttled == 1


# --------------------------------------------------------------------------
# result-cache satellite + door pricing


class TestPathFindCacheAndDoor:
    def _seed_accounts(self, node):
        alice = KeyPair.from_passphrase("pc-alice")
        carol = KeyPair.from_passphrase("pc-carol")
        fund(node, alice)
        fund(node, carol)
        node.close_ledger()
        return alice, carol

    def _params(self, alice, carol):
        from stellard_tpu.protocol.keys import encode_account_id

        return {
            "source_account": encode_account_id(alice.account_id),
            "destination_account": encode_account_id(carol.account_id),
            "destination_amount": STAmount.from_drops(1000).to_json(),
            "ledger_index": "validated",
        }

    def test_ripple_path_find_cached_with_copy_on_hit(self, node):
        alice, carol = self._seed_accounts(node)
        params = self._params(alice, carol)
        r1 = dispatch(Context(node, dict(params), Role.GUEST),
                      "ripple_path_find")
        assert "error" not in r1
        h0 = node.read_cache.get_json()["hits"]
        r2 = dispatch(Context(node, dict(params), Role.GUEST),
                      "ripple_path_find")
        assert node.read_cache.get_json()["hits"] == h0 + 1
        r1["status"] = "annotated"  # door annotation must not leak back
        r3 = dispatch(Context(node, dict(params), Role.GUEST),
                      "ripple_path_find")
        assert "status" not in r3 and r3 == r2
        # a new validated close opens a new epoch: miss again
        node.close_ledger()
        dispatch(Context(node, dict(params), Role.GUEST),
                 "ripple_path_find")
        assert node.read_cache.get_json()["hits"] == h0 + 2

    def test_path_find_create_shares_the_cache(self, node):
        """HTTP-degenerate path_find create is the same pure search —
        it must hit the ripple_path_find slot, and the cached entry
        must tolerate the door's `id` annotation (copy-on-hit)."""
        alice, carol = self._seed_accounts(node)
        params = self._params(alice, carol)
        dispatch(Context(node, dict(params), Role.GUEST),
                 "ripple_path_find")
        h0 = node.read_cache.get_json()["hits"]
        r = dispatch(Context(node, dict(params), Role.GUEST), "path_find")
        assert "error" not in r
        assert node.read_cache.get_json()["hits"] == h0 + 1
        r2 = dispatch(Context(node, dict(params), Role.GUEST),
                      "ripple_path_find")
        assert "id" not in r2  # create's annotation stayed out of cache

    def test_fee_class(self):
        assert rpc_method_fee("path_find") is FEE_PATH_FIND
        assert rpc_method_fee("ripple_path_find") is FEE_PATH_FIND
        assert FEE_PATH_FIND.cost > rpc_method_fee("account_info").cost

    def test_door_ladder_warn_then_refuse(self):
        """FEE_PATH_FIND at the door: a path-spam client crosses WARN
        (advisory load warning) and then the drop line (hard slowDown
        refusal) in a handful of requests; admins are exempt."""
        node = types.SimpleNamespace(
            rpc_resources=ResourceManager(admin={"10.0.0.1"}))
        ip = "9.9.9.9"
        assert charge_rpc_client(node, ip, "path_find", Role.GUEST) is None
        assert rpc_warning(node, ip, Role.GUEST) is None  # 400 < WARN
        assert charge_rpc_client(node, ip, "path_find", Role.GUEST) is None
        assert rpc_warning(node, ip, Role.GUEST) == "load"  # 800 >= WARN
        refused = None
        for _ in range(4):
            refused = charge_rpc_client(node, ip, "path_find", Role.GUEST)
            if refused is not None:
                break
        assert refused is not None and refused["error"] == "slowDown"
        # admin IP and admin role never throttle
        for _ in range(10):
            assert charge_rpc_client(
                node, "10.0.0.1", "path_find", Role.GUEST) is None
            assert charge_rpc_client(
                node, ip, "path_find", Role.ADMIN) is None
