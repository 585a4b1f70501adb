"""Ledger-close pipeline: ordered async persistence off the close path.

Covers the pipeline contracts the node relies on:
- equivalence: a multi-ledger flood closed through the pipeline yields
  byte-identical ledger hashes, per-tx results, and stored history vs
  the serial close path;
- drain-on-stop: nothing persisted is lost and the CLF resume pointer
  lands on the last closed ledger;
- read-your-writes: header/txn fetches for a queued-but-unpersisted
  ledger resolve from the in-flight entry;
- backpressure: a full queue blocks the submitter instead of growing;
- strict order: the CLF pointer never observes N+1 before N;
- metrics: stage histograms + queue gauges surface in get_counts /
  server_state.
"""

import os
import sys
import threading
import time

import pytest

from stellard_tpu.node.closepipeline import ClosePipeline, LatencyHist
from stellard_tpu.node.config import Config
from stellard_tpu.node.node import Node
from stellard_tpu.protocol.formats import TxType
from stellard_tpu.protocol.keys import KeyPair
from stellard_tpu.protocol.sfields import sfAmount, sfDestination
from stellard_tpu.protocol.stamount import STAmount
from stellard_tpu.protocol.sttx import SerializedTransaction
from stellard_tpu.rpc.handlers import Context, dispatch

BENCH = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
MASTER = KeyPair.from_passphrase("masterpassphrase")
DESTS = [KeyPair.from_passphrase(f"cp-dest-{i}").account_id for i in range(4)]


def _payments(n, start_seq=1):
    txs = []
    for i in range(n):
        tx = SerializedTransaction.build(
            TxType.ttPAYMENT, MASTER.account_id, start_seq + i, 10,
            {sfAmount: STAmount.from_drops(250_000_000),
             sfDestination: DESTS[i % len(DESTS)]},
        )
        tx.sign(MASTER)
        txs.append(tx)
    return txs


def _drive(node, txs, per_ledger):
    """Submit + close every per_ledger txs -> (hashes, {txid: int(ter)}).
    Closes via ops.accept_ledger — the PIPELINED path (Node.close_ledger
    is the synchronous-durable test convenience and would flush)."""
    hashes = []
    results_all = {}
    for start in range(0, len(txs), per_ledger):
        for tx in txs[start : start + per_ledger]:
            node.submit(SerializedTransaction.from_bytes(tx.serialize()))
        closed, results = node.ops.accept_ledger()
        hashes.append(closed.hash())
        results_all.update({k: int(v) for k, v in results.items()})
    return hashes, results_all


class TestEquivalence:
    def test_pipelined_flood_matches_serial(self):
        txs = _payments(90)
        runs = {}
        for mode, enabled in (("pipelined", True), ("serial", False)):
            node = Node(Config(close_pipeline_enabled=enabled)).setup()
            hashes, results = _drive(node, txs, per_ledger=30)
            assert node.close_pipeline.flush(timeout=60)
            stored = [
                node.txdb.get_transaction(tx.txid()) for tx in txs
            ]
            headers = [
                node.txdb.get_ledger_header(seq=s)
                for s in range(2, 2 + len(hashes))
            ]
            clf = node.clf.last_closed_hash
            runs[mode] = (hashes, results, stored, headers, clf)
            node.stop()

        p, s = runs["pipelined"], runs["serial"]
        assert p[0] == s[0], "ledger hashes diverge between modes"
        assert p[1] == s[1], "per-tx results diverge between modes"
        assert all(r is not None for r in p[2]), "pipelined run lost tx rows"
        assert p[2] == s[2], "stored tx rows diverge between modes"
        assert all(h is not None for h in p[3]), "pipelined run lost headers"
        assert p[3] == s[3], "stored headers diverge between modes"
        assert p[4] == s[4] == p[0][-1], "CLF pointer not on the last close"

    def test_serial_mode_bypasses_worker(self):
        node = Node(Config(close_pipeline_enabled=False)).setup()
        _drive(node, _payments(10), per_ledger=10)
        assert node.close_pipeline.persisted == 0
        assert node.txdb.get_ledger_header(seq=2) is not None
        node.stop()


class TestDrainOnStop:
    def test_stop_drains_everything_queued(self, tmp_path):
        from stellard_tpu.node.txdb import TxDatabase
        from stellard_tpu.state.clf import LedgerSqlDatabase

        db = str(tmp_path / "drain.db")
        node = Node(Config(close_pipeline_depth=16, database_path=db)).setup()
        txs = _payments(60)
        hashes, _ = _drive(node, txs, per_ledger=15)
        # stop immediately — whatever is still queued must persist first
        node.stop()
        # reopen the FILES: drain-on-stop means everything closed before
        # stop() is durable and the CLF pointer is on the last close
        txdb = TxDatabase(db)
        try:
            for seq in range(2, 2 + len(hashes)):
                assert txdb.get_ledger_header(seq=seq) is not None
            for tx in txs:
                assert txdb.get_transaction(tx.txid()) is not None
        finally:
            txdb.close()
        clf = LedgerSqlDatabase(db + ".clf")
        try:
            assert clf.get_state("LastClosedLedger") == hashes[-1]
        finally:
            clf.close()


class TestReadYourWrites:
    def _gated_node(self):
        """Node whose pipeline save stage blocks until `gate` is set, so a
        close stays queued-but-unpersisted for the duration of a test."""
        node = Node(Config()).setup()
        gate = threading.Event()
        inner = node.close_pipeline.save_stage

        def blocking_save(led):
            gate.wait(timeout=30)
            inner(led)

        node.close_pipeline.save_stage = blocking_save
        return node, gate

    def test_queued_ledger_header_and_txns_resolve(self):
        node, gate = self._gated_node()
        try:
            txs = _payments(5)
            for tx in txs:
                node.submit(tx)
            closed, _ = node.ops.accept_ledger()
            h = closed.hash()
            txid = txs[0].txid()
            # not yet in the stores
            assert node.txdb.get_transaction(txid) is None
            assert node.txdb.get_ledger_header(seq=closed.seq) is None
            # in-flight entry resolves by hash and by seq
            assert node.close_pipeline.get(h) is closed
            assert node.close_pipeline.get_by_seq(closed.seq) is closed
            # the tx RPC serves the queued tx
            out = dispatch(Context(node, {"transaction": txid.hex()}), "tx")
            assert out.get("ledger_index") == closed.seq
            assert "error" not in out
            # the ledger RPC resolves the queued seq
            out = dispatch(
                Context(node, {"ledger_index": str(closed.seq)}), "ledger"
            )
            assert "error" not in out
            # fetch_fallback (history-cache path) sees the in-flight entry
            assert node.ledger_master.fetch_fallback(h) is closed
        finally:
            gate.set()
            assert node.close_pipeline.flush(timeout=60)
            # after persist the stores serve it and the entry is gone
            assert node.txdb.get_transaction(txs[0].txid()) is not None
            assert node.close_pipeline.get(h) is None
            node.stop()


    def test_account_tx_sees_just_closed_ledger(self):
        """account_tx rides the SQL index, so it WAITS for the drain
        rather than merging in-flight entries — a tx reported COMMITTED
        must appear in account history immediately after the close."""
        node = Node(Config()).setup()
        try:
            txs = _payments(3)
            for tx in txs:
                node.submit(tx)
            node.ops.accept_ledger()  # pipelined: no flush
            out = dispatch(
                Context(node, {"account": MASTER.human_account_id}),
                "account_tx",
            )
            assert "error" not in out, out
            got = {t["tx"]["hash"].lower() for t in out["transactions"]}
            assert {tx.txid().hex() for tx in txs} <= got
        finally:
            node.stop()


class TestBackpressureAndOrder:
    def test_full_queue_blocks_submitter(self):
        release = threading.Event()
        started = threading.Event()
        order = []

        def slow_save(led):
            started.set()
            release.wait(timeout=30)

        pipe = ClosePipeline(
            save_stage=slow_save,
            txdb_stage=lambda led, results: None,
            clf_stage=lambda led: order.append(led.seq),
            depth=1,
        )

        class FakeLedger:
            def __init__(self, seq):
                self.seq = seq

            def hash(self):
                return self.seq.to_bytes(32, "big")

        pipe.submit_close(FakeLedger(1), {})  # drains into the worker
        assert started.wait(timeout=10)
        pipe.submit_close(FakeLedger(2), {})  # fills the depth-1 queue

        blocked_done = threading.Event()
        t = threading.Thread(
            target=lambda: (pipe.submit_close(FakeLedger(3), {}),
                            blocked_done.set()),
        )
        t.start()
        assert not blocked_done.wait(timeout=0.5), "submit did not block"
        release.set()
        assert blocked_done.wait(timeout=10), "submit never unblocked"
        t.join()
        assert pipe.stop(timeout=30)
        # strict order: CLF commits observed 1, 2, 3 — never out of order
        assert order == [1, 2, 3]
        assert pipe.backpressure_waits >= 1

    def test_stop_during_backpressure_fails_the_blocked_submitter(self):
        """stop() while a submitter is blocked in backpressure: the entry
        must take the on_failed path, never strand with no worker left."""
        release = threading.Event()
        failed = threading.Event()

        def slow_save(led):
            release.wait(timeout=30)

        pipe = ClosePipeline(
            save_stage=slow_save,
            txdb_stage=lambda led, results: None,
            clf_stage=lambda led: None,
            depth=1,
        )

        class FakeLedger:
            def __init__(self, seq):
                self.seq = seq

            def hash(self):
                return self.seq.to_bytes(32, "big")

        pipe.submit_close(FakeLedger(1), {})  # drains into the worker
        pipe.submit_close(FakeLedger(2), {})  # fills the depth-1 queue
        t = threading.Thread(
            target=lambda: pipe.submit_close(
                FakeLedger(3), {}, on_failed=failed.set
            )
        )
        t.start()
        # begin stop() while the WORKER is still blocked in the save
        # stage: the queue stays full, so the blocked submitter can only
        # leave its wait via the _stopping path — deterministic
        stopper = threading.Thread(target=lambda: pipe.stop(timeout=30))
        stopper.start()
        assert failed.wait(timeout=10), (
            "blocked submitter's on_failed never fired"
        )
        release.set()  # let the worker drain 1 and 2; stop() completes
        stopper.join(timeout=30)
        t.join(timeout=10)
        assert not t.is_alive(), "submitter still blocked after stop()"
        assert pipe.pending() == 0, "entry stranded in a dead pipeline"

    def test_failed_persist_releases_accounting_and_continues(self):
        failures = []
        boom = {"on": True}

        def bad_txdb(led, results):
            if boom["on"]:
                raise RuntimeError("disk on fire")

        pipe = ClosePipeline(
            save_stage=lambda led: None,
            txdb_stage=bad_txdb,
            clf_stage=lambda led: None,
            depth=4,
        )

        class FakeLedger:
            def __init__(self, seq):
                self.seq = seq

            def hash(self):
                return self.seq.to_bytes(32, "big")

        pipe.submit_close(FakeLedger(1), {}, on_failed=lambda: failures.append(1))
        assert pipe.flush(timeout=10)
        assert failures == [1] and pipe.failed == 1
        boom["on"] = False
        done = []
        pipe.submit_close(FakeLedger(2), {}, done=lambda r: done.append(2))
        assert pipe.flush(timeout=10)
        assert done == [2], "worker died after a failed persist"
        assert pipe.stop(timeout=10)


class _SeqLedger:
    def __init__(self, seq):
        self.seq = seq

    def hash(self):
        return self.seq.to_bytes(32, "big")


class TestDeferredStart:
    """The standalone close queues its ledger first of the sinks and
    wakes the drain when the closing thread is through with the rest:
    the persist does not run beside `accept_ledger`'s tail."""

    @staticmethod
    def _pipe(started):
        return ClosePipeline(
            save_stage=lambda led: started.set(),
            txdb_stage=lambda led, results: None,
            clf_stage=lambda led: None,
        )

    @pytest.mark.parametrize("waker", ["wake", "flush", "wait_for_closes"])
    def test_unwoken_entry_is_readable_and_waits_for_its_waker(self, waker):
        started = threading.Event()
        pipe = self._pipe(started)
        pipe.submit_close(_SeqLedger(1), {})  # the worker exists and idles
        assert pipe.flush(timeout=10)
        started.clear()
        led = _SeqLedger(2)
        pipe.submit_close(led, {}, wake=False)
        # read-your-writes sees it at once; the drain has not begun
        assert pipe.get_by_seq(2) is led and pipe.pending() == 1
        assert not started.wait(timeout=0.2)
        # the close's end, or anybody who waits for the ledger
        assert getattr(pipe, waker)() in (None, True)
        assert started.wait(timeout=0.5), "the waker did not start the drain"
        assert pipe.flush(timeout=10) and pipe.persisted == 2
        assert pipe.stop(timeout=10)

    def test_held_entry_outlasts_the_drains_polls_and_stop_drains_it(self):
        started = threading.Event()
        pipe = self._pipe(started)
        # a worker that has just started looks before it sleeps: held too
        pipe.submit_close(_SeqLedger(1), {}, wake=False)
        pipe.submit_repair(_SeqLedger(7))  # behind it, in order
        assert not started.wait(timeout=1.5)
        assert pipe.pending() == 2
        assert pipe.stop(timeout=10)
        assert started.is_set() and pipe.persisted == 2

    def test_a_sink_that_raises_still_lets_the_drain_start(self):
        node = Node(Config()).setup()

        def bad_sink(led, results):
            raise RuntimeError("a stream's sink fell over")

        node.ops.on_ledger_closed.append(bad_sink)
        for tx in _payments(2):
            node.submit(SerializedTransaction.from_bytes(tx.serialize()))
        with pytest.raises(RuntimeError):
            node.ops.accept_ledger()
        node.ops.on_ledger_closed.remove(bad_sink)
        deadline = time.monotonic() + 10
        while node.close_pipeline.persisted < 1:  # no flush: it would wake
            assert time.monotonic() < deadline, "the ledger stayed held"
            time.sleep(0.01)
        assert node.txdb.get_ledger_header(seq=2) is not None
        node.stop()

    def test_standalone_persist_starts_after_every_other_sink(self):
        node = Node(Config()).setup()
        order = []
        save = node.close_pipeline.save_stage
        node.close_pipeline.save_stage = lambda led: (
            order.append(("persist", led.seq)), save(led))

        def late_sink(led, results):
            # registered behind the pipeline's sink, as the streams are
            assert node.close_pipeline.get_by_seq(led.seq) is led
            time.sleep(0.3)
            order.append(("sink", led.seq))

        node.ops.on_ledger_closed.append(late_sink)
        _drive(node, _payments(6), per_ledger=2)
        assert node.close_pipeline.flush(timeout=60)
        # a ledger's persist begins once its sinks are through: the
        # first close's too, whose worker had yet to start
        assert [o for o in order if o[1] == 2] == [("sink", 2), ("persist", 2)]
        for seq in (3, 4):
            assert order.index(("sink", seq)) < order.index(("persist", seq))
        node.stop()


class TestMetrics:
    def test_counts_and_server_state_surface_pipeline(self):
        node = Node(Config()).setup()
        _drive(node, _payments(10), per_ledger=5)
        assert node.close_pipeline.flush(timeout=60)
        counts = dispatch(Context(node, {}), "get_counts")
        cp = counts["close_pipeline"]
        assert cp["persisted"] == 2
        assert set(cp["stages"]) == {
            "queue_wait", "nodestore", "txdb", "clf", "total"
        }
        assert cp["stages"]["total"]["count"] == 2
        assert cp["stages"]["total"]["p50_ms"] > 0
        assert counts["persist_backlog"] == 0
        state = dispatch(Context(node, {}), "server_state")
        assert state["state"]["close_pipeline"]["depth"] == 0
        node.stop()

    def test_sql_stages_say_what_they_bound_and_in_how_many_statements(self):
        """`persist.txdb` / `persist.clf` spans carry `rows` and
        `statements`; `get_counts` and the `/metrics` hook carry their
        sums; and they add up to what the tables hold. A row is whatever
        a statement bound: a header, a Transactions row, the id its
        AccountTransactions rows are deleted by, an AccountTransactions
        row; a changed entry's mirror row (or a deleted entry's key) and
        the pointer's two."""
        node = Node(Config(rpc_port=0)).setup().serve()  # serve(): the hooks
        _drive(node, _payments(40), per_ledger=10)
        assert node.close_pipeline.flush(timeout=60)
        spans = {"persist.txdb": [], "persist.clf": []}
        events = node.tracer.chrome_trace()["traceEvents"]
        for ev in events:
            if ev.get("ph") == "X" and ev["name"] in spans:
                spans[ev["name"]].append(ev["args"])
        assert len(spans["persist.txdb"]) == len(spans["persist.clf"]) == 4
        cp = dispatch(Context(node, {}), "get_counts")["close_pipeline"]
        hook = node.collector.instruments_snapshot()["hooks"]
        for stage, name in (("txdb", "persist.txdb"), ("clf", "persist.clf")):
            for what in ("rows", "statements"):
                total = sum(a[what] for a in spans[name])
                assert cp[f"{stage}_{what}"] == total > 0
                assert hook[f"close_pipeline.{stage}_{what}"] == total
        tables = node.txdb.counts()
        assert tables["transactions"] == 40
        # the genesis header went in by itself, before the pipeline
        assert cp["txdb_rows"] == (
            (tables["ledgers"] - 1) + 2 * tables["transactions"]
            + tables["account_transactions"])
        # a header, one REPLACE, one DELETE, one INSERT a ledger
        assert cp["txdb_statements"] == 4 * 4
        # the first commit finds a fresh mirror and imports the state:
        # the master and four destinations, and the pointer's two rows;
        # every later one rewrites those five accounts
        assert [a["rows"] for a in spans["persist.clf"]] == [7, 7, 7, 7]
        assert node.clf.db.count("accounts") == 5
        assert node.clf.full_imports == 1 and node.clf.commits == 3
        # accounts and the pointer: two statements a commit
        assert cp["clf_statements"] == 4 * 2
        node.stop()
        # and the benchmark's reader of these spans reads their ratio
        sys.path.insert(0, BENCH)
        from yardstick import manifest, readers

        got = readers.read_metric(manifest.reader_file(
            BENCH, "persist.rows_per_statement"), {"spans": events})
        assert got == (cp["txdb_rows"] + cp["clf_rows"]) / (16 + 8)

    def test_latency_hist_quantiles(self):
        h = LatencyHist()
        assert h.quantile(0.5) == 0.0
        for ms in (0.5, 1.5, 3.0, 8.0, 40.0):
            h.record(ms)
        j = h.get_json()
        assert j["count"] == 5
        assert j["max_ms"] == 40.0
        assert j["p50_ms"] == 5.0  # bucket upper bound holding the median
        assert h.quantile(1.0) == 50.0


class TestConfigKnobs:
    def test_close_pipeline_section_parses(self):
        cfg = Config.from_ini(
            "[close_pipeline]\nenabled=0\ndepth=3\n"
        )
        assert cfg.close_pipeline_enabled is False
        assert cfg.close_pipeline_depth == 3
        cfg = Config.from_ini("[close_pipeline]\nenabled=1\n")
        assert cfg.close_pipeline_enabled is True
        assert Config().close_pipeline_enabled is True
