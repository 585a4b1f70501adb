"""The cell ``quorum4.close`` (a validator in its quorum) on the CPU at
the rehearsal's toy size, and what the program grew for it:

- the cell's driver run once over four validators on loopback TCP and
  TLS (the rehearsal's ``[clock_speed]`` 2.5: a round's floor is two seconds), every check of
  ``correct`` passing, every validated ledger re-closed on the plain
  path, and each check shown able to fail;
- ``close_with_txset`` over an agreed set that differs from the open
  ledger, against the plain path, byte for byte, with delta replay on;
- the new spans and counters against hand-counted streams;
- the new ``layers/`` readers on synthetic spans;
- the repairs the cell forced (a burst's first sighting, a dispute's
  relay, leftovers in sequence, ``tx``'s ``validated`` along the
  quorum's chain, who waits for room in a peer's queue and what a full
  queue sheds);
- the door's hold: a networked node's door takes a client's ``submit``
  in no faster than the open ledger may fill.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
import time
import types

import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

from yardstick import manifest, nodedrive, readers  # noqa: E402

from stellard_tpu.consensus.consensus import LedgerConsensus  # noqa: E402
from stellard_tpu.engine.engine import TxParams  # noqa: E402
from stellard_tpu.node.hashrouter import SF_RELAYED  # noqa: E402
from stellard_tpu.node.ledgermaster import LedgerMaster  # noqa: E402
from stellard_tpu.node.ledgertools import replay_ledger  # noqa: E402
from stellard_tpu.node.tracer import Tracer  # noqa: E402
from stellard_tpu.node.verifyplane import VerifyPlane  # noqa: E402
from stellard_tpu.nodestore.core import make_database  # noqa: E402
from stellard_tpu.overlay.simnet import SimNet  # noqa: E402
from stellard_tpu.overlay.wire import TxMessage, frame  # noqa: E402
from stellard_tpu.protocol.formats import TxType  # noqa: E402
from stellard_tpu.protocol.keys import KeyPair  # noqa: E402
from stellard_tpu.protocol.sfields import sfAmount, sfDestination  # noqa: E402
from stellard_tpu.protocol.stamount import STAmount  # noqa: E402
from stellard_tpu.protocol.sttx import SerializedTransaction  # noqa: E402
from stellard_tpu.protocol.ter import TER  # noqa: E402

CELL = "quorum4.close"
MASTER = KeyPair.from_passphrase("masterpassphrase")
XRP = 1_000_000


def load_driver():
    spec = importlib.util.spec_from_file_location(
        "quorum_driver", os.path.join(BENCH, "drivers", "quorum.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


driver = load_driver()


def toy_context(work_root: str, cache_dir: str, **traffic):
    """The cell's own files at the rehearsal's sizes, every validator on
    the plain arms (no JAX in a tier-1 test)."""
    m = manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    files = manifest.cell_files(m, CELL, REPO, rehearsal=True)
    ini = nodedrive.plain_reference_ini(files["ini"])
    assert "[clock_speed]\n2.5" in ini  # a round's floor is two seconds
    config = dict(files["config"])
    config["ini_replace"] = dict(config["ini_replace"])
    # three validated rounds that carry transactions: one warms up,
    # one or more are the window, one settles
    tr = dict(files["traffic"], warmup_rounds=1, settle_rounds=1,
              **traffic)
    said: list[str] = []
    ctx = types.SimpleNamespace(
        seed=7, seconds=3.0, trace=True, rehearsal=True, config=config,
        ini_template=ini, traffic=tr, cache_dir=cache_dir,
        work_root=work_root, say=said.append, said=said, cap=None)

    def capture():
        from yardstick.capture import Capture

        # the program's spans without a profiler: the capture's state
        # stays idle, so nothing of jax is touched
        ctx.cap = Capture(True, os.path.join(work_root, "trace"))
        ctx.cap.start = lambda: None
        ctx.cap.finish = lambda: None
        return ctx.cap

    ctx.capture = capture
    return ctx


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    root = tmp_path_factory.mktemp("quorum4")
    ctx = toy_context(str(root / "work"), str(root / "cache"))
    os.makedirs(ctx.work_root)
    t0 = time.monotonic()
    result = driver.run(ctx)
    return types.SimpleNamespace(ctx=ctx, result=result,
                                 seconds=time.monotonic() - t0)


class TestToyNet:
    def test_every_check_of_correct_passes(self, toy_run):
        r = toy_run.result
        assert r["problems"] == []
        assert r["correct"] is True
        window = r["sources"]["counters"]
        assert window["closes"] >= 1  # validated rounds inside the window
        assert window["txs"] > 0 and r["attempted"] > 0
        assert r["end_to_end"]["validated_tx_per_s"] > 0
        # nobody submitted to the measured validator: all by relay
        assert window["relay.txs_in"] >= window["txs"]
        assert window["ops.bad_sig"] == 0
        assert window["quorum.peer_lag_ledgers"] in (0, 1)

    def test_the_window_runs_from_a_validated_ledger_to_one(self, toy_run):
        r = toy_run.result
        assert r["sources"]["counters"]["window_s"] >= toy_run.ctx.seconds
        carrying = [seq for seq, _h in r["validated_ledgers"]]
        assert len(carrying) >= 3

    def test_every_validated_ledger_recloses_on_the_plain_path(self, toy_run):
        from stellard_tpu.node.config import Config

        cfg = Config.from_ini(toy_run.result["stores"][driver.MEASURED])
        db = make_database(type=cfg.node_db_type, path=cfg.node_db_path)
        try:
            for seq, h in toy_run.result["validated_ledgers"]:
                stats = replay_ledger(db, bytes.fromhex(h))
                assert stats["ok"] and stats["state_hash_ok"] \
                    and stats["tx_hash_ok"], (seq, stats)
                assert stats["replayed_hash"] == h
        finally:
            db.close()

    def test_the_new_metrics_read_the_run(self, toy_run):
        sources = toy_run.result["sources"]
        m = manifest.load(os.path.join(REPO, "BENCHMARK.json"))
        mine = [x for x in m["per_layer"] if x.get("workloads") == [CELL]]
        # PR 32's twelve and PR 34's `host.net_cpu_share`
        assert len(mine) == 13
        for x in mine:
            value = readers.read_metric(
                manifest.reader_file(BENCH, x["name"]), sources)
            assert value is not None, x["name"]
        assert readers.read_metric(manifest.reader_file(
            BENCH, "netverify.sigs_per_batch"), sources) == 1.0

    def test_no_child_is_left(self, toy_run):
        assert _alive_under(toy_run.ctx.work_root) == []


def _alive_under(path: str) -> list[str]:
    out = subprocess.run(["pgrep", "-fa", path], capture_output=True,
                         text=True).stdout.splitlines()
    return [line for line in out if "pgrep" not in line]


def test_no_child_is_left_when_a_peer_fails_to_start(tmp_path):
    ctx = toy_context(str(tmp_path / "work"), str(tmp_path / "cache"),
                      mesh_timeout_s=60)
    os.makedirs(ctx.work_root)
    # one section the peers' daemon refuses: they exit at once
    ctx.config["ini_replace"]["[peer_ssl]\nrequire"] = "[peer_ssl]\nnonsense"
    with pytest.raises(SystemExit) as why:
        driver.run(ctx)
    assert "exited" in str(why.value)
    assert _alive_under(ctx.work_root) == []
    assert LedgerConsensus.accept.__name__ == "accept"
    assert not hasattr(LedgerConsensus.accept, "__wrapped__")


# --------------------------------------------------------------------------
# each check of `correct` can fail


class TestChecksCanFail:
    def test_a_doctored_hash_on_one_peer(self):
        problems: list = []
        driver.check_agreement({7: ["AA" * 32] * 4,
                                8: ["BB" * 32] * 3 + ["CC" * 32]}, problems)
        assert len(problems) == 1 and "sequence 8 has 2 hashes" in problems[0]

    def test_a_measured_validator_two_rounds_behind(self):
        problems: list = []
        driver.check_lag([10, 11, 11, 10], problems)
        assert problems == []
        driver.check_lag([9, 11, 11, 10], problems)
        assert len(problems) == 1

    @pytest.mark.parametrize("signers,own,bad", [
        ((0, 1, 2), 0, False),      # three, ours among them
        ((0, 1), 0, True),          # a ledger with 2 validations
        ((1, 2, 3), 0, True),       # three, but not ours
    ])
    def test_quorum(self, signers, own, bad):
        keys = [KeyPair.from_passphrase(f"q-{i}") for i in range(4)]
        vals = [types.SimpleNamespace(signer=keys[i].public, trusted=True)
                for i in signers]
        vals.append(types.SimpleNamespace(signer=b"x" * 33, trusted=False))
        problems: list = []
        driver.check_quorum([(5, b"h" * 32)], lambda h: vals,
                            keys[own].public, 3, problems)
        assert bool(problems) is bad

    @pytest.mark.parametrize("answer,bad", [
        ({"hash": "AB" * 32, "ledger_index": 9, "validated": True,
          "meta": {"TransactionResult": "tesSUCCESS"}}, False),
        ({"error": "txnNotFound"}, True),  # missing from a peer
        ({"hash": "AB" * 32, "ledger_index": 9, "validated": False,
          "meta": {"TransactionResult": "tesSUCCESS"}}, True),
        ({"hash": "AB" * 32, "ledger_index": 4, "validated": True,
          "meta": {"TransactionResult": "tesSUCCESS"}}, True),
    ])
    def test_read_back(self, monkeypatch, answer, bad):
        monkeypatch.setattr(driver, "rpc", lambda *a, **k: answer)
        problems: list = []
        driver.check_validated_transactions(
            1, [bytes.fromhex("AB" * 32)], {9, 10}, problems)
        assert bool(problems) is bad

    def test_account_arithmetic(self, monkeypatch):
        from yardstick import workload

        model = workload.BalanceModel(1000, 10)
        model.applied(0, 1, 100)
        good = {"account_data": {"Balance": "890", "Sequence": 2}}
        monkeypatch.setattr(driver, "rpc", lambda *a, **k: good)
        problems: list = []
        driver.check_accounts_at(1, "AB" * 32, model, "bench-pop-v1", [0], problems)
        assert problems == []
        driver.check_accounts_at(1, "AB" * 32, model, "bench-pop-v1", [1], problems)
        assert len(problems) == 1

    def test_a_tec_claims_the_fee(self):
        assert driver.claimed_fee("tecUNFUNDED_PAYMENT")
        assert driver.claimed_fee(104)
        assert not driver.claimed_fee("tesSUCCESS") \
            and not driver.claimed_fee(0) and not driver.claimed_fee(None)


# --------------------------------------------------------------------------
# the consensus close against the plain path


def payment(sender: KeyPair, seq: int, dest: bytes, drops: int):
    tx = SerializedTransaction.build(
        TxType.ttPAYMENT, sender.account_id, seq, 10,
        {sfAmount: STAmount.from_drops(drops), sfDestination: dest})
    tx.sign(sender)
    return tx


def chain(delta_replay: bool) -> LedgerMaster:
    lm = LedgerMaster()
    lm.delta_replay = delta_replay
    lm.start_new_ledger(MASTER.account_id, close_time=1000)
    return lm


class TestCloseWithTxset:
    def accounts(self):
        return [KeyPair.from_passphrase(f"cwt-{i}") for i in range(6)]

    def funded(self, lm: LedgerMaster):
        """One closed ledger in which the master funds six accounts."""
        accts = self.accounts()
        txs = [payment(MASTER, 1 + i, a.account_id, 5000 * XRP)
               for i, a in enumerate(accts)]
        for tx in txs:
            lm.do_transaction(tx, TxParams.OPEN_LEDGER)
        lm.close_with_txset(txs, 2000, 30)
        return accts

    def test_an_agreed_set_that_differs_from_the_open_ledger(self):
        fast, plain = chain(True), chain(False)
        accts = self.funded(fast)
        self.funded(plain)
        assert fast.closed_ledger().hash() == plain.closed_ledger().hash()
        # the open ledger holds five payments, two of one sender ...
        mine = [payment(accts[i], 1, accts[(i + 1) % 6].account_id, 7 * XRP)
                for i in range(4)]
        mine.append(payment(accts[0], 2, accts[3].account_id, 9 * XRP))
        for tx in mine:
            ter, applied = fast.do_transaction(
                tx, TxParams.OPEN_LEDGER | TxParams.RETRY)
            assert ter == TER.tesSUCCESS and applied
        # ... the net agreed on a set without one of them (accts[2]'s)
        # and with one this node never saw (accts[5]'s)
        extra = payment(accts[5], 1, accts[0].account_id, 11 * XRP)
        agreed = [tx for tx in mine if tx.account != accts[2].account_id]
        agreed.append(extra)
        closed, results = fast.close_with_txset(agreed, 3000, 30)
        reference, ref_results = plain.close_with_txset(agreed, 3000, 30)
        assert closed.hash() == reference.hash()
        assert closed.state_map.get_hash() == reference.state_map.get_hash()
        assert closed.tx_map.get_hash() == reference.tx_map.get_hash()
        assert {k: int(v) for k, v in results.items()} \
            == {k: int(v) for k, v in ref_results.items()}
        assert len(results) == 5 and set(map(int, results.values())) == {0}
        dj = fast.delta_replay_json()
        assert dj["spliced"] + dj["fallback"] >= 5 and dj["spliced"] >= 1
        # the transaction left out is back in the next open ledger
        left = {txid for txid, _b, _m in fast.current_ledger().tx_entries()}
        assert left == {mine[2].txid()}

    def test_leftovers_go_back_in_sequence(self):
        lm = chain(True)
        accts = self.funded(lm)
        # three of one account, in an open ledger whose tx map hands
        # them back in txid order
        mine = [payment(accts[1], 1 + i, accts[2].account_id, (3 + i) * XRP)
                for i in range(3)]
        for tx in mine:
            lm.do_transaction(tx, TxParams.OPEN_LEDGER | TxParams.RETRY)
        lm.close_with_txset([], 3000, 30)
        left = {txid for txid, _b, _m in lm.current_ledger().tx_entries()}
        assert left == {tx.txid() for tx in mine}
        assert lm.take_held_transactions() == []


# --------------------------------------------------------------------------
# spans and counters against hand-counted streams


def complete(tracer, name):
    return [ev for ev in tracer.chrome_trace()["traceEvents"]
            if ev.get("ph") == "X" and ev["name"] == name]


class TestRelayCounters:
    def node(self):
        net = SimNet(4, quorum=3)
        net.start()
        v = net.validators[0]
        plane = VerifyPlane(backend="cpu")
        tracer = Tracer(enabled=True, sample=1.0)
        v.node.verify_many = plane.verify_many
        v.node.lm.tracer = tracer
        plane.tracer = tracer
        return net, v, plane, tracer

    def test_a_burst_of_five_with_two_duplicates(self):
        net, v, plane, tracer = self.node()
        try:
            dest = KeyPair.from_passphrase("relay-dest").account_id
            txs = [payment(MASTER, 1 + i, dest, 300 * XRP) for i in range(3)]
            burst = [txs[0], txs[1], txs[0], txs[2], txs[1]]
            v.deliver(1, b"".join(frame(TxMessage(t.serialize()))
                                  for t in burst))
            spans = complete(tracer, "relay.tx_batch")
            assert len(spans) == 1
            assert {k: spans[0]["args"][k] for k in
                    ("n", "verified", "duplicates", "already_flagged")} == {
                "n": 5, "verified": 3, "duplicates": 2, "already_flagged": 0}
            batches = complete(tracer, "verify.batch")
            assert [(b["args"]["n"], b["args"]["source"]) for b in batches] \
                == [(3, "relay")]
            assert batches[0]["args"]["parent"] == spans[0]["args"]["span"]
            stats = v.node.relay_stats.snapshot()
            assert (stats["batches"], stats["sigs_verified"],
                    stats["singles"]) == (1, 3, 0)
            # the same burst again: every verdict is known, nothing to
            # verify, no span
            v.deliver(2, b"".join(frame(TxMessage(t.serialize()))
                                  for t in burst))
            assert len(complete(tracer, "relay.tx_batch")) == 1
            assert v.node.relay_stats.snapshot()["batches"] == 1
            # one transaction alone is a single
            lone = payment(MASTER, 4, dest, 300 * XRP)
            v.deliver(1, frame(TxMessage(lone.serialize())))
            stats = v.node.relay_stats.snapshot()
            assert (stats["batches"], stats["sigs_verified"],
                    stats["singles"]) == (1, 4, 1)
            assert [b["args"]["source"] for b in
                    complete(tracer, "verify.batch")] == ["relay", "relay"]
        finally:
            plane.stop()

    def test_proposals_and_validations_by_kind(self):
        net, v, plane, tracer = self.node()
        try:
            for _ in range(40):
                net.step()
                if v.node.rounds_completed >= 2:
                    break
            assert v.node.rounds_completed >= 2
            nv = v.node.netverify_stats.snapshot()
            # one object a call, so as many signatures as batches
            assert nv["proposal_batches"] == nv["proposal_sigs"] > 0
            assert nv["validation_batches"] == nv["validation_sigs"] > 0
            sources = [b["args"]["source"]
                       for b in complete(tracer, "verify.batch")]
            assert sources.count("proposal") == nv["proposal_sigs"]
            assert sources.count("validation") == nv["validation_sigs"]
            assert all(b["args"]["n"] == 1
                       for b in complete(tracer, "verify.batch"))
        finally:
            plane.stop()


class TestRoundSpans:
    def test_a_round_is_a_tree_of_intervals(self):
        net = SimNet(4, quorum=3)
        net.start()
        v = net.validators[0]
        tracer = Tracer(enabled=True, sample=1.0)
        v.node.lm.tracer = tracer
        v.node.begin_round()  # a round that records into this tracer
        dest = KeyPair.from_passphrase("round-dest").account_id
        net.validators[1].submit_client_tx(
            payment(MASTER, 1, dest, 300 * XRP))
        for _ in range(60):
            net.step()
            if v.node.rounds_completed >= 3:
                break
        rounds = complete(tracer, "consensus.round")
        assert len(rounds) == v.node.rounds_completed >= 3
        by_parent: dict = {}
        for name in ("consensus.open", "consensus.establish",
                     "consensus.accept"):
            for ev in complete(tracer, name):
                by_parent.setdefault(ev["args"]["parent"], []).append(ev)
        for r in rounds:
            args = r["args"]
            assert set(args) >= {"proposers", "txs", "disputes",
                                 "position_changes", "round_ms"}
            assert args["proposers"] == 3
            kids = by_parent[args["span"]]
            assert [k["name"] for k in kids] == [
                "consensus.open", "consensus.establish", "consensus.accept"]
            lo, hi = r["ts"], r["ts"] + r["dur"]
            assert all(lo <= k["ts"] and k["ts"] + k["dur"] <= hi + 1
                       for k in kids)
            # open, establish and accept tile the round
            assert sum(k["dur"] for k in kids) <= r["dur"] + 3
        assert sum(r["args"]["txs"] for r in rounds) == 1
        # the close's own spans lie under the accept
        accepts = {ev["args"]["span"]
                   for ev in complete(tracer, "consensus.accept")}
        totals = complete(tracer, "close.total")
        assert totals and all(ev["args"]["parent"] in accepts
                              for ev in totals)
        validated = complete(tracer, "consensus.validated")
        assert len(validated) >= len(rounds) - 1
        assert all(ev["args"]["trusted"] >= 3 for ev in validated)
        # the instants stay
        names = {ev["name"] for ev in
                 tracer.chrome_trace()["traceEvents"] if ev.get("ph") == "i"}
        assert {"consensus.state", "consensus.propose_out",
                "consensus.proposal_in", "consensus.validation_out"} <= names


# --------------------------------------------------------------------------
# the readers on synthetic spans


def span(name, ts_ms, dur_ms, **args):
    return {"ph": "X", "name": name, "ts": ts_ms * 1000.0,
            "dur": dur_ms * 1000.0, "args": args}


def read(metric, sources):
    return readers.read_metric(manifest.reader_file(BENCH, metric), sources)


ROUNDS = [
    span("consensus.round", 0, 6000, disputes=10),
    span("consensus.round", 6000, 9000, disputes=40),
    span("consensus.round", 15000, 7000, disputes=1),
    span("consensus.open", 0, 2000), span("consensus.open", 6000, 3000),
    span("consensus.establish", 2000, 3000),
    span("consensus.establish", 9000, 5000),
    span("consensus.accept", 5000, 1000),
    span("consensus.validated", 6000, 400),
    span("consensus.validated", 15000, 200),
    {"ph": "i", "name": "consensus.state", "ts": 1.0, "args": {}},
]
COUNTERS = {"relay.sigs_verified": 900, "relay.batches": 250,
            "relay.singles": 50, "relay.duplicates": 1200,
            "relay.txs_in": 2000, "netverify.sigs": 40,
            "netverify.batches": 40, "overlay.msgs": 4500,
            "overlay.bytes": 5_000_000, "txs": 1000,
            "quorum.peer_lag_ledgers": 1}


@pytest.mark.parametrize("metric,want", [
    ("consensus.round_ms_p50", 7000.0),
    ("consensus.open_ms_per_round", 2500.0),
    ("consensus.establish_ms_per_round", 4000.0),
    ("consensus.accept_ms_per_round", 1000.0),
    ("consensus.validated_lag_ms", 300.0),
    ("consensus.disputes_per_round", 17.0),
    ("relay.sigs_per_batch", 3.0),
    ("relay.duplicate_share", 60.0),
    ("netverify.sigs_per_batch", 1.0),
    ("overlay.msgs_per_tx", 4.5),
    ("overlay.bytes_per_tx", 5000.0),
    ("quorum.peer_lag_ledgers", 1),
])
def test_reader(metric, want):
    assert read(metric, {"spans": ROUNDS, "counters": COUNTERS}) \
        == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "consensus.round_ms_p50", "consensus.open_ms_per_round",
    "consensus.establish_ms_per_round", "consensus.accept_ms_per_round",
    "consensus.validated_lag_ms", "consensus.disputes_per_round",
    "relay.sigs_per_batch", "relay.duplicate_share",
    "netverify.sigs_per_batch", "overlay.msgs_per_tx",
    "overlay.bytes_per_tx",
])
def test_reader_finds_nothing_on_a_program_without_them(metric):
    """The parent commit records instants only and counts none of it."""
    old = [ev for ev in ROUNDS if ev["ph"] == "i"] + [
        span("close.total", 0, 700)]
    assert read(metric, {"spans": old, "counters": {"txs": 1000}}) is None
    assert read(metric, {"spans": [], "counters": {}}) is None


def test_the_manifest_holds_the_cell():
    m = manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    manifest.validate(m, REPO)
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "quorum4", "close", 1)
    e2e = {x["name"] for x in manifest.metrics_of(m, CELL, "end_to_end")}
    assert e2e >= {"validated_tx_per_s", "setup_s"}
    with open(os.path.join(BENCH, "configs", "quorum4.json")) as fh:
        cfg = json.load(fh)
    entry = next(c for c in m["configs"] if c["name"] == "quorum4")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    # a peer is the measured validator's INI on the plain arms, with
    # doors of its own
    with open(os.path.join(BENCH, "configs", cfg["ini"])) as fh:
        measured = fh.read()
    with open(os.path.join(BENCH, "configs", cfg["peer_ini"])) as fh:
        peer = fh.read()
    # ... and a soft cap of 2,048 a ledger (what every other node cell
    # closes at), which its door paces clients by; the measured
    # validator serves none and keeps standalone-fsync's
    assert nodedrive.plain_reference_ini(measured).replace(
        "[rpc_port]\n0", "[rpc_port]\n{rpc_port}\n\n[websocket_port]\n"
        "{websocket_port}").replace(
        "min_cap=1000000\nmax_cap=1000000",
        "min_cap=1024\nmax_cap=1024") == peer
    # the loop the issue names: 96 unanswered submits, no think time
    files = manifest.cell_files(m, CELL, REPO)
    assert files["traffic"]["connections_per_peer"] == 32
    assert "think_ms" not in files["traffic"]
    assert files["traffic"]["presign_tx_per_s"] == 2000
    assert "clock_speed" not in measured and "[standalone]\n0" in measured
    with open(os.path.join(BENCH, "configs", "standalone-fsync.ini")) as fh:
        alone = fh.read()
    assert measured.startswith(alone.replace("[standalone]\n1",
                                             "[standalone]\n0"))


# --------------------------------------------------------------------------
# the repairs


class TestRepairs:
    def pair(self):
        from test_tcp_overlay import free_ports, wait_until
        from stellard_tpu.overlay.tcp import TcpOverlay

        ports = free_ports(2)
        keys = [KeyPair.from_passphrase(f"q4-pair-{i}") for i in range(2)]
        unl = {k.public for k in keys}
        overlays = [TcpOverlay(
            key=keys[i], unl=unl, quorum=2, port=ports[i],
            peer_addrs=[("127.0.0.1", ports[1 - i])],
            network_time=lambda: 20_000_000, timer_interval=30.0,
            idle_interval=3600) for i in range(2)]
        for ov in overlays:
            ov.start(MASTER.account_id, close_time=20_000_000)
        assert wait_until(lambda: all(ov.peer_count() == 1
                                      for ov in overlays), 15)
        return overlays, wait_until

    def test_a_burst_is_applied_and_counted_and_a_dispute_not_resent(self):
        overlays, wait_until = self.pair()
        a, b = overlays
        try:
            dest = KeyPair.from_passphrase("burst-dest").account_id
            txs = [payment(MASTER, 1 + i, dest, 300 * XRP) for i in range(4)]
            data = b"".join(frame(TxMessage(t.serialize())) for t in txs)
            peer = next(iter(a.peers.values()))
            sent_before = a.traffic.snapshot().get("msgs_out.transaction", 0)
            peer.send(data)  # four frames, one write: one read at b
            want = {t.txid() for t in txs}

            def applied():
                return {t for t, _b, _m in
                        b.node.lm.current_ledger().tx_entries()} == want

            # before the repair the burst was verified and dropped
            assert wait_until(applied, 10)
            stats = b.node.relay_stats.snapshot()
            assert stats["txs_in"] == 4 and stats["duplicates"] == 0
            assert stats["sigs_verified"] == 4
            tj = b.traffic_json()
            assert tj["msgs_in"]["transaction"] == 4
            assert tj["bytes_in"]["transaction"] == len(data)
            assert a.traffic.snapshot()["msgs_out.transaction"] \
                == sent_before + 1  # one send call, counted by its frame
            assert tj["sendq_dropped"] == 0 and tj["sendq_evicted"] == 0
            # b relayed nothing back to the peer it came from, and a
            # dispute over one of them sends nothing again
            assert all(b.node.router.get_flags(t) & SF_RELAYED for t in want)
            out_before = b.traffic.snapshot().get("msgs_out.transaction", 0)
            b.relay_disputed_tx(txs[0].serialize())
            assert b.traffic.snapshot().get("msgs_out.transaction", 0) \
                == out_before
            # one this node never relayed does go out, once
            fresh = payment(MASTER, 9, dest, 300 * XRP)
            b.relay_disputed_tx(fresh.serialize())
            b.relay_disputed_tx(fresh.serialize())
            # (a frame counts when the peer's writer takes it)
            assert wait_until(lambda: b.traffic.snapshot().get(
                "msgs_out.transaction", 0) == out_before + 1, 5)
            time.sleep(0.1)
            assert b.traffic.snapshot()["msgs_out.transaction"] \
                == out_before + 1
        finally:
            for ov in overlays:
                ov.stop()

    def test_a_position_never_exceeds_what_a_peer_takes(self, monkeypatch):
        """Past MAX_TXSET_BLOBS a peer refuses the set as hostile: a
        position is cut to it by rank within each account, and what is
        left out stays for the next round."""
        from stellard_tpu.consensus import consensus as cons

        monkeypatch.setattr(cons, "MAX_TXSET_BLOBS", 5)
        net = SimNet(4, quorum=3)
        net.start()
        v = net.validators[0].node
        senders = [KeyPair.from_passphrase(f"cap-{i}") for i in range(3)]
        fund = [payment(MASTER, 1 + i, k.account_id, 5000 * XRP)
                for i, k in enumerate(senders)]
        for tx in fund:
            net.validators[0].submit_client_tx(tx)
        for _ in range(40):
            net.step()
            if v.lm.validated is not None and all(
                    v.lm.validated.account_root(k.account_id) is not None
                    for k in senders):
                break
        dest = KeyPair.from_passphrase("cap-dest").account_id
        mine = [payment(k, 1 + n, dest, (1 + n) * XRP)
                for k in senders for n in range(3)]  # 9 in the open ledger
        for tx in mine:
            ter, applied = v.submit(tx)
            assert applied, ter
        v.round.close_ledger()
        position = v.round.our_set
        assert len(position) == 5
        # the first of every account, then the second of two of them
        ranks = sorted(tx.sequence for tx in mine
                       if tx.txid() in position)
        assert ranks == [1, 1, 1, 2, 2]
        assert v.round._pre_close_open_ids == {tx.txid() for tx in mine}

    def test_validators_that_disagree_on_the_close_time_close_one_ledger(self):
        """No agreement on the close time is an outcome of a round, not
        a fork: every validator stamps the parent's time plus one."""
        net = SimNet(4, quorum=3)
        net.start()
        closed = []
        for k, v in enumerate(net.validators[:2]):
            rnd = v.node.round
            parent = rnd.prev_ledger
            rnd.close_ledger()
            rnd.accept(parent.close_time + 40 + 30 * k, False)
            closed.append(v.node.lm.closed_ledger())
            assert closed[-1].close_time == parent.close_time + 1
            assert closed[-1].close_flags == 1
        assert closed[0].hash() == closed[1].hash()

    def test_a_relayed_transaction_is_the_frame_a_full_queue_spares(self):
        import socket as socketlib

        from stellard_tpu.overlay.tcp import _Peer

        a, b = socketlib.socketpair()
        try:
            peer = _Peer(a, inbound=False, sendq_depth=2, evict_drops=2)
            peer._writer = object()  # no writer: the queue stays as filled
            peer.send(b"proposal")
            peer.send(b"validation")
            for _ in range(5):
                peer.send(b"relayed-tx", spare=True)
            assert peer.sendq_dropped == 5 and peer.alive
            assert [peer.sendq.get_nowait() for _ in range(2)] == [
                b"proposal", b"validation"]
            # a frame that is not spare still sheds the oldest, and a
            # reader that never reads is still evicted
            peer.send(b"one")
            peer.send(b"two")
            peer.send(b"three")
            assert list(peer.sendq.queue) == [b"two", b"three"]
            peer.send(b"four")
            assert peer.evicted and not peer.alive
        finally:
            b.close()

    def test_a_tls_reader_waits_for_bytes_outside_the_links_lock(self):
        """The writer of a TLS link shares one lock with its reader: a
        reader that held it through every poll starved the writer."""
        import socket as socketlib
        import threading

        from stellard_tpu.overlay.tcp import _Peer

        class FakeTls:
            def __init__(self, sock):
                self.sock = sock

            def pending(self):
                return 0

            def fileno(self):
                return self.sock.fileno()

            def recv(self, n):
                return self.sock.recv(n)

            def getpeername(self):
                return ("127.0.0.1", 1)

        a, b = socketlib.socketpair()
        try:
            peer = _Peer(FakeTls(a), inbound=True)
            peer.is_tls = True
            peer.TLS_POLL_S = 2.0
            got = []
            reader = threading.Thread(
                target=lambda: got.append(peer.recv_locked()))
            reader.start()
            time.sleep(0.2)  # the reader is waiting for bytes now
            assert peer.io_lock.acquire(timeout=0.1)
            peer.io_lock.release()
            b.sendall(b"frame")
            reader.join(timeout=5)
            assert got == [b"frame"]
            peer.TLS_POLL_S = 0.05
            assert peer.recv_locked() is None  # a poll that finds nothing
        finally:
            a.close()
            b.close()

    def test_tx_says_validated_only_of_the_quorums_chain(self):
        """A row left from a ledger this node closed alone and left is
        not validated because the quorum's chain passed its sequence."""
        from stellard_tpu.rpc.handlers import do_tx

        mine, net = chain(False), chain(False)
        a, b = (KeyPair.from_passphrase(f"txv-{i}") for i in range(2))
        alone_tx = payment(MASTER, 1, a.account_id, 500 * XRP)
        net_tx = payment(MASTER, 1, b.account_id, 500 * XRP)
        # this node closed sequence 2 alone, with a transaction the
        # quorum never agreed on ...
        alone, _ = mine.close_with_txset([alone_tx], 2000, 30)
        # ... the quorum closed another 2, and a 3 on top of it
        two, _ = net.close_with_txset([net_tx], 2000, 30)
        three, _ = net.close_with_txset([], 3000, 30)
        assert alone.seq == two.seq == 2 and alone.hash() != two.hash()
        mine.min_validations = 3
        rows = {
            alone_tx.txid(): {"raw": alone_tx.serialize(), "meta": b"",
                              "ledger_seq": 2},
            net_tx.txid(): {"raw": net_tx.serialize(), "meta": b"",
                            "ledger_seq": 2},
        }
        node = types.SimpleNamespace(
            txdb=types.SimpleNamespace(get_transaction=rows.get),
            ledger_master=mine)

        def validated(tx):
            ctx = types.SimpleNamespace(
                node=node, params={"transaction": tx.txid().hex()})
            return do_tx(ctx)["validated"]

        # nothing beyond the first ledger is validated yet
        assert validated(alone_tx) is False and validated(net_tx) is False
        # the quorum's chain reaches this node and passes sequence 2
        mine.switch_lcl(two)
        mine.switch_lcl(three)
        mine.set_validated(three)
        assert mine.validated.seq == 3
        assert validated(net_tx) is True
        assert validated(alone_tx) is False  # its row still says 2
        # at the tip, and ahead of it
        assert mine.validated_ledger_at(3) is three
        assert mine.validated_ledger_at(2).hash() == two.hash()
        assert mine.validated_ledger_at(4) is None
        mine.min_validations = 0  # a standalone node validates its own
        assert validated(alone_tx) is True

    # -- repairs 3 and 4: who waits for room in a peer's queue, what a
    # -- full queue sheds, and what counts as sent

    def queue_of(self, depth: int):
        import socket as socketlib

        from stellard_tpu.overlay.tcp import _Peer

        a, b = socketlib.socketpair()
        peer = _Peer(a, inbound=False, sendq_depth=depth, evict_drops=8)
        peer._writer = object()  # no writer: the queue stays as filled
        return peer, a, b

    def test_the_origin_of_a_transaction_waits_for_room(self):
        import threading

        peer, a, b = self.queue_of(1)
        try:
            peer.send(b"proposal")
            threading.Timer(0.2, peer.sendq.get_nowait).start()
            t = time.monotonic()
            peer.send(b"my-client's-tx", wait_s=5.0)
            waited = time.monotonic() - t
            assert 0.15 <= waited < 2.0
            assert peer.sendq_dropped == 0
            assert list(peer.sendq.queue) == [b"my-client's-tx"]
            # room never comes: the wait ends, and the frame then sheds
            # the oldest like any other
            t = time.monotonic()
            peer.send(b"next", wait_s=0.2)
            assert 0.15 <= time.monotonic() - t < 2.0
            assert peer.sendq_dropped == 1 and peer.alive
            assert list(peer.sendq.queue) == [b"next"]
        finally:
            a.close()
            b.close()

    def test_only_the_door_waits_and_the_persist_worker_never(self):
        """`process_transaction` relays with `wait`; the promotion
        drain of `publish_closed_ledger`, which runs on the persist
        worker, relays without: a full queue cannot stall a flush."""
        from stellard_tpu.node.networkops import NetworkOPs
        from stellard_tpu.overlay.tcp import TcpOverlay, _Peer

        calls = []
        tx = payment(MASTER, 1, b"\x02" * 20, XRP)
        ops = types.SimpleNamespace(
            router=types.SimpleNamespace(
                swap_set=lambda txid, peers, flag: (set(), True)),
            relay_tx=lambda tx, prev, wait: calls.append(wait),
            local_push=None, lm=None)
        NetworkOPs.relay_applied(ops, tx)  # as the drain calls it
        NetworkOPs.relay_applied(ops, tx, wait=True)  # as the door does
        assert calls == [False, True]

        import threading

        sent = []
        fake_peer = types.SimpleNamespace(
            uid=1, send=lambda data, wait_s=0.0: sent.append(wait_s))
        overlay = types.SimpleNamespace(
            _stamp_ctx=lambda msg, txid=None: None,
            _peers_lock=threading.Lock(), peers={1: fake_peer})
        TcpOverlay.broadcast_tx(overlay, tx)
        TcpOverlay.broadcast_tx(overlay, tx, None, True)
        assert sent == [0.0, _Peer.ORIGIN_WAIT_S]

    def test_a_frame_counts_as_sent_when_the_writer_takes_it(self):
        peer, a, b = self.queue_of(2)
        try:
            counted = []
            peer.on_send = counted.append
            peer.send(b"one")
            peer.send(b"two")
            peer.send(b"relayed", spare=True)  # shed: the queue is full
            assert counted == [] and peer.sendq_dropped == 1
            import threading

            writer = threading.Thread(target=peer._write_loop)
            writer.start()
            assert b.recv(64) == b"onetwo"
            peer.sendq.put(None)  # the close sentinel
            writer.join(timeout=5)
            assert counted == [b"one", b"two"]
        finally:
            a.close()
            b.close()


# --------------------------------------------------------------------------
# the door's hold at the soft cap


class TestDoorHold:
    """A networked node's door holds a client's `submit` while the open
    ledger has grown by its soft cap, or faster than evenly over the
    protocol's shortest round, and admits it when there is room
    (rpc/http_server.py `_hold_submit`, `TxQ.open_has_room`)."""

    def txq(self, monkeypatch, cap, fill_s=5.0):
        from stellard_tpu.node import txq as txq_module

        clock = [100.0]
        monkeypatch.setattr(txq_module.time, "monotonic", lambda: clock[0])
        monkeypatch.setattr(txq_module, "OPEN_FILL_S", fill_s)
        q = txq_module.TxQ(metrics=txq_module.FeeMetrics(
            min_cap=cap, max_cap=cap))
        return q, clock

    def pay(self, lm, seq):
        ter, applied = lm.do_transaction(
            payment(MASTER, seq, bytes([seq]) * 20, 500 * XRP),
            TxParams.OPEN_LEDGER)
        assert applied, ter

    def test_the_open_ledger_fills_evenly_over_the_shortest_round(
            self, monkeypatch):
        """A cap of 10 over 5 s: two transactions a second, ten a
        ledger, counted from what the ledger opened with."""
        q, clock = self.txq(monkeypatch, cap=10)
        lm = chain(True)
        self.pay(lm, 1)
        self.pay(lm, 2)  # the leftovers this open ledger was found with
        assert not q.open_has_room(lm)  # first sight: nothing allowed yet
        clock[0] += 0.25
        assert q.open_has_room(lm)      # 0.5 allowed, grown by 0
        self.pay(lm, 3)
        assert not q.open_has_room(lm)  # grown by 1, 0.5 allowed
        clock[0] += 0.5
        assert q.open_has_room(lm)      # 1.5 allowed
        for seq in range(4, 12):
            self.pay(lm, seq)           # the peers' doors let eight in
        clock[0] += 3.0
        assert not q.open_has_room(lm)  # grown by 9, 7.5 allowed
        clock[0] += 1.0
        assert q.open_has_room(lm)      # 9.5 allowed
        self.pay(lm, 12)
        clock[0] += 60.0
        assert not q.open_has_room(lm)  # grown by the cap: the next ledger
        # the round closes: the count starts again from the leftovers
        lm.txq = q
        lm.close_with_txset([], 2000, 30)
        assert TxQ_open_size(lm) == 12 and not q.open_has_room(lm)
        clock[0] += 0.5
        assert q.open_has_room(lm)

    def test_an_adopted_chains_open_ledger_starts_the_count_again(
            self, monkeypatch):
        q, clock = self.txq(monkeypatch, cap=2, fill_s=1.0)
        lm, other = chain(True), chain(True)
        self.pay(lm, 1)
        self.pay(lm, 2)
        q.open_has_room(lm)
        clock[0] += 10.0
        assert q.open_has_room(lm)  # found with two, grown by none
        self.pay(lm, 3)
        self.pay(lm, 4)
        assert not q.open_has_room(lm)
        net, _ = other.close_with_txset([], 2000, 30)
        lm.switch_lcl(net)  # no close of ours: the net moved on
        assert not q.open_has_room(lm)  # first sight of the new one
        clock[0] += 0.6
        assert q.open_has_room(lm)

    # -- the door itself, over HTTP

    def door(self, monkeypatch, overlay=object(), cap=2, fill_s=0.05):
        from stellard_tpu.node import txq as txq_module
        from stellard_tpu.rpc import http_server

        monkeypatch.setattr(txq_module, "OPEN_FILL_S", fill_s)
        lm = chain(True)
        q = txq_module.TxQ(metrics=txq_module.FeeMetrics(
            min_cap=cap, max_cap=cap))
        lm.txq = q
        node = types.SimpleNamespace(
            txq=q, overlay=overlay, ledger_master=lm, tracer=None,
            config=types.SimpleNamespace(admin_ips=("127.0.0.1",)))
        handled = []

        def answer(node, body, role, client_ip="", seen=None):
            handled.append(json.loads(body)["method"])
            return {"result": {"status": "success"}}

        monkeypatch.setattr(http_server, "process_http_request", answer)
        door = http_server.HttpRpcServer(node).start()
        q.open_has_room(lm)  # the open ledger seen, empty
        time.sleep(0.1)      # ... and its shortest round over
        return door, lm, handled

    def post(self, door, method):
        return nodedrive.rpc(door.port, method, {}, timeout=20)

    def test_room_ends_the_hold(self, monkeypatch):
        import threading

        door, lm, handled = self.door(monkeypatch)
        try:
            self.post(door, "submit")
            assert door.submit_holds == 0  # room: not held
            txs = [payment(MASTER, 1 + i, bytes([i + 1]) * 20, 500 * XRP)
                   for i in range(2)]
            for tx in txs:
                lm.do_transaction(tx, TxParams.OPEN_LEDGER)
            assert not door.node.txq.open_has_room(lm)
            t = time.monotonic()
            # the net agrees on both: the next open ledger is empty
            threading.Timer(
                0.3, lambda: lm.close_with_txset(txs, 2000, 30)).start()
            self.post(door, "submit")
            held = time.monotonic() - t
            assert 0.25 <= held < 5.0
            assert door.submit_holds == 1
            assert 0.2 <= door.submit_hold_s < 5.0
            assert door.get_json()["submit_holds"] == 1
            assert handled == ["submit", "submit"]
            # a read is never held, whatever the open ledger holds
            for tx in [payment(MASTER, 3 + i, bytes([i + 7]) * 20, 500 * XRP)
                       for i in range(2)]:
                lm.do_transaction(tx, TxParams.OPEN_LEDGER)
            assert not door.node.txq.open_has_room(lm)
            t = time.monotonic()
            self.post(door, "server_info")
            assert time.monotonic() - t < 0.25 and door.submit_holds == 1
        finally:
            door.stop()

    def test_the_hold_ends_by_itself(self, monkeypatch):
        from stellard_tpu.rpc import http_server

        monkeypatch.setattr(http_server, "SUBMIT_HOLD_S", 0.3)
        door, lm, handled = self.door(monkeypatch)
        try:
            self.pay(lm, 1)
            self.pay(lm, 2)
            t = time.monotonic()
            self.post(door, "submit")  # no close comes: admitted as ever
            assert 0.25 <= time.monotonic() - t < 3.0
            assert door.submit_holds == 1 and len(handled) == 1
        finally:
            door.stop()

    def test_a_standalone_door_never_holds(self, monkeypatch):
        """Nothing closes a standalone node's ledger but its client."""
        door, lm, handled = self.door(monkeypatch, overlay=None)
        try:
            for seq in range(1, 4):
                self.pay(lm, seq)
            assert not door.node.txq.open_has_room(lm)
            t = time.monotonic()
            self.post(door, "submit")
            assert time.monotonic() - t < 0.25 and door.submit_holds == 0
        finally:
            door.stop()


def TxQ_open_size(lm) -> int:
    from stellard_tpu.node.txq import TxQ

    return TxQ.open_size(lm.current_ledger())
