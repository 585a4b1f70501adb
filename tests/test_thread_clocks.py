"""Who ran (PR 34): the thread's CPU clock on a span, CPU seconds by
role on every close cycle, and the readers over them.

- a span that sleeps reads little ``cpu_us`` and one that spins reads
  most of its ``dur``; a span ended on another thread carries none;
  ``complete(cpu_s=...)`` is exported and without it the key is absent;
- two threads spinning in Python share one interpreter: their ``cpu_us``
  sum to little more than the longer wall;
- ``THREAD_ROLES``: a thread that has ended is folded into its role and
  is never read again; ``close.total`` of a node's second close carries
  the cycle; ``replay.span`` carries ``cpu_s`` and ``process_cpu_s``;
  ``get_counts.runtime.threads`` and ``/metrics`` ``threads.*``;
- with ``[trace] enabled=0`` no thread clock is read at a close;
- the eleven readers of ``benchmarks/layers/`` over the synthetic spans
  of ``benchmarks/tests/test_layers34.py``, through
  ``readers.read_metric``.
"""

from __future__ import annotations

import importlib.util
import os
import sys
import threading
import time

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(REPO, "tools"))

import traceview  # noqa: E402

from stellard_tpu.engine.engine import TxParams  # noqa: E402
from stellard_tpu.node import tracer as tracer_mod  # noqa: E402
from stellard_tpu.node.config import Config  # noqa: E402
from stellard_tpu.node.ledgermaster import LedgerMaster  # noqa: E402
from stellard_tpu.node.ledgertools import replay_range  # noqa: E402
from stellard_tpu.node.node import Node  # noqa: E402
from stellard_tpu.node.tracer import ROLES, THREAD_ROLES, Tracer  # noqa: E402
from stellard_tpu.nodestore.core import make_database  # noqa: E402
from stellard_tpu.protocol.formats import TxType  # noqa: E402
from stellard_tpu.protocol.keys import KeyPair  # noqa: E402
from stellard_tpu.protocol.sfields import sfAmount, sfDestination  # noqa: E402
from stellard_tpu.protocol.stamount import STAmount  # noqa: E402
from stellard_tpu.protocol.sttx import SerializedTransaction  # noqa: E402
from stellard_tpu.rpc.handlers import Context, dispatch  # noqa: E402

MASTER = KeyPair.from_passphrase("masterpassphrase")
XRP = 1_000_000

# the step of this host's thread CPU clock: nanoseconds on Linux, 10 ms
# on a kernel that accounts CPU time by timer tick (there one span reads
# 0 or a whole tick, and the cases that hold ONE span to its wall are
# not this host's to run)


def thread_clock_tick_us() -> int:
    """The smallest of a few changes of ``time.thread_time()`` seen
    while spinning (at most 0.1 s)."""
    steps = []
    give_up = time.perf_counter() + 0.1
    last = time.thread_time()
    while len(steps) < 3 and time.perf_counter() < give_up:
        now = time.thread_time()
        if now != last:
            steps.append(now - last)
            last = now
    return max(1, round(min(steps) * 1e6)) if steps else 100_000


TICK_US = thread_clock_tick_us()
SLACK_US = max(1_000, TICK_US)
fine_clock = pytest.mark.skipif(
    TICK_US >= 1_000, reason=f"thread CPU clock ticks {TICK_US} us here")


def payment(key, seq, dest, drops=250 * XRP):
    tx = SerializedTransaction.build(
        TxType.ttPAYMENT, key.account_id, seq, 10,
        {sfAmount: STAmount.from_drops(drops), sfDestination: dest},
    )
    tx.sign(key)
    return tx


def spans(tracer, name=None):
    return [ev for ev in tracer.chrome_trace()["traceEvents"]
            if ev["ph"] == "X" and (name is None or ev["name"] == name)]


def spin(seconds: float) -> None:
    """Burn the calling thread's CPU, in Python, for `seconds` of it."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        sum(range(200))


# -- the thread's CPU clock on a span ---------------------------------------


class TestSpanCpu:
    @fine_clock
    def test_a_sleeping_span_ran_little(self):
        tr = Tracer()
        with tr.span("nap", "test"):
            time.sleep(0.060)
        (ev,) = spans(tr, "nap")
        assert ev["dur"] >= 60_000
        assert ev["args"]["cpu_us"] < 10_000

    @fine_clock
    def test_a_spinning_span_ran_most_of_its_wall(self):
        tr = Tracer()
        # a loaded host can take the core away for half of one try
        for _ in range(5):
            with tr.span("spin", "test"):
                spin(0.050)
        got = spans(tr, "spin")
        for ev in got:
            assert ev["args"]["cpu_us"] >= 50_000 * 0.98
            assert ev["args"]["cpu_us"] <= ev["dur"] + 1_000
        assert any(ev["args"]["cpu_us"] >= ev["dur"] / 2 for ev in got)

    def test_a_span_ended_on_another_thread_carries_none(self):
        tr = Tracer()
        token = tr.begin("handed.over", "test")
        t = threading.Thread(target=tr.end, args=(token,))
        t.start()
        t.join()
        same = tr.begin("kept", "test")
        tr.end(same)
        (ev,) = spans(tr, "handed.over")
        assert "cpu_us" not in ev["args"]
        assert "cpu_us" in spans(tr, "kept")[0]["args"]

    def test_one_span_in_a_few_is_clocked_and_its_children_with_it(self):
        """The clock is a system call: of a transaction's spans that a
        thread opens outside any other, the first of a name and then
        every ``CPU_EVERY``-th read it, and what is opened beneath a
        span follows that span; a ledger's span always reads it."""
        tr = Tracer(sample=1.0)
        for k in range(2 * tr.CPU_EVERY):
            txid = bytes([k]) * 32
            with tr.span("close.sketch", "test", seq=k):
                pass
            with tr.span("outer", "test", txid=txid, k=k):
                with tr.span("inner", "test", k=k):
                    c0 = tr.thread_cpu(one_in_few="leaf")
                    t0 = time.perf_counter()
                    tr.complete("leaf", "test", t0, t0, k=k,
                                cpu_s=tr.cpu_since(c0))
            # a second name in turn does not take the first one's slots
            with tr.span("other", "test", txid=txid, k=k):
                pass
        assert all("cpu_us" in ev["args"] for ev in spans(tr, "close.sketch"))
        want = {0, tr.CPU_EVERY}
        for name in ("outer", "inner", "leaf", "other"):
            got = {ev["args"]["k"] for ev in spans(tr, name)
                   if "cpu_us" in ev["args"]}
            assert got == want, name
        # a stage's caller is always handed the clock
        assert tr.thread_cpu() is not None

    def test_complete_exports_what_its_caller_clocked(self):
        tr = Tracer()
        t0 = time.perf_counter()
        tr.complete("clocked", "test", t0, t0 + 0.5, cpu_s=0.125, rows=3)
        tr.complete("unclocked", "test", t0, t0 + 0.5, rows=3)
        (ev,) = spans(tr, "clocked")
        assert ev["args"]["cpu_us"] == 125_000 and ev["args"]["rows"] == 3
        (ev,) = spans(tr, "unclocked")
        assert "cpu_us" not in ev["args"] and ev["args"]["rows"] == 3
        # the stage histograms stay wall milliseconds, one an interval
        assert tr.stage_hist["clocked"].count == 1

    def test_an_instant_and_a_parked_span_carry_none(self):
        tr = Tracer()
        tr.instant("mark", "test")
        t0 = time.perf_counter()
        tr.complete_unlocked("gc.collect", "runtime", t0, t0 + 0.01)
        events = tr.chrome_trace()["traceEvents"]
        assert len(events) == 2
        assert all("cpu_us" not in ev["args"] for ev in events)

    def test_a_disabled_tracer_reads_no_clock(self, monkeypatch):
        tr = Tracer(enabled=False)
        reads = []
        monkeypatch.setattr(
            tracer_mod.time, "thread_time",
            lambda: reads.append(1) or 0.0)
        assert tr.thread_cpu() is None
        with tr.span("off", "test"):
            pass
        assert tr.begin("off", "test") is None
        assert reads == []

    @fine_clock
    def test_two_spinning_threads_share_one_interpreter(self):
        """Two threads spinning in Python under the interpreter's one
        lock: their walls overlap, their CPU cannot."""
        tr = Tracer()
        go = threading.Barrier(2)

        def work(name):
            go.wait()
            with tr.span(name, "test"):
                spin(0.15)

        threads = [threading.Thread(target=work, args=(n,))
                   for n in ("left", "right")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        both = spans(tr, "left") + spans(tr, "right")
        assert len(both) == 2
        cpu = sum(ev["args"]["cpu_us"] for ev in both)
        wall = max(ev["dur"] for ev in both)
        assert cpu >= 2 * 150_000 * 0.98
        assert cpu <= 1.25 * wall


# -- CPU seconds by role -----------------------------------------------------


class TestThreadRoles:
    def test_an_ended_thread_is_folded_and_never_read_again(
            self, monkeypatch):
        before = THREAD_ROLES.cpu_s()["upkeep"]
        inside = {}

        def body():
            spin(0.03)
            inside.update(THREAD_ROLES.get_json()["upkeep"])

        t = threading.Thread(target=THREAD_ROLES.wrap("upkeep", body))
        t.start()
        t.join()
        assert inside["threads"] >= 1
        # behind its join: no entry, so no clock of a dead thread
        assert THREAD_ROLES.role_of(t.ident) is None
        reads = []
        real = time.clock_gettime
        monkeypatch.setattr(
            tracer_mod.time, "clock_gettime",
            lambda clk: reads.append(clk) or real(clk))
        after = THREAD_ROLES.cpu_s()["upkeep"]
        assert after - before >= 0.03 * 0.98
        live = {w[1] for w in THREAD_ROLES._live.values()}
        assert set(reads) <= live
        assert THREAD_ROLES.get_json()["upkeep"]["cpu_s"] >= 0.0

    def test_a_thread_that_ended_without_leaving_is_folded_not_read(
            self, monkeypatch):
        """A pool's worker enters through the pool's ``initializer`` and
        never leaves: once it has ended, a snapshot keeps its last
        reading in its role and does not touch its clock."""
        done = threading.Event()

        def body():
            THREAD_ROLES.enter("fanout")
            spin(0.03)
            done.set()
            time.sleep(0.05)  # alive, and idle, for one snapshot

        t = threading.Thread(target=body)
        t.start()
        done.wait()
        seen = THREAD_ROLES.cpu_s()["fanout"]
        t.join()
        assert THREAD_ROLES.role_of(t.ident) == "fanout"
        reads = []
        real = time.clock_gettime
        monkeypatch.setattr(
            tracer_mod.time, "clock_gettime",
            lambda clk: reads.append(clk) or real(clk))
        dead_clock = THREAD_ROLES._live[t.ident][1]
        after = THREAD_ROLES.get_json()["fanout"]
        assert dead_clock not in reads
        assert THREAD_ROLES.role_of(t.ident) is None
        assert after["cpu_s"] >= seen - 1e-6 and seen >= 0.03 * 0.98

    def test_a_helpers_seconds_are_credited_to_its_callers_role(self):
        got = {}

        def body():
            before = THREAD_ROLES.cpu_s()["seal"]
            THREAD_ROLES.credit(0.25)
            THREAD_ROLES.credit(None)
            got["d"] = THREAD_ROLES.cpu_s()["seal"] - before

        t = threading.Thread(target=THREAD_ROLES.wrap("seal", body))
        t.start()
        t.join()
        assert 0.25 <= got["d"] < 0.26
        before = THREAD_ROLES.cpu_s()
        THREAD_ROLES.credit(0.25)  # this thread is in no role
        assert THREAD_ROLES.cpu_s()["seal"] == before["seal"]

    def test_an_unknown_role_and_a_stranger_leaving(self):
        with pytest.raises(ValueError):
            THREAD_ROLES.enter("nobody")
        THREAD_ROLES.leave()  # never entered: nothing to fold
        assert THREAD_ROLES.role_of(threading.get_ident()) is None

    def test_a_live_thread_is_read_without_its_help(self):
        stop = threading.Event()
        ready = threading.Event()

        def body():
            ready.set()
            while not stop.is_set():
                sum(range(500))

        t = threading.Thread(target=THREAD_ROLES.wrap("fanout", body))
        t.start()
        try:
            ready.wait()
            a = THREAD_ROLES.cpu_s()["fanout"]
            time.sleep(0.05)
            b = THREAD_ROLES.cpu_s()["fanout"]
            assert b > a
            assert THREAD_ROLES.get_json()["fanout"]["threads"] >= 1
        finally:
            stop.set()
            t.join()


@pytest.fixture()
def node():
    node = Node(Config(rpc_port=0, trace_sample=1.0)).setup().serve()
    yield node
    node.stop()


def flood(node, n, start_seq=1):
    for k in range(n):
        dest = KeyPair.from_passphrase(f"tclk-{start_seq + k}").account_id
        ter, _ = node.ledger_master.do_transaction(
            payment(MASTER, start_seq + k, dest, 1000 * XRP),
            TxParams.OPEN_LEDGER)
        assert int(ter) == 0
    return start_seq + n


class TestCloseCycle:
    def test_the_second_close_carries_its_cycle(self, node):
        seq = flood(node, 4)
        node.ops.accept_ledger()
        seq = flood(node, 4, seq)
        node.ops.accept_ledger()
        node.close_pipeline.flush(timeout=30)
        first, second = spans(node.tracer, "close.total")[:2]
        # the first traced close only sets the marks
        assert "cycle_s" not in first["args"] and "cpu_us" in first["args"]
        args = second["args"]
        keys = ["cycle_s", "process_cpu_s"] + [f"cpu_{r}_s" for r in ROLES]
        assert all(args[k] >= 0.0 for k in keys), args
        # a difference of differences, not clamped: a fault would show
        assert args["cpu_other_s"] >= -1e-3
        assert args["cycle_s"] > 0 and args["process_cpu_s"] > 0
        # this test's thread closed, and is in no role
        assert "closer" not in args
        roles = sum(args[f"cpu_{r}_s"] for r in ROLES)
        assert roles <= args["process_cpu_s"] + 1e-5
        assert roles + args["cpu_us"] / 1e6 <= args["process_cpu_s"] + 1e-3
        # the cache's differences still ride the span
        assert {"faults", "fault_s", "evictions"} <= set(args)

    def test_every_same_thread_span_ran_no_more_than_it_took(self, node):
        seq = flood(node, 12)
        node.ops.accept_ledger()
        flood(node, 12, seq)
        node.ops.accept_ledger()
        node.close_pipeline.flush(timeout=30)
        events = spans(node.tracer)
        clocked = [ev for ev in events if "cpu_us" in ev["args"]]
        names = {ev["name"] for ev in clocked}
        assert {"close.apply", "close.seal", "close.total",
                "persist.nodestore", "persist.txdb", "persist.clf",
                "persist.total", "subs.publish", "paths.index.advance",
                "open.apply"} <= names
        for ev in clocked:
            assert ev["args"]["cpu_us"] <= ev["dur"] + SLACK_US, ev
        dump = node.tracer.chrome_trace()
        # the dump's own bound on the clock's step: its smallest
        # non-zero cpu_us, taken without spinning
        assert dump["otherData"]["cpu_tick_us"] == min(
            ev["args"]["cpu_us"] for ev in clocked if ev["args"]["cpu_us"])
        assert traceview.cpu_slack_us(dump) >= 1_000
        assert Tracer().chrome_trace()["otherData"]["cpu_tick_us"] is None
        assert traceview.cpu_overruns(events, SLACK_US) == []

    def test_get_counts_and_metrics_list_the_roles(self, node):
        flood(node, 2)
        node.ops.accept_ledger()
        node.close_pipeline.flush(timeout=30)
        threads = dispatch(Context(node, {}), "get_counts")[
            "runtime"]["threads"]
        assert set(threads) == set(ROLES) | {"process_cpu_s"}
        assert threads["process_cpu_s"] > 0
        for role in ("intake", "drain", "door", "upkeep"):
            assert threads[role]["threads"] >= 1, role
            assert threads[role]["cpu_s"] >= 0.0
        text = node.collector.prometheus_text()
        assert "threads_drain_cpu_s" in text.replace(".", "_")
        flat = THREAD_ROLES.flat_json()
        assert "drain_cpu_s" in flat and "process_cpu_s" in flat

    def test_a_close_on_a_thread_in_a_role_names_it(self, node):
        flood(node, 2)
        node.ops.accept_ledger()
        seen = {}

        def close_as_net():
            flood(node, 2, 3)
            node.ops.accept_ledger()
            seen.update(spans(node.tracer, "close.total")[-1]["args"])

        t = threading.Thread(target=THREAD_ROLES.wrap("net", close_as_net))
        t.start()
        t.join()
        assert seen["closer"] == "net"
        # the close is a part of its role's seconds
        assert seen["cpu_net_s"] >= seen["cpu_us"] / 1e6 - 1e-3
        assert seen["cpu_other_s"] >= -1e-3


def test_no_thread_clock_at_a_close_with_the_tracer_disabled(monkeypatch):
    quiet = Node(Config.from_ini("[trace]\nenabled=0\n")).setup()
    try:
        flood(quiet, 3)
        quiet.ops.accept_ledger()
        reads = []
        monkeypatch.setattr(tracer_mod.time, "thread_time",
                            lambda: reads.append("thread") or 0.0)
        monkeypatch.setattr(tracer_mod.time, "clock_gettime",
                            lambda clk: reads.append("clock") or 0.0)
        monkeypatch.setattr(tracer_mod.time, "process_time",
                            lambda: reads.append("process") or 0.0)
        flood(quiet, 3, 4)
        quiet.ops.accept_ledger()
        quiet.close_pipeline.flush(timeout=30)
        assert reads == []
        assert quiet.ledger_master._cycle_marks is None
        assert spans(quiet.tracer) == []
    finally:
        monkeypatch.undo()
        quiet.stop()


def test_replay_span_carries_the_threads_and_the_process_clock():
    lm = LedgerMaster()
    lm.start_new_ledger(MASTER.account_id, close_time=1000)
    db = make_database(type="memory")
    lm.closed_ledger().save(db)
    ledgers = []
    seq = 1
    for i in range(2):
        for k in range(4):
            dest = KeyPair.from_passphrase(f"tclk-r-{i}-{k}").account_id
            ter, _ = lm.do_transaction(
                payment(MASTER, seq, dest, (1000 + seq) * XRP),
                TxParams.OPEN_LEDGER)
            assert int(ter) == 0
            seq += 1
        closed, _ = lm.close_and_advance(2000 + i * 10, 30)
        closed.save(db)
        ledgers.append(closed)
    tr = Tracer(sample=1.0)
    out = replay_range(db, [led.hash() for led in ledgers], tracer=tr)
    assert out["ledger_count"] == 2
    (root,) = spans(tr, "replay.span")
    args = root["args"]
    assert 0 < args["cpu_s"] <= root["dur"] / 1e6 + 1e-3
    assert args["process_cpu_s"] >= args["cpu_s"] - 1e-3
    # the span's own clock agrees with the attribute
    assert abs(args["cpu_us"] / 1e6 - args["cpu_s"]) < 0.005
    for name in ("replay.parse", "replay.ledger", "replay.apply",
                 "replay.close", "ledger.load"):
        assert all("cpu_us" in ev["args"] for ev in spans(tr, name)), name


# -- the eleven readers, through the harness's own read_metric ---------------


def _layers34():
    spec = importlib.util.spec_from_file_location(
        "test_layers34", os.path.join(BENCH, "tests", "test_layers34.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


LAYERS34 = _layers34()


@pytest.mark.parametrize("metric", LAYERS34.ALL)
def test_reader_on_synthetic_spans(metric):
    try:
        got = LAYERS34.read(metric, LAYERS34.sources_for(metric))
        assert got == pytest.approx(LAYERS34.WANT[metric])
        assert LAYERS34.read(
            metric, LAYERS34.sources_for(metric, cpu=False)) is None
        assert LAYERS34.read(metric, {"counters": {}, "spans": []}) is None
    finally:
        tracer_mod.get_tracer().reset()


def test_readers_on_the_programs_own_spans(node):
    """A small node's window through the node cells' readers: every one
    finds something to read, and CPU stays within wall (every span
    clocked: three clocked transactions of 24 are an estimate, and an
    estimate may pass the wall it is compared with)."""
    node.tracer.CPU_EVERY = 1
    seq = 1
    for _ in range(4):
        seq = flood(node, 6, seq)
        node.ops.accept_ledger()
    node.close_pipeline.flush(timeout=30)
    sources = {"counters": {"closes": 4, "txs": 24},
               "spans": node.tracer.chrome_trace()["traceEvents"]}
    read = LAYERS34.read
    assert 0 < read("host.cores_busy", sources) < 13
    for metric in ("host.intake_cpu_share", "host.drain_cpu_share",
                   "host.close_cpu_share", "host.net_cpu_share",
                   "door.loop_cpu_share"):
        assert read(metric, sources) >= 0.0, metric
    assert read("host.close_cpu_share", sources) > 0.0
    assert 0 < read("apply.cpu_ms_per_tx", sources) \
        <= read("apply.ms_per_tx", sources) + 0.05
    assert 0 < read("persist.cpu_ms_per_close", sources) \
        <= read("persist.ms_per_close", sources) + 1.0


def test_traceview_tables_and_a_coarse_clocks_slack(capsys):
    """By span: count, wall, CPU, share not run; by role over the dump's
    cycles; an overrun is judged by the dump's own tick."""
    events = LAYERS34.node_sources()["spans"]
    rows = traceview.cpu_by_span(events)
    assert rows["persist.total"] == {
        "count": 2, "wall_ms": 1600.0, "clocked": 2, "cpu_ms": 200.0,
        "waited_share": pytest.approx(87.5)}
    by_role = traceview.cpu_by_role(events)
    assert by_role["cycles"] == 3
    assert by_role["roles"]["drain"] == pytest.approx(9.75)
    assert by_role["close_cpu_s"] == pytest.approx(1.749)
    assert by_role["closers"] == {}
    # a close that ran on a thread of a role is that role's already
    on_net = [dict(ev, args=dict(ev["args"], closer="net"))
              if ev["name"] == "close.total" else ev for ev in events]
    by_role = traceview.cpu_by_role(on_net)
    assert by_role["close_cpu_s"] == 0.0
    assert by_role["closers"] == {"net": pytest.approx(1.749)}
    replay = [LAYERS34.span("replay.span", 0, 7_000_000, cpu_us=3_500_000,
                            cpu_s=3.5, process_cpu_s=4.0)]
    assert traceview.cpu_of_replay(replay) == {
        "spans": 1, "wall_s": 7.0, "cpu_s": 3.5, "process_cpu_s": 4.0}
    traceview.print_cpu_tables(on_net + replay)
    out = capsys.readouterr().out
    assert "persist.total" in out and "cores busy" in out
    assert "of net: 1.749 CPU s are closes" in out
    assert "replay spans: 1" in out and "process 4.000 CPU s" in out
    over = [dict(events[3], dur=100)]  # open.apply: cpu_us 400 in 100 us
    over[0]["args"] = dict(over[0]["args"], cpu_us=9_999)
    assert traceview.cpu_overruns(over) != []
    coarse = {"otherData": {"cpu_tick_us": 10_000}}
    assert traceview.cpu_overruns(
        over, traceview.cpu_slack_us(coarse)) == []
    assert traceview.cpu_slack_us({}) == traceview.CPU_SLACK_US
