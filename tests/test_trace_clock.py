"""One clock for the program's spans and the device trace, and the spans
and counters inside the intervals that were blind (PR 23): catch-up, the
interpreter's collector, the HTTP door, persist back-pressure, the
verify router's evidence, set-up.

Each case stands alone:
- the clock anchor places a synthetic span on a synthetic profiler clock
  exactly, with one anchor and (drift taken out) with two;
- ``tools/traceview.py --xplane``: one timeline with the device's
  programs and not its operations; the device's idle seconds go to the
  innermost span of each host thread, never twice on one thread, and to
  ``span:none`` where no span is open;
- a forced full collection is one generation-2 counter step and one
  ``gc.collect`` span, a young one a counter step alone, and nothing at
  all once the tracer is disabled or the probe removed;
- the door counts requests, busy seconds and errors, samples request
  spans, and a callback that blocks its loop shows as ``rpc.loop_lag``;
- ``replay_range`` yields the ``replay.span`` tree; ``HotNodeCache``
  counts its victim scans exactly; a depth-1 pipeline with a slow drain
  yields one ``persist.backpressure`` span as long as ``backpressure_ms``;
  ``verify.batch`` says why a batch stayed on the host;
- ``get_counts`` keeps its stage blocks' shape with the histograms now
  the tracer's own, and loses them (only them) with ``[trace] enabled=0``.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import traceview  # noqa: E402

from stellard_tpu.crypto.backend import (  # noqa: E402
    BatchVerifier,
    VerifyRequest,
    register_verifier,
)
from stellard_tpu.engine.engine import TxParams  # noqa: E402
from stellard_tpu.node.closepipeline import ClosePipeline  # noqa: E402
from stellard_tpu.node.config import Config  # noqa: E402
from stellard_tpu.node.ledgermaster import LedgerMaster  # noqa: E402
from stellard_tpu.node.ledgertools import replay_ledger, replay_range  # noqa: E402
from stellard_tpu.node.node import Node  # noqa: E402
from stellard_tpu.node.tracer import (  # noqa: E402
    GC_PROBE,
    Tracer,
    parse_anchor,
    place_on_trace_clock,
)
from stellard_tpu.node.verifyplane import VerifyPlane, _LatencyModel  # noqa: E402
from stellard_tpu.nodestore.core import make_database  # noqa: E402
from stellard_tpu.protocol.formats import TxType  # noqa: E402
from stellard_tpu.protocol.keys import KeyPair  # noqa: E402
from stellard_tpu.protocol.sfields import sfAmount, sfDestination  # noqa: E402
from stellard_tpu.protocol.stamount import STAmount  # noqa: E402
from stellard_tpu.protocol.sttx import SerializedTransaction  # noqa: E402
from stellard_tpu.rpc.handlers import Context, dispatch  # noqa: E402
from stellard_tpu.state import hotcache  # noqa: E402
from stellard_tpu.state.shamap import inner_node_cache  # noqa: E402

MASTER = KeyPair.from_passphrase("masterpassphrase")
XRP = 1_000_000


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


# -- a synthetic profiler trace (what ProfileData hands traceview) ----------


class Ev:
    def __init__(self, name, start_ns, duration_ns=0):
        self.name, self.start_ns, self.duration_ns = (
            name, start_ns, duration_ns)


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


def dump_of(events, epoch_ns=0, tag="0000beef"):
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"epoch_ns": epoch_ns, "node_tag": tag}}


def span_ev(name, tid, ts_us, dur_us, span=1, parent=None):
    args = {"span": span}
    if parent is not None:
        args["parent"] = parent
    return {"name": name, "cat": "t", "ph": "X", "ts": ts_us, "dur": dur_us,
            "pid": 1, "tid": tid, "args": args}


class TestClockAnchor:
    def test_anchor_name_round_trips(self):
        assert parse_anchor("stellard.anchor tag=00c0ffee pc_ns=123456789") \
            == ("00c0ffee", 123456789)
        assert parse_anchor("measured.window") is None
        assert parse_anchor("stellard.anchor tag=xyz pc_ns=1") is None

    def test_one_anchor_places_a_span_exactly(self):
        # perf_counter read 7.000 s at the anchor, which the profiler put
        # at 2,000,000 ns of its own clock; the tracer's epoch is 5.000 s
        place = place_on_trace_clock([(7_000_000_000, 2_000_000)],
                                     5_000_000_000)
        # a span stamped 2.5 s after the epoch is 0.5 s after the anchor
        assert place(2_500_000.0) == 2_000_000 + 500_000_000

    def test_two_anchors_take_out_the_drift(self):
        # the profiler's clock runs 100 ppm fast against perf_counter
        a0 = (10_000_000_000, 1_000)
        a1 = (20_000_000_000, 1_000 + 10_001_000_000)
        place = place_on_trace_clock([a1, a0], 0)
        assert place(15_000_000.0) == pytest.approx(1_000 + 5_000_500_000)
        with pytest.raises(ValueError):
            place_on_trace_clock([], 0)

    def test_dump_exports_epoch_and_tag(self):
        tr = Tracer(node_tag=0xBEEF)
        other = tr.chrome_trace()["otherData"]
        assert other["node_tag"] == "0000beef"
        assert other["epoch_ns"] == int(tr.epoch * 1e9)
        t0 = time.perf_counter()
        tr.complete("a", "t", t0, t0 + 0.001)
        ts = spans(tr, "a")[0]["ts"]
        assert ts == int((t0 - tr.epoch) * 1e6)

    def test_place_spans_on_a_synthetic_xplane(self):
        """complete()-style and cross-thread spans are placed like any
        other: only ts, the epoch and the anchor enter."""
        epoch_ns = 40_000_000_000
        prof = Profile([Plane("/host:CPU", [Line("python", [
            Ev("stellard.anchor tag=0000beef pc_ns=41000000000", 9_000),
            Ev("stellard.anchor tag=0000aaaa pc_ns=1", 5),  # another node
        ])])])
        dump = dump_of([span_ev("x", 7, 1_500_000, 250)], epoch_ns)
        placed, info = traceview.place_spans(dump, prof)
        # 1.5 s after the epoch = 0.5 s after the anchor = 9,000 ns + 0.5 s
        assert placed[0]["ts"] * 1000.0 == pytest.approx(500_009_000)
        assert placed[0]["dur"] == 250
        assert info["anchors"] == 1 and info["drift_ns"] == 0

    def test_no_anchor_or_no_epoch_is_an_error(self):
        prof = Profile([Plane("/host:CPU", [Line("python", [])])])
        with pytest.raises(ValueError, match="no clock anchor"):
            traceview.place_spans(dump_of([]), prof)
        with pytest.raises(ValueError, match="epoch_ns"):
            traceview.place_spans({"traceEvents": []}, prof)

    def test_anchor_writes_nothing_when_disabled(self):
        assert Tracer(enabled=False).anchor() is None


class TestTimeline:
    """--xplane: spans and device programs on one timeline; idle seconds
    of the device by the innermost span of each host thread."""

    def profile(self):
        # device: two programs, 1 ms each, at 2 ms and 6 ms; the second
        # is made of 1,000 operations that must not reach the timeline
        ops = [Ev(f"%op.{i} = f32[] add(...)", 6_000_000 + i * 1_000, 1_000)
               for i in range(1_000)]
        ops.insert(0, Ev("%fusion = f32[] fusion(...)", 2_000_000, 1_000_000))
        return Profile([
            Plane("/host:CPU", [Line("python", [
                Ev("stellard.anchor tag=0000beef pc_ns=0", 0)])]),
            Plane("/device:TPU:0", [
                Line("XLA Modules", [
                    Ev("jit_verify_kernel(123)", 2_000_000, 1_000_000),
                    Ev("jit_inner_body(9)", 6_000_000, 1_000_000)]),
                Line("XLA Ops", ops),
                Line("Steps", [Ev("0", 0, 10)]),
            ]),
        ])

    def dump(self):
        # thread 1: outer 0-10 ms, mid 1-9 ms inside it, inner 4-5 ms
        # inside that; thread 2: other 8-12 ms. ts in us, epoch 0.
        return dump_of([
            span_ev("outer", 1, 0, 10_000, span=1),
            span_ev("mid", 1, 1_000, 8_000, span=2, parent=1),
            span_ev("inner", 1, 4_000, 1_000, span=3, parent=2),
            span_ev("other", 2, 8_000, 4_000, span=4),
        ])

    def test_timeline_holds_programs_not_operations(self):
        merged, info = traceview.join_xplane(self.dump(), self.profile())
        assert traceview.validate_chrome_trace(merged) == []
        device = [ev for ev in merged["traceEvents"]
                  if ev.get("cat") == "device"]
        assert sorted(ev["name"] for ev in device) == [
            "jit_inner_body", "jit_verify_kernel"]
        assert len(merged["traceEvents"]) < 20
        kernel = next(ev for ev in device
                      if ev["name"] == "jit_verify_kernel")
        mid = next(ev for ev in merged["traceEvents"]
                   if ev["name"] == "mid")
        # one clock: the program lies inside the span that was open
        assert mid["ts"] <= kernel["ts"]
        assert kernel["ts"] + kernel["dur"] <= mid["ts"] + mid["dur"]
        assert kernel["ts"] - mid["ts"] == pytest.approx(1_000.0)

    def test_idle_goes_to_the_innermost_span_once(self):
        _merged, info = traceview.join_xplane(self.dump(), self.profile())
        idle = info["idle"]
        rows = {k: v / 1e6 for k, v in idle["rows"].items()}  # ms
        # window 0-12 ms, device busy 2-3 and 6-7: idle 10 ms
        assert idle["idle"] / 1e6 == pytest.approx(10.0)
        # thread 1: outer alone 0-1 and 9-10; mid 1-9 less inner 4-5 and
        # less the two busy ms; inner 4-5 whole
        assert rows["span:outer"] == pytest.approx(2.0)
        assert rows["span:mid"] == pytest.approx(5.0)
        assert rows["span:inner"] == pytest.approx(1.0)
        # thread 2 overlaps thread 1 from 8 to 10: rows of different
        # threads may overlap
        assert rows["span:other"] == pytest.approx(4.0)
        # no span anywhere: nothing (thread 2 covers 10-12)
        assert rows["span:none"] == pytest.approx(0.0)
        # one thread's rows never double count: they add up to that
        # thread's covered idle (0-10 less 2 busy)
        assert rows["span:outer"] + rows["span:mid"] + rows["span:inner"] \
            == pytest.approx(8.0)

    def test_span_none_takes_what_no_span_covers(self):
        out = traceview.idle_by_span(
            [("a", 1, 10.0, 20.0), ("b", 1, 12.0, 14.0)],
            [(0.0, 5.0), (13.0, 15.0)], 0.0, 40.0)
        assert out["idle"] == pytest.approx(33.0)
        assert out["rows"]["span:a"] == pytest.approx(7.0)   # 10-12, 15-20
        assert out["rows"]["span:b"] == pytest.approx(1.0)   # 12-13
        assert out["rows"]["span:none"] == pytest.approx(25.0)

    def test_innermost_is_the_span_that_started_last(self):
        # a cross-thread span (ended elsewhere) may overlap without
        # nesting: the later start still wins while both are open
        seg = traceview._innermost_segments(
            [("a", 0.0, 10.0), ("b", 5.0, 15.0)])
        assert seg == [(0.0, 5.0, "a"), (5.0, 15.0, "b")]


class TestCollectorProbe:
    def setup_method(self):
        assert GC_PROBE.installed == 0, "a probe leaked from another test"

    def test_full_collection_is_one_step_and_one_span(self):
        tr = Tracer()
        assert GC_PROBE.install(tr)
        try:
            before = GC_PROBE.get_json()
            gc.collect()
            after = GC_PROBE.get_json()
        finally:
            GC_PROBE.remove(tr)
        assert after["gen2_collections"] - before["gen2_collections"] == 1
        assert after["gen2_pause_s"] > before["gen2_pause_s"]
        got = spans(tr, "gc.collect")
        assert len(got) == 1
        assert got[0]["args"]["generation"] == 2
        assert got[0]["cat"] == "runtime"
        assert got[0]["dur"] >= 0

    def test_a_collection_under_the_tracers_own_lock_cannot_deadlock(self):
        """The hook fires at any allocation, one made while this thread
        holds the tracer's lock included: the span is parked, not
        recorded through the lock, and reaches the ring with the next
        record."""
        tr = Tracer()
        GC_PROBE.install(tr)
        done = threading.Event()

        def collect_under_the_lock():
            with tr._lock:
                gc.collect()
            done.set()

        t = threading.Thread(target=collect_under_the_lock, daemon=True)
        try:
            t.start()
            assert done.wait(timeout=10), "gc hook deadlocked on _lock"
        finally:
            GC_PROBE.remove(tr)
        assert len(spans(tr, "gc.collect")) == 1  # a dump takes it in
        assert tr.get_json()["stages"]["gc.collect"]["count"] == 1

    def test_young_collection_is_a_counter_step_alone(self):
        tr = Tracer()
        GC_PROBE.install(tr)
        try:
            before = GC_PROBE.collections[0]
            gc.collect(0)
            assert GC_PROBE.collections[0] - before == 1
        finally:
            GC_PROBE.remove(tr)
        # far under the 10 ms threshold: no span
        assert spans(tr, "gc.collect") == []

    def test_nothing_once_disabled_or_removed(self):
        off = Tracer(enabled=False)
        assert GC_PROBE.install(off) is False
        assert GC_PROBE.installed == 0
        assert GC_PROBE._on_gc not in gc.callbacks
        tr = Tracer()
        GC_PROBE.install(tr)
        GC_PROBE.install(tr)  # counted: two installations, one hook
        assert gc.callbacks.count(GC_PROBE._on_gc) == 1
        assert GC_PROBE.installed == 2
        gc.collect()  # and one span a collection, not one an installation
        assert len(spans(tr, "gc.collect")) == 1
        tr.reset()
        GC_PROBE.remove(tr)
        assert GC_PROBE._on_gc in gc.callbacks
        GC_PROBE.remove(tr)
        assert GC_PROBE._on_gc not in gc.callbacks
        before = list(GC_PROBE.collections)
        gc.collect()
        assert GC_PROBE.collections == before
        assert spans(tr, "gc.collect") == []

    def test_node_installs_on_setup_and_removes_on_stop(self):
        node = Node(Config()).setup()
        try:
            assert GC_PROBE.installed == 1
            gc.collect()
            assert len(spans(node.tracer, "gc.collect")) >= 1
            counts = dispatch(Context(node, {}), "get_counts")
            assert counts["runtime"]["gc"]["gen2_collections"] >= 1
        finally:
            node.stop()
        assert GC_PROBE.installed == 0
        quiet = Node(Config.from_ini("[trace]\nenabled=0\n")).setup()
        try:
            assert GC_PROBE.installed == 0
        finally:
            quiet.stop()


def _answered(door, n, timeout=5.0):
    """The door counts a request once its response is written, which a
    client can beat by a moment."""
    deadline = time.time() + timeout
    while door.requests < n and time.time() < deadline:
        time.sleep(0.005)
    return door.requests


def _rpc(port, method, params=None):
    body = json.dumps({"method": method, "params": [params or {}]}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.load(resp)["result"]


class TestDoor:
    @pytest.fixture()
    def node(self):
        node = Node(Config(rpc_port=0, trace_sample=1.0)).setup().serve()
        yield node
        node.stop()

    def test_requests_busy_errors_and_request_spans(self, node):
        port = node.http_server.port
        for _ in range(3):
            assert _rpc(port, "ping")["status"] == "success"
        assert _rpc(port, "no_such_method")["status"] == "error"
        assert _answered(node.http_server, 4) == 4
        door = dispatch(Context(node, {}), "get_counts")["rpc_door"]
        assert door["requests"] == 4 and door["errors"] == 1
        # a name from outside the handler table gets no counter of its own
        assert door["by_method"] == {"ping": 3, "?": 1}
        assert door["busy_s"] > 0
        got = spans(node.tracer, "rpc.request")
        assert len(got) == 4  # sample=1.0: every request
        assert [ev["args"]["status"] for ev in got].count("error") == 1
        assert got[0]["args"]["method"] == "ping"
        assert got[0]["args"]["bytes_out"] > 0
        assert sum(ev["dur"] for ev in got) / 1e6 <= door["busy_s"] + 1e-3
        # the door's counters reach /metrics through the `rpc` hook
        flat = node.collector.prometheus_text()
        assert "rpc_requests" in flat and "gc_gen2_collections" in flat

    def test_request_spans_follow_the_sampling_rate(self):
        node = Node(Config(rpc_port=0, trace_sample=0.25)).setup().serve()
        try:
            for _ in range(8):
                _rpc(node.http_server.port, "ping")
            assert _answered(node.http_server, 8) == 8
            assert len(spans(node.tracer, "rpc.request")) == 2
        finally:
            node.stop()

    def test_a_blocked_loop_is_one_loop_lag_span(self, node):
        door = node.http_server
        time.sleep(0.12)  # a few quiet ticks first
        quiet = door.lag_late_ticks
        # hold the door's own loop for 125 ms: the tick due meanwhile
        # runs 75-125 ms late (it was due up to 50 ms into the block)
        door._loop.call_soon_threadsafe(time.sleep, 0.125)
        deadline = time.time() + 5
        while door.lag_late_ticks == quiet and time.time() < deadline:
            time.sleep(0.01)
        time.sleep(0.12)
        lags = spans(node.tracer, "rpc.loop_lag")
        assert lags, "the blocked tick recorded no span"
        longest = max(ev["dur"] for ev in lags) / 1000.0
        # 75-150 ms on a quiet box; a loaded CI host adds scheduling
        # delay on top, never less than the lower bound's slack
        assert 60.0 <= longest <= 400.0
        assert door.lag_s * 1000.0 >= longest - 1.0
        assert door.lag_ticks > door.lag_late_ticks >= 1

    def test_no_lag_probe_with_the_tracer_disabled(self):
        cfg = Config.from_ini("[trace]\nenabled=0\n[rpc_port]\n0\n")
        cfg.rpc_port = 0
        node = Node(cfg).setup().serve()
        try:
            _rpc(node.http_server.port, "ping")
            time.sleep(0.15)
            assert node.http_server.lag_ticks == 0
            assert _answered(node.http_server, 1) == 1  # counters stay
            assert node.tracer.chrome_trace()["traceEvents"] == []
        finally:
            node.stop()


PER_LEDGER = 6


@pytest.fixture()
def chain():
    """A 3-ledger chain of 6 payments each, persisted to a memory
    NodeStore (its genesis too: the first ledger's parent)."""
    lm = LedgerMaster()
    lm.start_new_ledger(MASTER.account_id, close_time=1000)
    db = make_database(type="memory")
    lm.closed_ledger().save(db)
    ledgers = []
    seq = 1
    for i in range(3):
        for k in range(PER_LEDGER):
            dest = KeyPair.from_passphrase(f"tc-{i}-{k}").account_id
            ter, _ = lm.do_transaction(
                payment(MASTER, seq, dest, (1000 + seq) * XRP),
                TxParams.OPEN_LEDGER)
            assert int(ter) == 0
            seq += 1
        closed, _ = lm.close_and_advance(2000 + i * 10, 30)
        closed.save(db)
        ledgers.append(closed)
    return db, ledgers


class TestReplaySpans:
    def test_replay_range_yields_the_span_tree(self, chain):
        db, ledgers = chain
        tr = Tracer(sample=1.0)
        seen = []

        def verify_many(requests):
            seen.append(len(requests))
            t0 = time.perf_counter()
            tr.complete("verify.batch", "verify", t0, time.perf_counter(),
                        n=len(requests))
            return [True] * len(requests)

        hashes = [l.hash() for l in ledgers]
        # once unrecorded: the first call in a process pays its imports
        assert replay_range(db, hashes, tracer=Tracer(enabled=False))["ok"]
        def verify_and_collect(requests):
            gc.collect()  # a full collection inside the span
            return verify_many(requests)

        out = replay_range(db, hashes, verify_many=verify_and_collect,
                           tracer=tr)
        assert out["ok"] and seen == [3 * PER_LEDGER]
        events = spans(tr)
        by_name: dict = {}
        for ev in events:
            by_name.setdefault(ev["name"], []).append(ev)
        n = len(ledgers)
        root, = by_name["replay.span"]
        assert root["args"]["ledgers"] == n
        assert root["args"]["txs"] == n * PER_LEDGER
        assert root["args"]["gc_pause_s"] >= 0
        # a collection is ONE span in the ring
        gcs = by_name.get("gc.collect", [])
        assert len({ev["ts"] for ev in gcs}) == len(gcs)
        assert root["args"]["evict_scan_s"] >= 0
        # every target opened lazily, ONE state loaded eagerly: the
        # first target's parent; the chain carries the rest
        loads = by_name["ledger.load"]
        assert len(loads) == n + 1
        assert [ev["args"]["seq"] for ev in loads if ev["args"]["lazy"]] \
            == [l.seq for l in ledgers]
        eager, = [ev for ev in loads if not ev["args"]["lazy"]]
        assert eager["args"]["seq"] == ledgers[0].seq - 1
        assert "nodes_fetched" in eager["args"]
        assert all("cache_hits" in ev["args"] for ev in loads)
        assert root["args"]["chained"] == out["chained"] == n - 1
        assert root["args"]["state_loads"] == 1
        assert [ev["args"]["parent_from"] for ev in sorted(
            by_name["replay.ledger"], key=lambda ev: ev["ts"])] \
            == ["store"] + ["chain"] * (n - 1)
        # the eager load is the first ledger's own child
        assert eager["args"]["parent"] == min(
            by_name["replay.ledger"], key=lambda ev: ev["ts"])["args"]["span"]
        for name, count in (("replay.parse", 1), ("replay.verify", 1),
                            ("replay.ledger", n), ("replay.apply", n),
                            ("replay.close", n)):
            assert len(by_name[name]) == count, name
        # the plane's batch nests under replay.verify
        assert by_name["verify.batch"][0]["args"]["parent"] \
            == by_name["replay.verify"][0]["args"]["span"]
        # apply and close nest under their ledger, ledgers under the root
        ledger_ids = {ev["args"]["span"] for ev in by_name["replay.ledger"]}
        assert {ev["args"]["parent"] for ev in by_name["replay.apply"]} \
            == ledger_ids
        assert {ev["args"]["parent"] for ev in by_name["replay.ledger"]} \
            == {root["args"]["span"]}
        # the root's own children, and the aging passes between its
        # ledgers (node/heapaging.py: `gc.collect` spans, which have no
        # parent), cover at least 90% of it
        children = [ev for ev in events
                    if ev["args"].get("parent") == root["args"]["span"]]
        end = max(ev["ts"] + ev["dur"] for ev in by_name["replay.verify"])
        covered = sum(ev["dur"] for ev in children) + sum(
            ev["dur"] for ev in gcs
            if end <= ev["ts"] <= root["ts"] + root["dur"])
        assert covered >= 0.9 * root["dur"], (covered, root["dur"])
        assert GC_PROBE.installed == 0  # taken back on the way out

    def test_replay_ledger_alone_and_the_default_tracer(self, chain):
        db, ledgers = chain
        tr = Tracer()
        out = replay_ledger(db, ledgers[1].hash(), tracer=tr)
        assert out["ok"]
        names = [ev["name"] for ev in spans(tr)]
        assert names.count("replay.ledger") == 1
        assert names.count("ledger.load") == 2
        assert names.count("replay.parse") == 1
        assert "replay.span" not in names
        led = spans(tr, "replay.ledger")[0]
        assert led["args"]["seq"] == ledgers[1].seq
        # disabled: the same answer, nothing recorded, nothing hooked
        off = Tracer(enabled=False)
        assert replay_ledger(db, ledgers[1].hash(), tracer=off)["ok"]
        assert off.chrome_trace()["traceEvents"] == []


class TestHotCacheScans:
    def test_scans_are_counted_exactly(self, monkeypatch):
        monkeypatch.setattr(hotcache, "EAGER_ENTRY_CAP", 4)
        cache = hotcache.HotNodeCache("t", limit_bytes=1 << 30)

        class Node_:
            item = None

        for i in range(4):
            cache.put(bytes([i]) * 32, Node_(), eager=True)
        assert (cache.evict_scans, cache.evict_scanned) == (0, 0)
        cache.put(b"\x10" * 32, Node_())  # lazy: no eager pressure
        assert cache.evict_scans == 0
        # the fifth eager entry: its one victim is all eviction examines
        # (the eager index's head), not the 6 entries of the table
        cache.put(b"\x04" * 32, Node_(), eager=True)
        assert (cache.evict_scans, cache.evict_scanned) == (1, 1)
        assert cache.evictions == 1 and b"\x00" * 32 not in cache._data
        # a hit moves an eager entry to the tail of the index too
        assert cache.get(b"\x01" * 32) is not None
        cache.put(b"\x05" * 32, Node_(), eager=True)
        assert (cache.evict_scans, cache.evict_scanned) == (2, 2)
        assert b"\x02" * 32 not in cache._data and b"\x01" * 32 in cache._data
        j = cache.get_json()
        assert j["evict_scans"] == 2 and j["evict_scanned"] == 2
        assert j["evict_scan_s"] >= 0

    def test_old_epoch_pass_counts_its_walk(self):
        cache = hotcache.HotNodeCache("t", limit_bytes=3000)

        class Node_:
            item = None

        cache.put(b"\x01" * 32, Node_())
        cache.put(b"\x02" * 32, Node_())
        assert cache.evict_scans == 0
        cache.advance_epoch(1)
        # 3,600 > 3,000 and two entries are behind the epoch: pass 1
        # examines the head, which frees enough, and stops there
        cache.put(b"\x03" * 32, Node_())
        assert (cache.evict_scans, cache.evict_scanned) == (1, 1)
        assert cache.epoch_first_evictions == 1
        # a cold put lands at the TAIL with an old stamp: with the head
        # restamped by a hit, pass 1 examines all three to reach it
        assert cache.get(b"\x02" * 32) is not None
        cache.put(b"\x04" * 32, Node_(), cold=True)
        assert (cache.evict_scans, cache.evict_scanned) == (2, 4)
        assert list(cache._data) == [b"\x03" * 32, b"\x02" * 32]
        assert cache.epoch_first_evictions == 2
        # nothing behind the epoch: pass 2 pops the head, nothing looks
        cache.put(b"\x05" * 32, Node_())
        assert (cache.evict_scans, cache.evict_scanned) == (2, 4)
        assert (cache.evictions, cache.epoch_first_evictions) == (3, 2)

    def test_replay_range_past_the_eager_cap(self, chain, monkeypatch):
        # the real path: every eager load of a tree with more inner
        # nodes than the cap evicts, and still replays to its hashes.
        # Newest first, so that no ledger's parent is the one re-closed
        # before it and every parent is loaded from the store
        db, ledgers = chain
        ledgers = ledgers[::-1]
        monkeypatch.setattr(hotcache, "EAGER_ENTRY_CAP", 2)
        cache = inner_node_cache()
        cache.clear()
        tr = Tracer(sample=1.0)
        before = (cache.evictions, cache.evict_scans, cache.evict_scanned)
        out = replay_range(db, [l.hash() for l in ledgers], tracer=tr)
        assert out["ok"] and all(
            l["state_hash_ok"] and l["tx_hash_ok"] for l in out["ledgers"])
        evicted, scans, scanned = (
            now - was for now, was in zip(
                (cache.evictions, cache.evict_scans, cache.evict_scanned),
                before))
        assert evicted > 0 and cache._eager_count <= 2
        assert scanned == evicted and scans <= evicted
        root, = spans(tr, "replay.span")
        assert root["args"]["evict_scan_s"] > 0
        assert (root["args"]["chained"], root["args"]["state_loads"]) \
            == (0, len(ledgers))
        loads = spans(tr, "ledger.load")
        assert sum(ev["args"]["evict_scans"] for ev in loads) == scans
        assert sum(ev["args"]["evict_scan_s"] for ev in loads) \
            == pytest.approx(root["args"]["evict_scan_s"], abs=1e-4)


class FakeLedger:
    def __init__(self, seq):
        self.seq = seq

    def hash(self):
        return self.seq.to_bytes(32, "big")


class TestPersistBackpressure:
    def test_slow_drain_yields_one_span_as_long_as_the_counter(self):
        tr = Tracer()
        pipe = ClosePipeline(
            save_stage=lambda led: time.sleep(0.08),
            txdb_stage=lambda led, results: None,
            clf_stage=lambda led: None,
            depth=1, tracer=tr,
        )
        for seq in (1, 2, 3):
            pipe.submit_close(FakeLedger(seq), {})
        assert pipe.flush(timeout=10)
        assert pipe.stop(timeout=10)
        waits = spans(tr, "persist.backpressure")
        # 1 drains at once, 2 queues (after a moment's wait where the
        # worker has not taken 1 yet), 3 waits for 2 to leave the queue:
        # a span for every wait the pipeline counted, as long in all
        assert pipe.backpressure_waits == len(waits) >= 1
        assert sum(ev["dur"] for ev in waits) / 1000.0 == pytest.approx(
            pipe.backpressure_ms, abs=0.01 * len(waits))
        longest = max(waits, key=lambda ev: ev["dur"])
        assert longest["args"]["trace"] == "ledger-3"
        assert longest["args"]["kind"] == "close"
        assert longest["dur"] / 1000.0 >= 40.0
        # the stages are the tracer's own histograms, per pipeline here
        stages = pipe.get_json()["stages"]
        assert set(stages) == {"queue_wait", "nodestore", "txdb", "clf",
                               "total"}
        assert stages["total"]["count"] == 3
        assert "backpressure" not in stages


class SlowDevice(BatchVerifier):
    name = "slow-device"

    def __init__(self, **_):
        pass

    def verify_batch(self, batch):
        time.sleep(0.03)
        return np.ones(len(batch), bool)


register_verifier("slow-device", SlowDevice)


def requests(n):
    k = KeyPair.from_passphrase("tc-verify")
    m = b"\x42" * 32
    s = k.sign(m)
    return [VerifyRequest(k.public, m, s) for _ in range(n)]


class TestRouterEvidence:
    def test_decide_names_each_branch(self):
        m = _LatencyModel(min_device_batch=64)
        assert m.decide(8) == ("cpu", "small", None, None)
        assert m.decide(128)[:2] == ("device", "explore")
        for _ in range(2):
            m.observe_device(128, 50.0)
        assert m.decide(128) == ("cpu", "explore", 50.0, None)
        m.observe_cpu(100, 1.0)
        side, why, dev_ms, cpu_ms = m.decide(128)
        assert (side, why) == ("cpu", "priced")
        assert dev_ms == 50.0 and cpu_ms == pytest.approx(1.28)
        assert m.decide(16384)[:2] == ("device", "priced")
        assert m.route(128) == "cpu" and m.route(16384) == "device"

    def test_verify_batch_says_why_small_and_why_priced(self):
        tr = Tracer()
        plane = VerifyPlane(backend="slow-device", min_device_batch=64,
                            tracer=tr)
        try:
            plane.verify_many(requests(8))          # under the floor
            # the host's price is the one stated here, not what that
            # batch of 8 took on a machine busy with other tests
            plane.model.cpu_persig_ms = None
            plane.model.observe_cpu(1000, 1.0)      # 0.001 ms a signature
            for _ in range(2):                      # the device: 30 ms flat
                plane.model.observe_device(128, 30.0)
            plane.verify_many(requests(128))        # priced out
            j = plane.get_json()
        finally:
            plane.stop()
        small, priced = spans(tr, "verify.batch")
        assert (small["args"]["routed"], small["args"]["why"]) \
            == ("cpu", "small")
        assert "exp_device_ms" not in small["args"]
        assert (priced["args"]["routed"], priced["args"]["why"]) \
            == ("cpu", "priced")
        assert priced["args"]["exp_device_ms"] == 30.0
        assert priced["args"]["exp_cpu_ms"] < 30.0
        assert j["host_small_sigs"] == 8 and j["host_priced_sigs"] == 128
        assert j["host_cold_sigs"] == j["host_wedged_sigs"] == 0
        assert j["device_sigs"] == 0

    def test_device_batches_and_the_other_reasons(self):
        tr = Tracer()
        plane = VerifyPlane(backend="slow-device", min_device_batch=64,
                            tracer=tr)
        try:
            plane.verify_many(requests(64))         # unmeasured arm
            plane._prewarm_pending = True
            plane.verify_many(requests(64))         # prewarm running
        finally:
            plane._prewarm_pending = False
            plane.stop()
        explore, cold = spans(tr, "verify.batch")
        assert (explore["args"]["routed"], explore["args"]["why"]) \
            == ("device", "explore")
        assert (cold["args"]["routed"], cold["args"]["why"]) == ("cpu", "cold")
        host = VerifyPlane(backend="cpu", tracer=tr)
        try:
            host.verify_many(requests(2))
        finally:
            host.stop()
        assert spans(tr, "verify.batch")[-1]["args"]["why"] == "nodevice"

    def test_prewarm_is_a_span_with_its_programs(self):
        from stellard_tpu.utils.xlacache import COMPILES

        tr = Tracer()
        plane = VerifyPlane(backend="slow-device", min_device_batch=64,
                            max_batch=128, tracer=tr)
        try:
            t = plane.start_prewarm()
            # what the compile meter reports while the prewarm runs
            # becomes a child span (here: said by hand)
            for fn in list(COMPILES.observers):
                fn("verify_kernel", True, 0.002)
            t.join(timeout=30)
        finally:
            plane.stop()
        assert COMPILES.observers == []
        warm, = spans(tr, "node.prewarm")
        assert warm["args"]["sizes"] == [64, 128]
        assert warm["dur"] >= 4 * 30_000  # 2 sizes x 2 rounds x 30 ms
        prog, = spans(tr, "prewarm.program")
        assert prog["args"]["parent"] == warm["args"]["span"]
        assert prog["args"]["program"] == "verify_kernel"
        assert prog["args"]["cache_hit"] is True
        assert prog["dur"] == pytest.approx(2_000, abs=2)


def _flood(node, n, per_ledger):
    done = threading.Semaphore(0)
    for i in range(n):
        dest = KeyPair.from_passphrase(f"tc-d{i % 4}").account_id
        node.ops.submit_transaction(
            payment(MASTER, 1 + i, dest), lambda *_a: done.release())
        if (i + 1) % per_ledger == 0:
            for _ in range(per_ledger):
                done.acquire()
            node.ops.accept_ledger()


class TestOneHistogramAnInterval:
    def test_get_counts_stages_keep_their_shape(self):
        node = Node(Config()).setup()
        try:
            _flood(node, 10, per_ledger=5)
            assert node.close_pipeline.flush(timeout=60)
            counts = dispatch(Context(node, {}), "get_counts")
        finally:
            node.stop()
        stages = counts["close_pipeline"]["stages"]
        assert set(stages) == {"queue_wait", "nodestore", "txdb", "clf",
                               "total"}
        for block in stages.values():
            assert block["count"] == 2
            assert {"count", "p50_ms", "p90_ms", "p99_ms"} <= set(block)
        replay = counts["delta_replay"]
        for stage in ("apply", "seal", "total"):
            assert replay[f"{stage}_p50_ms"] >= 0
            assert replay[f"{stage}_p90_ms"] >= replay[f"{stage}_p50_ms"]
        assert {"apply_ms", "seal_ms", "total_ms"} <= set(replay["last_close"])
        # the same numbers as the tracer's stage histograms: one record
        trace = counts["trace"]["stages"]
        assert trace["persist.total"]["count"] == 2
        assert trace["persist.total"]["p50_ms"] == stages["total"]["p50_ms"]
        assert trace["close.total"]["count"] == 2
        # the pipeline and the ledger master keep no histogram of their own
        assert not hasattr(node.close_pipeline, "stage_hist")
        assert not hasattr(node.ledger_master, "close_stage_hist")

    def test_stages_are_absent_with_the_tracer_disabled(self):
        node = Node(Config.from_ini("[trace]\nenabled=0\n")).setup()
        try:
            _flood(node, 5, per_ledger=5)
            assert node.close_pipeline.flush(timeout=60)
            counts = dispatch(Context(node, {}), "get_counts")
        finally:
            node.stop()
        assert counts["close_pipeline"]["persisted"] == 1
        assert counts["close_pipeline"]["stages"] == {}
        assert "apply_p50_ms" not in counts["delta_replay"]
        assert counts["delta_replay"]["last_close"]["total_ms"] > 0
        assert "drain_p50_ms" not in counts["tree"]

    def test_two_pipelines_on_two_tracers_do_not_mix(self):
        pipes = []
        for n in (1, 2):
            tr = Tracer()
            pipe = ClosePipeline(
                save_stage=lambda led: None,
                txdb_stage=lambda led, results: None,
                clf_stage=lambda led: None, tracer=tr)
            for seq in range(n):
                pipe.submit_close(FakeLedger(seq + 1), {})
            assert pipe.flush(timeout=10) and pipe.stop(timeout=10)
            pipes.append(pipe)
        assert [p.get_json()["stages"]["total"]["count"] for p in pipes] \
            == [1, 2]

    def test_node_boot_is_a_span(self):
        node = Node(Config(rpc_port=0)).setup().serve()
        try:
            boot, = spans(node.tracer, "node.boot")
            assert boot["cat"] == "setup"
            assert boot["args"]["start_up"] == "fresh"
            assert boot["dur"] > 0
        finally:
            node.stop()
