"""The per-layer readers PR 23 added, on synthetic sources with exact
answers, and on what a parent commit hands them (a program without the
spans): there every one returns None and raises nothing. Run by hand:
``python -m pytest benchmarks/tests -q`` (not part of tier-1)."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, REPO)

from yardstick import manifest, progspans, readers  # noqa: E402

NODE_METRICS = (
    "door.stalled_s", "door.gc_full_pause_s", "door.gc_full_collections",
    "flood.gc_full_pause_s", "persist.backpressure_ms_per_close",
    "verify.eligible_sig_share")
SPAN_METRICS = ("catchup.load_share", "catchup.evict_scan_share",
                "catchup.gc_share")


def read(metric, sources):
    return readers.read_metric(manifest.reader_file(BENCH, metric), sources)


def span(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "tid": 1,
            "pid": 1, "cat": "t", "args": dict(args)}


def node_sources():
    return {"counters": {"closes": 4}, "spans": [
        span("rpc.loop_lag", 0, 1_900_000),
        span("rpc.loop_lag", 5_000_000, 100_000),
        span("gc.collect", 0, 1_800_000, generation=2, collected=5),
        span("gc.collect", 9_000_000, 200_000, generation=2, collected=0),
        span("gc.collect", 9_500_000, 15_000, generation=1, collected=9),
        span("persist.backpressure", 1_000_000, 2_500_000, kind="close"),
        span("persist.backpressure", 4_000_000, 1_500_000, kind="close"),
        span("verify.batch", 0, 10, n=30, routed="cpu", why="small"),
        span("verify.batch", 20, 10, n=100, routed="cpu", why="priced"),
        span("verify.batch", 40, 10, n=64, routed="cpu", why="cold"),
        span("verify.batch", 60, 10, n=6, routed="device", why="explore"),
        {"name": "close.tx", "ph": "i", "ts": 1, "args": {}},
    ]}


@pytest.mark.parametrize("metric,want", [
    ("door.stalled_s", 2.0),
    ("door.gc_full_pause_s", 2.0),
    ("flood.gc_full_pause_s", 2.0),
    ("door.gc_full_collections", 2),
    ("persist.backpressure_ms_per_close", 1000.0),
    ("verify.eligible_sig_share", 53.0),
])
def test_node_cell_readers(metric, want):
    assert read(metric, node_sources()) == pytest.approx(want)


def test_a_quiet_window_reads_zero_not_nothing():
    quiet = {"counters": {"closes": 4},
             "spans": [span("close.total", 0, 5)]}
    assert read("door.stalled_s", quiet) == 0.0
    assert read("door.gc_full_collections", quiet) == 0
    assert read("persist.backpressure_ms_per_close", quiet) == 0.0
    # no batch at all is nothing to read
    assert read("verify.eligible_sig_share", quiet) is None


@pytest.mark.parametrize("metric", NODE_METRICS + SPAN_METRICS)
def test_readers_return_nothing_on_a_parent(metric, monkeypatch):
    """A program without PR 23's spans: no `why` on a batch, no probe in
    the tracer module, no spans in catch-up's sources."""
    monkeypatch.setattr(progspans, "program_records", lambda: False)
    parent = {"counters": {"closes": 4}, "capture": types.SimpleNamespace(
        t_start=1.0, t_stop=2.0, spans=[]), "spans": [
        span("verify.batch", 0, 10, n=30, routed="cpu"),
        span("close.total", 0, 5)]}
    assert read(metric, parent) is None
    assert read(metric, {"counters": {}, "spans": []}) is None


def test_catchup_readers_read_the_process_tracer():
    from stellard_tpu.node.tracer import get_tracer

    tr = get_tracer()
    tr.reset()
    e = tr.epoch
    # before the capture: a warm-up span that must not count
    tr.complete("replay.span", "replay", e + 1.0, e + 2.0,
                gc_pause_s=0.9, evict_scan_s=0.9)
    # the window's span: 10 s, 6 s of loads, 1 s collector, 4 s scans
    tr.complete("replay.span", "replay", e + 10.0, e + 20.0,
                gc_pause_s=1.0, evict_scan_s=4.0)
    tr.complete("ledger.load", "state", e + 10.0, e + 14.0)
    tr.complete("ledger.load", "state", e + 15.0, e + 17.0)
    tr.complete("replay.ledger", "replay", e + 14.0, e + 20.0)
    # behind the capture: the correctness check's loads
    tr.complete("ledger.load", "state", e + 31.0, e + 32.0)
    cap = types.SimpleNamespace(t_start=e + 9.5, t_stop=e + 30.0, spans=[])
    try:
        sources = {"counters": {}, "spans": [], "capture": cap}
        assert read("catchup.load_share", sources) == pytest.approx(60.0)
        assert read("catchup.gc_share", sources) == pytest.approx(10.0)
        assert read("catchup.evict_scan_share", sources) \
            == pytest.approx(40.0)
        # an untraced run has no capture interval: nothing to read
        off = types.SimpleNamespace(t_start=None, t_stop=None, spans=[])
        assert read("catchup.load_share",
                    {"capture": off, "counters": {}}) is None
        # a ring that wrapped gives no number from a torn tree
        small = {"counters": {}, "spans": [], "capture": cap}
        for i in range(tr.capacity + 1):
            tr.instant("filler", "t")
        assert read("catchup.load_share", small) is None
    finally:
        tr.reset()


def test_manifest_names_a_reader_for_every_new_metric():
    m = manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    manifest.validate(m, REPO)
    names = {x["name"] for x in m["per_layer"]}
    assert set(NODE_METRICS + SPAN_METRICS) <= names
