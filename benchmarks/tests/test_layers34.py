"""The eleven per-layer readers PR 34 added (who had the interpreter:
``cpu_us`` on spans, CPU seconds by role on ``close.total``), on
synthetic spans with exact answers, on what a parent commit hands them
(spans without ``cpu_us``, ``close.total`` without a cycle: None, and
nothing raised) and on an empty window. Run by hand: ``python -m pytest
benchmarks/tests -q``; ``tests/test_thread_clocks.py`` runs the same
synthetic spans through ``readers.read_metric`` once in tier-1."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, REPO)

from yardstick import hostcpu, manifest, readers  # noqa: E402

NODE_METRICS = (
    "host.cores_busy", "host.intake_cpu_share", "host.drain_cpu_share",
    "host.close_cpu_share", "host.net_cpu_share", "apply.cpu_ms_per_tx",
    "persist.cpu_ms_per_close", "state.fault_cpu_ms_per_close",
    "door.cores_busy", "door.loop_cpu_share")
SPAN_METRIC = "catchup.replay_cpu_share"
ALL = NODE_METRICS + (SPAN_METRIC,)

# exact answers over node_sources() / replay_sources() below
WANT = {
    # cycles 2 and 3 (the first began in the warm-up): 3.0 + 4.5 CPU
    # seconds of the process over 2.0 + 3.0 s of wall
    "host.cores_busy": 1.5,
    "door.cores_busy": 1.5,
    "host.intake_cpu_share": 100 * (0.8 + 1.2) / 5.0,
    "host.drain_cpu_share": 100 * (0.5 + 0.25) / 5.0,
    "host.net_cpu_share": 100 * (0.1 + 0.15) / 5.0,
    "door.loop_cpu_share": 100 * (0.2 + 0.05) / 5.0,
    # cpu_us of close.total 300 + 450 ms over 5 s
    "host.close_cpu_share": 100 * 0.75 / 5.0,
    # open: (400 - 100 of its same-thread fault) + 200 us over 2 sampled
    # transactions; close.apply 90 + 110 ms less nothing over 1,000 tx
    "apply.cpu_ms_per_tx": (0.3 + 0.2) / 2 + 200.0 / 1000,
    "persist.cpu_ms_per_close": (120.0 + 80.0) / 2,
    # 100 + 60 + 40 us of faults over 4 closes
    "state.fault_cpu_ms_per_close": 0.2 / 4,
    "catchup.replay_cpu_share": 100 * (3.5 + 2.5) / (7.0 + 5.0),
}


def read(metric, sources):
    return readers.read_metric(manifest.reader_file(BENCH, metric), sources)


def span(name, ts, dur, tid=1, span_id=None, parent=None, **args):
    if span_id is not None:
        args["span"] = span_id
    if parent is not None:
        args["parent"] = parent
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "tid": tid,
            "pid": 1, "cat": "t", "args": args}


def cycle(ts, cpu_us, cycle_s, process, intake, drain, net, door, sid):
    return span("close.total", ts, 400_000, tid=7, span_id=sid,
                cpu_us=cpu_us, cycle_s=cycle_s, process_cpu_s=process,
                cpu_intake_s=intake, cpu_drain_s=drain, cpu_seal_s=0.01,
                cpu_door_s=door, cpu_fanout_s=0.0, cpu_net_s=net,
                cpu_upkeep_s=0.0, cpu_other_s=0.0, faults=0)


def node_sources(cpu=True):
    """A window of three closes. ``cpu=False``: what a parent commit
    records (no ``cpu_us``, no cycle on ``close.total``)."""
    events = [
        # the warm-up's cycle ends in the window: left out
        cycle(1_000_000, 999_000, 9.0, 9.9, 9.0, 9.0, 9.0, 9.0, 1),
        cycle(3_000_000, 300_000, 2.0, 3.0, 0.8, 0.5, 0.1, 0.2, 2),
        cycle(6_000_000, 450_000, 3.0, 4.5, 1.2, 0.25, 0.15, 0.05, 3),
        span("open.apply", 100, 900, tid=3, span_id=10, trace="aa",
             cpu_us=400),
        # the fault is its child on the same thread: not apply's own
        span("cache.fault", 200, 300, tid=3, span_id=11, parent=10,
             cpu_us=100, bytes=512),
        # a child on ANOTHER thread took nothing from its parent's clock
        span("verify.batch", 300, 100, tid=4, span_id=12, parent=10,
             cpu_us=90, n=1),
        span("open.speculate", 2_000, 500, tid=3, span_id=13, trace="bb",
             cpu_us=200),
        span("close.apply", 1_000_000, 150_000, tid=7, span_id=20,
             cpu_us=90_000),
        span("close.apply", 3_000_000, 150_000, tid=7, span_id=21,
             cpu_us=110_000),
        span("cache.fault", 5_000, 400, tid=5, span_id=30, cpu_us=60),
        span("cache.fault", 6_000, 400, tid=5, span_id=31, cpu_us=40),
        span("persist.total", 1_500_000, 900_000, tid=8, span_id=40,
             cpu_us=120_000),
        span("persist.total", 3_500_000, 700_000, tid=8, span_id=41,
             cpu_us=80_000),
        {"name": "close.tx", "ph": "i", "ts": 1, "args": {}},
    ]
    if not cpu:
        for ev in events:
            ev["args"] = {k: v for k, v in ev["args"].items()
                          if k != "cpu_us" and not k.startswith("cpu_")
                          and k not in ("cycle_s", "process_cpu_s")}
    return {"counters": {"closes": 4, "txs": 1000}, "spans": events}


def replay_sources(cpu=True):
    """Two ``replay.span`` roots in the process tracer, inside a
    capture, as ``catchup.span`` hands them over."""
    from stellard_tpu.node.tracer import SpanToken, get_tracer

    tr = get_tracer()
    tr.reset()
    e = tr.epoch
    extra = ({"cpu_s": 3.5, "process_cpu_s": 4.0},
             {"cpu_s": 2.5, "process_cpu_s": 3.0})
    for k, ((start, stop), more) in enumerate(
            zip(((10, 17), (17, 22)), extra)):
        # as replay_range leaves it: the attribute `cpu_s` is set on the
        # open span (complete() takes the name for a span's own clock)
        token = SpanToken("replay.span", "replay", None, k + 1, None,
                          e + start, 1,
                          {"ledgers": 8, **(more if cpu else {})})
        tr._record_complete(token, e + stop, (stop - start) * 1000.0)
    cap = types.SimpleNamespace(t_start=e + 9.5, t_stop=e + 60.0, spans=[])
    return {"counters": {}, "spans": [], "capture": cap}


def sources_for(metric, cpu=True):
    return replay_sources(cpu) if metric == SPAN_METRIC \
        else node_sources(cpu)


@pytest.fixture(autouse=True)
def clean_ring():
    yield
    from stellard_tpu.node.tracer import get_tracer

    get_tracer().reset()


@pytest.mark.parametrize("metric", ALL)
def test_exact_value(metric):
    assert read(metric, sources_for(metric)) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", ALL)
def test_nothing_on_a_parents_spans(metric):
    assert read(metric, sources_for(metric, cpu=False)) is None


@pytest.mark.parametrize("metric", ALL)
def test_nothing_on_an_empty_window(metric):
    for empty in ({"counters": {"closes": 4, "txs": 1000}, "spans": []},
                  {"counters": {}, "spans": None}, {}):
        assert read(metric, empty) is None


def test_a_window_of_one_close_reads_no_cycle():
    one = node_sources()
    one["spans"] = [ev for ev in one["spans"]
                    if ev["name"] != "close.total"][:] + [
        cycle(1_000_000, 300_000, 2.0, 3.0, 0.8, 0.5, 0.1, 0.2, 2)]
    assert hostcpu.cycles(one) is None
    assert read("host.cores_busy", one) is None


def test_one_span_without_cpu_us_is_nothing_not_less():
    """A stage that ended on another thread carries no ``cpu_us``: the
    sum over the others would read low, so the reader reads nothing;
    and a name of which no span is clocked has nothing to scale."""
    src = node_sources()
    del src["spans"][-2]["args"]["cpu_us"]  # one persist.total
    assert read("persist.cpu_ms_per_close", src) is None
    src = node_sources()
    del src["spans"][3]["args"]["cpu_us"]  # one open.apply
    assert read("apply.cpu_ms_per_tx", src) is None


def test_one_span_in_a_few_is_clocked_and_the_rest_scaled():
    """The program clocks one ``cache.fault`` and one open-ledger span
    in a few: the clocked spans' sum counts for all of the name."""
    src = node_sources()
    src["spans"] += [
        span("cache.fault", 7_000, 400, tid=5, span_id=32),
        span("open.apply", 8_000, 700, tid=3, span_id=14, trace="cc"),
    ]
    # 200 us over 3 clocked faults of 4, over 4 closes
    assert read("state.fault_cpu_ms_per_close", src) \
        == pytest.approx(0.2 * 4 / 3 / 4)
    # open.apply: 300 us of self CPU on 1 clocked of 2; open.speculate
    # 200 us on 1 of 1; three sampled transactions
    assert read("apply.cpu_ms_per_tx", src) \
        == pytest.approx((0.3 * 2 + 0.2) / 3 + 200.0 / 1000)


def test_the_manifest_lists_the_eleven():
    m = manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    manifest.validate(m, REPO)
    by_name = {e["name"]: e for e in m["per_layer"]}
    assert [e["name"] for e in m["per_layer"][-11:]] == [
        "host.cores_busy", "host.intake_cpu_share",
        "host.drain_cpu_share", "host.close_cpu_share",
        "host.net_cpu_share", "apply.cpu_ms_per_tx",
        "persist.cpu_ms_per_close", "state.fault_cpu_ms_per_close",
        "door.cores_busy", "door.loop_cpu_share",
        "catchup.replay_cpu_share"]
    for name in ALL:
        assert by_name[name]["source"] == "program_span"
