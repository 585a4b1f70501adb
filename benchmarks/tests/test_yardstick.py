"""Tests of the benchmark's own arithmetic, run by hand and in the CPU
rehearsal: ``python -m pytest benchmarks/tests -q``. They are not part of
the repository's tier-1 suite (that runs ``tests/`` only)."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from yardstick import capture, manifest, peaks, readers, stats, xtrace  # noqa: E402


# -- percentile and rate arithmetic ---------------------------------------


@pytest.mark.parametrize("values,q,want", [
    ([5.0], 99, 5.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([1, 2, 3, 4], 0, 1.0),
    ([1, 2, 3, 4], 100, 4.0),
    ([4, 1, 3, 2], 25, 1.75),
    (list(range(1, 101)), 99, 99.01),
    (list(range(1, 101)), 95, 95.05),
])
def test_percentile_is_numpys_linear_rule(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_edges():
    assert stats.percentile([], 50) is None
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_rate_share_spread():
    assert stats.rate(100, 4.0) == 25.0
    assert stats.rate(100, 0.0) is None
    assert stats.share_pct(1, 4) == 25.0
    assert stats.share_pct(1, 0) is None
    # quartiles of 1..5 are 2 and 4, the median 3
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx(2 / 3)
    assert stats.samples_beyond(1000, 99) == pytest.approx(10.0)


def test_sliced_percentiles_take_whole_slices_only():
    # three whole 2 s slices in 7 s: 0-2 quiet, 2-4 a stall, 4-6 quiet;
    # the row at 6.5 s is behind the last whole slice
    rows = [(0.1, 1.0), (1.9, 3.0), (2.0, 900.0), (3.5, 1000.0),
            (4.0, 2.0), (5.99, 4.0), (6.5, 7000.0)]
    got = stats.sliced_percentiles(rows, 2.0, 7.0, 50)
    assert got == [2.0, 950.0, 3.0]
    assert stats.median(got) == 3.0  # one stall does not move the median
    # an empty slice is left out, rows before the window belong to none
    assert stats.sliced_percentiles([(-0.5, 9.0), (2.5, 5.0)], 2.0, 4.0,
                                    99) == [5.0]
    assert stats.sliced_percentiles(rows, 2.0, 1.0, 99) == []
    # 51 s in 2 s intervals: 25 whole slices
    assert len(stats.sliced_percentiles(
        [(k * 0.5, 1.0) for k in range(102)], 2.0, 51.0, 99)) == 25


def test_union_seconds_merges_overlaps():
    assert stats.union_seconds([(0, 3), (2, 4), (6, 8), (7, 7.5)]) == 6.0
    assert stats.union_seconds([]) == 0.0
    assert stats.union_seconds([(5, 5), (9, 8)]) == 0.0


# -- the trace reduction ---------------------------------------------------


def _synthetic(window=None):
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "synthetic.xspace.txt")) as fh:
        profile = ProfileData.from_text_proto(fh.read())
    return xtrace.reduce_profile(
        profile, {"submit", "accept_ledger"}, chips=1, window=window)


@pytest.fixture(scope="module")
def synthetic():
    return _synthetic()


def test_the_window_annotation_cuts_what_the_metrics_read():
    # device ops [1,4) [3,5) [7,9) [21,25) ms; the window is [2,8) ms:
    # busy inside it [2,5) + [7,8) = 4 ms of 6; of the programs only
    # inner_body starts inside it ([7,8); tree_leaf_body starts at 8)
    cut = _synthetic(capture.WINDOW)
    assert cut["window_marked"]
    assert cut["window_s"] == pytest.approx(0.006)
    assert cut["window_busy_s"] == pytest.approx(0.004)
    assert cut["programs"] == {"inner_body": [pytest.approx(0.001), 1]}
    # the capture's own numbers stay whole
    assert cut["busy_s"] == pytest.approx(0.010)
    assert cut["span_s"] == pytest.approx(0.030)
    whole = _synthetic()
    assert not whole["window_marked"]
    assert whole["window_s"] == pytest.approx(whole["span_s"])
    assert whole["window_busy_s"] == pytest.approx(whole["busy_s"])
    assert readers.GENERIC["trace_idle_share"](
        {"trace": cut}, {}) == pytest.approx(100.0 * (1 - 4 / 6))


def test_a_device_plane_without_operations_is_idle_not_missing():
    from jax.profiler import ProfileData

    quiet = ProfileData.from_text_proto(
        'planes { id: 1 name: "/device:TPU:0" } '
        'planes { id: 2 name: "/host:CPU" lines { id: 1 name: "t" '
        'timestamp_ns: 0 events { metadata_id: 1 offset_ps: 0 '
        'duration_ps: 5000000000 } } event_metadata { key: 1 value { id: 1 '
        'name: "measured.window" } } }')
    out = xtrace.reduce_profile(quiet, set(), window=capture.WINDOW)
    assert out["busy_s"] == 0.0 and out["window_busy_s"] == 0.0
    assert out["window_s"] == pytest.approx(0.005)
    assert readers.GENERIC["trace_idle_share"]({"trace": out}, {}) == 100.0


def test_busy_is_the_union_of_device_ops(synthetic):
    # ops: [1.000,1.003) [1.002,1.004) [1.006,1.008) [1.020,1.024) ms
    # -> union 4 + 2 + 4 = 10 ms, not the 11 ms sum
    assert synthetic["busy_s"] == pytest.approx(0.010)
    # first event at 0 (host), last ends at 30 ms (host)
    assert synthetic["span_s"] == pytest.approx(0.030)


def test_program_sums_by_name(synthetic):
    progs = synthetic["programs"]
    assert progs["verify_kernel"] == [pytest.approx(0.008), 2]
    assert progs["inner_body"] == [pytest.approx(0.001), 1]
    assert progs["tree_leaf_body"] == [pytest.approx(0.001), 1]
    assert xtrace.program_name("jit_inner_body(42)") == "inner_body"
    assert xtrace.program_name("plain") == "plain"


@pytest.mark.parametrize("hlo,want", [
    ("%while.51 = (s32[]{:T(128)}, s32[4,20,16384]{2,1,0:T(8,128)S(1)}) "
     "while((s32[]{:T(128)}) %tuple.6294), condition=%wide", "%while.51 while"),
    ("%copy-done = s32[9,4]{3,2:T(8,128)} copy-done((s32[9,4], u32[]{:S(2)}) "
     "%copy-start)", "%copy-done copy-done"),
    ("%m_fusion.2 = s32[4,20]{2,1,0:T(8,128)} fusion(s32[9] %x), kind=kLoop",
     "%m_fusion.2 fusion"),
    ("fusion.1", "fusion.1"),
])
def test_op_names_are_cut_to_instruction_and_opcode(hlo, want):
    assert xtrace.op_name(hlo) == want


def test_device_ops_ranked(synthetic):
    assert synthetic["device_ops"][0] == ["fusion.1", pytest.approx(0.007)]
    assert [n for n, _s in synthetic["device_ops"]] == [
        "fusion.1", "copy.2", "fusion.9"]


def test_idle_gaps_go_to_the_call_the_host_was_in(synthetic):
    # device idle: [0,1) [5,7) [9,21) [25,30) ms = 20 ms in all.
    # submit covers [0,1) and [10,16): 1 + 6 = 7 ms of idle;
    # accept_ledger covers [5,6.5): 1.5 ms; the rest is outside.
    gaps = dict(synthetic["idle_gaps"])
    assert gaps["submit"] == pytest.approx(0.007)
    assert gaps["accept_ledger"] == pytest.approx(0.0015)
    assert gaps[xtrace.OUTSIDE] == pytest.approx(0.0115)
    assert sum(gaps.values()) == pytest.approx(0.030 - 0.010)


def test_no_device_plane_reads_as_nothing():
    from jax.profiler import ProfileData

    host_only = ProfileData.from_text_proto(
        'planes { id: 2 name: "/host:CPU" lines { id: 1 name: "t" '
        'timestamp_ns: 0 events { metadata_id: 1 offset_ps: 0 '
        'duration_ps: 5 } } event_metadata { key: 1 value { id: 1 '
        'name: "x" } } }')
    assert xtrace.reduce_profile(host_only, set()) is None


RECORDED = os.path.join(HERE, "recorded.v5e.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded chip trace in this checkout")
def test_recorded_chip_trace_reduces():
    out = xtrace.reduce_file(RECORDED, {"bench_step"}, chips=1)
    assert out is not None and out["busy_s"] > 0
    assert out["busy_s"] <= out["span_s"]
    assert any(name.startswith("bench_probe") for name in out["programs"])
    assert out["device_ops"] and out["idle_gaps"]


# -- the generic readers ---------------------------------------------------


def _span(name, sid, ts, dur, parent=None, trace="t"):
    args = {"span": sid, "trace": trace}
    if parent is not None:
        args["parent"] = parent
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "args": args}


def test_span_self_time_subtracts_children_once():
    events = [
        _span("close", 1, 0, 100),
        _span("apply", 2, 10, 40, parent=1),
        _span("seal", 3, 30, 40, parent=1),  # overlaps apply by 20
        _span("leaf", 4, 35, 5, parent=3),
    ]
    self_us = readers.span_self_times(events)
    assert self_us[1] == 100 - 60  # children cover [10,70)
    assert self_us[2] == 40
    assert self_us[3] == 35


def test_generic_readers():
    sources = {
        "counters": {"a": 3, "b": 12, "closes": 2,
                     "verify.device_sigs": 32768},
        "samples": {"lat": [1.0, 2.0, 3.0]},
        "spans": [_span("close.seal", 1, 0, 3000),
                  _span("close.seal", 2, 5000, 5000),
                  _span("persist.total", 3, 0, 1000)],
        "trace": {"programs": {"verify_kernel": [0.4, 2],
                               "inner_body": [0.1, 9]},
                  "window_busy_s": 0.5, "window_s": 10.0},
    }
    R = readers.GENERIC
    assert R["counter"](sources, {"path": "a"}) == 3
    assert R["counter"](sources, {"path": "missing"}) is None
    assert R["counter_ratio"](
        sources, {"num": ["a"], "den": ["a", "b"], "scale": 100}) == 20.0
    assert R["counter_ratio"](sources, {"num": ["a"], "den": ["zz"]}) is None
    assert R["sample_percentile"](sources, {"sample": "lat", "q": 50}) == 2.0
    assert R["sample_percentile"](sources, {"sample": "no", "q": 50}) is None
    assert R["span_ms_per"](
        sources, {"spans": ["close.seal"], "per": "span"}) == 4.0
    assert R["span_ms_per"](
        sources, {"spans": ["persist.total"], "per": "closes"}) == 0.5
    assert R["span_ms_per"](sources, {"spans": ["nope"]}) is None
    assert R["program_us_per"](
        sources, {"prefixes": ["verify_kernel"],
                  "per": "verify.device_sigs"}) == pytest.approx(
                      0.4e6 / 32768)
    assert R["trace_idle_share"](sources, {}) == pytest.approx(95.0)
    assert R["trace_idle_share"]({"trace": None}, {}) is None


def test_every_reader_file_names_a_known_reader():
    layers = os.path.join(BENCH, "layers")
    for f in sorted(os.listdir(layers)):
        if f.endswith(".json"):
            with open(os.path.join(layers, f)) as fh:
                spec = json.load(fh)
            if "same_as" in spec:  # read through another metric's file
                assert spec == {"same_as": spec["same_as"]}, f
                manifest.reader_file(BENCH, spec["same_as"])
            else:
                assert spec["reader"] in readers.GENERIC, f


def test_same_as_reads_through_the_other_metrics_file(tmp_path):
    (tmp_path / "a.x.json").write_text('{"reader": "counter", "path": "n"}')
    (tmp_path / "b.x.json").write_text('{"same_as": "a.x"}')
    (tmp_path / "c.x.json").write_text('{"same_as": "nowhere"}')
    sources = {"counters": {"n": 7}}
    assert readers.read_metric(str(tmp_path / "b.x.json"), sources) == 7
    with pytest.raises(FileNotFoundError):
        readers.read_metric(str(tmp_path / "c.x.json"), sources)


# -- the door's sweep -------------------------------------------------------


def test_step_report_judges_a_step_by_answers_and_backlog():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "door_driver", os.path.join(BENCH, "drivers", "door.py"))
    door = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(door)
    steps = [[10.0, 10.0], [10.0, 20.0]]
    # step 0: 100 requests, each answered 5 ms after it was due
    rows = [(t / 10.0, t / 10.0 + 0.005, 0.1, 5.0, 0) for t in range(100)]
    # step 1: 200 due, but only 12 a second are answered: a backlog grows
    rows += [(10.0 + t / 20.0, 10.0 + t / 12.0, 0.0,
              (t / 12.0 - t / 20.0) * 1000.0, 1) for t in range(200)]
    first, second = door.step_report(steps, rows)
    assert "10 req/s for 10s, sustained: offered 10.0/s" in first
    assert "answered inside 10.0/s, backlog at its end 0;" in first
    assert "20 req/s for 10s, NOT sustained: offered 20.0/s" in second
    assert "answered inside 12.0/s, backlog at its end 80;" in second


# -- peaks and operation counts -------------------------------------------


def test_peaks_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")
    per_sig = peaks.verify_int32_lane_ops(16384) / 16384
    assert 2.5e6 < per_sig < 4.5e6  # "about 3.1M" in the old prose
    assert peaks.roofline_share(1e9, 1e6, 1.0, None, 819e9) is None
    share, bound = peaks.roofline_share(1e12, 1e6, 0.1, 100e12, 819e9)
    assert bound == "compute" and share == pytest.approx(10.0)


# -- the manifest ----------------------------------------------------------


def _base():
    return manifest.load(os.path.join(REPO, "BENCHMARK.json"))


def test_manifest_holds_to_the_contract():
    m = _base()
    manifest.validate(m, REPO)  # names, units, files found by name
    for x in m["end_to_end"] + m["per_layer"]:
        assert len(x["unit"]) <= 16 and " " not in x["unit"]


def with_a_fourth_cell(m: dict, name: str, like: str, traffic: str) -> dict:
    """``m`` plus one cell that reports what the cell ``like`` reports:
    one ``workloads`` entry, and its name in the metrics' lists."""
    cell = dict(next(w for w in m["workloads"] if w["name"] == like),
                name=name, traffic=traffic, why="a cell added as data")
    m["workloads"].append(cell)
    for x in m["end_to_end"] + m["per_layer"]:
        if like in x.get("workloads", []):
            x["workloads"].append(name)
    return m


SWEEP = ("node.door-sweep", "node.door", "door-sweep")


def test_a_fourth_cell_is_one_entry_and_its_files():
    # traffic/door-sweep.json is in no cell of BENCHMARK.json: naming it
    # in one more entry makes it a cell, and no file that is there changes
    m = with_a_fourth_cell(_base(), *SWEEP)
    manifest.validate(m, REPO)
    files = manifest.cell_files(m, SWEEP[0], REPO)
    assert files["traffic"]["profile"] and files["traffic"]["driver"] == "door"
    toy = manifest.cell_files(m, SWEEP[0], REPO, rehearsal=True)
    assert toy["config"]["population"]["accounts"] < (
        files["config"]["population"]["accounts"])
    assert toy["config"]["population"]["name"] == (
        files["config"]["population"]["name"])  # laid over, not replaced
    assert "max_batch=16384" in files["ini"]
    assert "max_batch=16384" not in toy["ini"]


@pytest.mark.parametrize("cell", [w["name"] for w in _base()["workloads"]]
                         + [SWEEP[0]])
def test_cpu_rehearsal_end_to_end(cell, tmp_path):
    """The whole command at toy sizes on the CPU, for every cell and for
    a cell added as data: correct, and no timing metric on the line."""
    m = with_a_fourth_cell(_base(), *SWEEP)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3", "--seconds", "5", "--trace", "1", "--rehearsal",
         "--manifest", str(path)],
        env=env, capture_output=True, text=True, timeout=900, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    timed = {x["name"] for x in m["end_to_end"] + m["per_layer"]
             if x["source"] != "program_counter"}
    assert line["metrics"] and not timed & set(line["metrics"])


@pytest.mark.parametrize("breakage", [
    lambda m: m["workloads"][0].update(name="has space"),
    lambda m: m["workloads"][0].update(chips=2),
    lambda m: m["workloads"][0].update(why="x" * 201),
    lambda m: m["end_to_end"][0].update(unit="tokens per second"),
    lambda m: m["end_to_end"][0].update(bound=0.5),
    lambda m: m["end_to_end"][0].update(source="program_counter"),
    lambda m: m["end_to_end"][0].update(why="no such key"),
    lambda m: m["per_layer"][0].update(moves="nothing"),
    lambda m: m["per_layer"].append(dict(m["per_layer"][0])),
    lambda m: m.update(run_seconds=52),
    lambda m: m.update(extra=1),
    lambda m: m["command"].append("/etc/passwd"),
    lambda m: m["workloads"][0].update(traffic="no-such-traffic"),
    lambda m: m["end_to_end"].pop(
        next(i for i, x in enumerate(m["end_to_end"])
             if x["name"] == "setup_s")),
])
def test_manifest_refuses(breakage):
    m = _base()
    breakage(m)
    with pytest.raises(manifest.ManifestError):
        manifest.validate(m, REPO)


def test_run_py_names_no_cell_config_or_metric():
    with open(os.path.join(BENCH, "run.py")) as fh:
        text = fh.read()
    m = _base()
    names = ([w["name"] for w in m["workloads"]]
             + [c["name"] for c in m["configs"]]
             + [w["traffic"] for w in m["workloads"]]
             + [x["name"] for x in m["end_to_end"] + m["per_layer"]
                if x["name"] != "setup_s"])
    for name in names:
        assert f'"{name}"' not in text and f"'{name}'" not in text, name


# -- no chip, no result ----------------------------------------------------


def test_run_refuses_to_print_a_result_on_cpu():
    m = _base()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         m["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no result" in proc.stderr
