"""The cell PR 26 added as files: the six readers of ``state-1m.zipf`` on
synthetic counters with exact answers, what they do with a parent
commit's counters (which lack ``fault_s`` and the seal's counts: None,
nothing raised), and the grown ``BENCHMARK.json`` against the contract.
Run by hand: ``python -m pytest benchmarks/tests -q`` (not tier-1)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, REPO)

from yardstick import manifest, readers  # noqa: E402

CELL = "state-1m.zipf"
COUNTERS = {
    "txs": 30720, "closes": 15,
    "cache.faults": 46080, "cache.fault_s": 13.5, "cache.hits": 150000,
    "cache.misses": 50000, "cache.evictions": 12000,
    "cache.evicted_bytes": 16_000_000,
    "cache.resident_bytes": 66_437_775, "cache.limit_bytes": 67_108_864,
    "seal.closes": 15, "seal.incremental_seals": 15,
    "seal.building_fold_failures": 0,
}
WANT = {
    "state.faults_per_tx": 1.5,
    "state.fault_ms_per_close": 900.0,
    "state.cache_hit_share": 75.0,
    "state.evictions_per_close": 800.0,
    "state.resident_share": 100.0 * 66_437_775 / 67_108_864,
    "seal.incremental_share": 100.0,
}
# what a parent without this PR's counters hands over
PARENT = {k: v for k, v in COUNTERS.items()
          if k not in ("cache.fault_s", "seal.incremental_seals",
                       "seal.building_fold_failures")}


def read(metric, counters):
    return readers.read_metric(manifest.reader_file(BENCH, metric),
                               {"counters": counters})


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_on_synthetic_counters(metric):
    assert read(metric, COUNTERS) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_finds_nothing_without_its_counters(metric):
    assert read(metric, {}) is None
    assert read(metric, {"txs": 0, "closes": 0}) is None


def test_a_parent_reports_what_it_counts_and_leaves_out_the_rest():
    got = {metric: read(metric, PARENT) for metric in WANT}
    assert got["state.fault_ms_per_close"] is None
    assert got["seal.incremental_share"] is None
    for metric in ("state.faults_per_tx", "state.cache_hit_share",
                   "state.evictions_per_close", "state.resident_share"):
        assert got[metric] == pytest.approx(WANT[metric])


def test_a_close_sealed_in_full_lowers_the_share():
    assert read("seal.incremental_share",
                dict(COUNTERS, **{"seal.incremental_seals": 12})) == 80.0


def _manifest():
    return manifest.load(os.path.join(REPO, "BENCHMARK.json"))


def test_the_grown_manifest_holds_to_the_contract():
    m = _manifest()
    manifest.validate(m, REPO)
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "state-1m", "zipf", 1)
    assert m["workloads"][-1] is cell and m["configs"][-1]["name"] == "state-1m"
    assert m["configs"][-1]["reduced"] == ["accounts", "cache_mb"]
    e2e = {x["name"] for x in manifest.metrics_of(m, CELL, "end_to_end")}
    # close_p50_ms is not the cell's: six runs spread it by 4.5%, more
    # than half its bound (PERF.md section 6, PR 26), so the metrics
    # that move it are not the cell's either
    assert e2e == {"validated_tx_per_s", "setup_s"}
    per = {x["name"]: x for x in manifest.metrics_of(m, CELL, "per_layer")}
    assert set(WANT) <= set(per) and len(per) == len(WANT) + 9
    assert {x["moves"] for x in per.values()} == {"validated_tx_per_s"}
    for name in WANT:
        assert per[name]["workloads"] == [CELL]


def test_the_cell_is_the_floods_in_all_but_the_state_and_the_senders():
    m = _manifest()
    mine = manifest.cell_files(m, CELL, REPO)
    flood = manifest.cell_files(m, "node.flood", REPO)
    assert mine["ini"] == flood["ini"] + "\n[tree]\ncache_mb=64\n"
    differ = {k for k in set(mine["traffic"]) | set(flood["traffic"])
              if mine["traffic"].get(k) != flood["traffic"].get(k)}
    assert differ == {"driver", "what", "slide", "reclose_ledgers"}
    assert (mine["traffic"]["senders"], mine["traffic"]["slide"]) == (4096, 512)
    pop = mine["config"]["population"]
    assert pop["accounts"] == 1 << 20 and pop["name"] == "bench-pop-1m"
    assert mine["config"]["guarantees"][:4] == flood["config"]["guarantees"]
    assert mine["config"]["architecture"] is None
    assert set(mine["config"]["reduced"]) == {"accounts", "cache_mb"}
    entry = m["configs"][-1]
    assert entry["source"] == mine["config"]["source"]
    toy = manifest.cell_files(m, CELL, REPO, rehearsal=True)
    assert toy["config"]["population"]["accounts"] < 10_000
    assert "cache_mb=1" in toy["ini"] and "start_up" in toy["ini"]
    with open(os.path.join(BENCH, "configs", "state-1m.json")) as fh:
        assert json.load(fh)["name"] == "state-1m"
