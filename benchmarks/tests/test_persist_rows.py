"""``persist.rows_per_statement`` (PR 31) on made-up spans with exact
answers, and on what a parent commit hands it (``persist.txdb`` and
``persist.clf`` spans with no ``rows`` / ``statements``): there it
returns None and raises nothing. Run by hand:
``python -m pytest benchmarks/tests -q`` (not part of tier-1)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, REPO)

from yardstick import manifest, readers  # noqa: E402

METRIC = "persist.rows_per_statement"


def read(sources):
    return readers.read_metric(manifest.reader_file(BENCH, METRIC), sources)


def span(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "tid": 1,
            "pid": 1, "cat": "persist", "args": dict(args)}


def window():
    """Two closes of an exchange: 22,000 and 3,000 rows in 4 and 6."""
    return [
        span("persist.nodestore", 0, 900, seq=7),
        span("persist.txdb", 900, 700, seq=7, rows=22_000, statements=4),
        span("persist.clf", 1_600, 400, seq=7, rows=3_000, statements=6),
        span("persist.total", 0, 2_000, seq=7, kind="close", txs=2048),
        span("persist.txdb", 5_000, 700, seq=8, rows=21_000, statements=4),
        span("persist.clf", 5_700, 400, seq=8, rows=4_000, statements=6),
        {"name": "persist.tx", "ph": "i", "ts": 5_800, "args": {}},
    ]


def test_the_ratio_over_both_sql_stages_of_the_window():
    assert read({"spans": window()}) == pytest.approx(50_000 / 20)


def test_a_repair_has_no_clf_span_and_still_counts():
    spans = [span("persist.txdb", 0, 5, seq=3, rows=10, statements=4)]
    assert read({"spans": spans}) == pytest.approx(2.5)


def test_only_the_windows_list_and_only_the_sql_stages_are_read():
    """The driver hands over the spans it drained inside the window:
    what it drained in set-up (or keeps under another key) is not read,
    and no other stage's attributes are."""
    sources = {
        "spans": window() + [
            span("persist.nodestore", 9_000, 5, rows=10**9, statements=1),
            span("close.total", 9_000, 5, rows=10**9, statements=1)],
        "setup_spans": [
            span("persist.txdb", -9_000, 5, rows=10**9, statements=1)],
    }
    assert read(sources) == pytest.approx(2_500.0)


@pytest.mark.parametrize("sources", [
    {},
    {"spans": []},
    {"spans": [span("persist.total", 0, 5, seq=7, kind="close", txs=9)]},
    # the parent: the spans are there, the attributes are not
    {"spans": [span("persist.txdb", 0, 5, seq=7),
               span("persist.clf", 5, 5, seq=7)]},
    # a torn mix of the two is nothing sound either
    {"spans": [span("persist.txdb", 0, 5, seq=7, rows=9, statements=3),
               span("persist.clf", 5, 5, seq=7)]},
    {"spans": [span("persist.txdb", 0, 5, seq=7, rows=0, statements=0)]},
], ids=["no-spans-key", "untraced", "no-sql-stage", "parent", "torn",
        "no-statement"])
def test_nothing_to_read_is_none_and_raises_nothing(sources):
    assert read(sources) is None


def test_the_manifest_names_the_reader_and_its_cells():
    m = manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    manifest.validate(m, REPO)
    entry, = [x for x in m["per_layer"] if x["name"] == METRIC]
    assert entry["workloads"] == ["node.flood", "state-1m.zipf",
                                  "node.offers"]
    assert entry["layer"] == "persist"
    assert entry["moves"] == "validated_tx_per_s"
