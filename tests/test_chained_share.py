"""The reader of ``catchup.chained_share`` (a file of the benchmark's,
``benchmarks/layers/``) on synthetic ``replay.span`` roots with exact
answers, on a program whose spans lack the attribute and on the spans
``replay_range`` records itself; and the manifest with its entry."""

from __future__ import annotations

import os
import sys
import time
import types

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

from yardstick import manifest, readers  # noqa: E402

from stellard_tpu.engine.engine import TxParams  # noqa: E402
from stellard_tpu.node.ledgermaster import LedgerMaster  # noqa: E402
from stellard_tpu.node.ledgertools import replay_range  # noqa: E402
from stellard_tpu.node.tracer import get_tracer  # noqa: E402
from stellard_tpu.nodestore.core import make_database  # noqa: E402
from stellard_tpu.protocol.formats import TxType  # noqa: E402
from stellard_tpu.protocol.keys import KeyPair  # noqa: E402
from stellard_tpu.protocol.sfields import sfAmount, sfDestination  # noqa: E402
from stellard_tpu.protocol.stamount import STAmount  # noqa: E402
from stellard_tpu.protocol.sttx import SerializedTransaction  # noqa: E402

METRIC = "catchup.chained_share"


def read(sources):
    return readers.read_metric(manifest.reader_file(BENCH, METRIC), sources)


def window(roots, lo=9.5, hi=60.0):
    """The process tracer holding `roots` ((start, stop, attrs) on its
    own clock) and a capture from `lo` to `hi` -> the reader's sources."""
    tr = get_tracer()
    tr.reset()
    e = tr.epoch
    for start, stop, attrs in roots:
        tr.complete("replay.span", "replay", e + start, e + stop, **attrs)
    cap = types.SimpleNamespace(t_start=e + lo, t_stop=e + hi, spans=[])
    return {"counters": {}, "spans": [], "capture": cap}


@pytest.fixture(autouse=True)
def clean_ring():
    yield
    get_tracer().reset()


@pytest.mark.parametrize("roots,want", [
    # two sound spans of 8: 7 of 8 each
    ([(10, 17, {"ledgers": 8, "chained": 7}),
      (17, 24, {"ledgers": 8, "chained": 7})], 87.5),
    # one of them with a broken chain (a ledger failed: two store loads)
    ([(10, 17, {"ledgers": 8, "chained": 7}),
      (17, 25, {"ledgers": 8, "chained": 6})], 81.25),
    # the warm-up's span lies before the capture and does not count
    ([(1, 2, {"ledgers": 1, "chained": 0}),
      (10, 17, {"ledgers": 8, "chained": 7})], 87.5),
    ([(10, 11, {"ledgers": 1, "chained": 0})], 0.0),
    # a program without the attribute (the parent commit): nothing
    ([(10, 24, {"ledgers": 8, "gc_pause_s": 1.0})], None),
    ([(10, 17, {"ledgers": 8, "chained": 7}),
      (17, 31, {"ledgers": 8})], None),
    ([], None),
])
def test_chained_share_on_synthetic_roots(roots, want):
    got = read(window(roots))
    assert got is None if want is None else got == pytest.approx(want)


def test_an_untraced_run_reads_nothing():
    sources = window([(10, 17, {"ledgers": 8, "chained": 7})])
    sources["capture"] = types.SimpleNamespace(
        t_start=None, t_stop=None, spans=[])
    assert read(sources) is None
    assert read({"counters": {}, "spans": []}) is None


def test_chained_share_of_what_replay_range_records():
    """The program's own spans in the process tracer, as the catch-up
    driver leaves them: 4 contiguous ledgers, then the same list with
    its second ledger's one signature refused (the third ledger's parent
    comes from the store): 3 + 2 of 8."""
    master = KeyPair.from_passphrase("masterpassphrase")
    lm = LedgerMaster()
    lm.start_new_ledger(master.account_id, close_time=1000)
    db = make_database(type="memory")
    lm.closed_ledger().save(db)
    hashes = []
    for i in range(4):
        tx = SerializedTransaction.build(
            TxType.ttPAYMENT, master.account_id, i + 1, 10,
            {sfAmount: STAmount.from_drops((1000 + i) * 1_000_000),
             sfDestination: KeyPair.from_passphrase(f"cs-{i}").account_id})
        tx.sign(master)
        ter, _ = lm.do_transaction(tx, TxParams.OPEN_LEDGER)
        assert int(ter) == 0
        closed, _ = lm.close_and_advance(2000 + i * 10, 30)
        closed.save(db)
        hashes.append(closed.hash())
    get_tracer().reset()
    t_start = time.perf_counter()
    assert replay_range(db, hashes)["ok"]
    out = replay_range(
        db, hashes, verify_many=lambda reqs: [True, False, True, True])
    assert [l["ok"] for l in out["ledgers"]] == [True, False, True, True]
    cap = types.SimpleNamespace(
        t_start=t_start, t_stop=time.perf_counter(), spans=[])
    got = read({"counters": {}, "spans": [], "capture": cap})
    assert got == pytest.approx(100.0 * (3 + 2) / 8)


def test_the_manifest_holds_the_metric():
    m = manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    manifest.validate(m, REPO)
    entry = next(x for x in m["per_layer"] if x["name"] == METRIC)
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "apply",
        "moves": "catchup_tx_per_s", "workloads": ["catchup.span"]}
    assert METRIC in [x["name"] for x in manifest.metrics_of(
        m, "catchup.span", "per_layer")]
    assert manifest.reader_file(BENCH, METRIC).endswith(METRIC + ".py")
