"""A lazily resumed node keeps its incremental seal (ISSUE 26).

The plain reference is a ``cpu``/``hashlib`` node over the same stored
state loaded EAGERLY (whole tree in memory, no stubs), serial apply,
full seal; the node under test boots ``start_up=load`` (lazy trees)
under a hot cache small enough to evict in every close. Both are fed
the same seeded stream of the sliding-window generator and must close
to byte-identical ledger, state and transaction hashes at every close,
with the native and with the Python merge.
"""

import json
import os
import shutil
import sys
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))

from yardstick import nodedrive, prepared, prepared_state, sliding  # noqa: E402

from stellard_tpu.engine.deltareplay import SpecRecord, SpecState  # noqa: E402
from stellard_tpu.node.config import Config  # noqa: E402
from stellard_tpu.node.node import Node  # noqa: E402
from stellard_tpu.nodestore.core import make_database  # noqa: E402
from stellard_tpu.protocol.sfields import sfBalance  # noqa: E402
from stellard_tpu.protocol.stobject import STObject  # noqa: E402
from stellard_tpu.protocol.sttx import SerializedTransaction  # noqa: E402
from stellard_tpu.protocol.ter import TER  # noqa: E402
from stellard_tpu.state import shamap  # noqa: E402
from stellard_tpu.state.hotcache import HotNodeCache  # noqa: E402
from stellard_tpu.state.ledger import Ledger  # noqa: E402
from stellard_tpu.state.shamap import (  # noqa: E402
    LazyInner, SHAMap, SHAMapItem, Stub, inner_node_cache,
)

ACCOUNTS = 8192
CLOSES = 12
POP = {"name": "lazy-resume-pop", "accounts": ACCOUNTS,
       "funded_drops": 20_000_000_000, "fee_drops": 1_000_000}
TRAFFIC = {"senders": 512, "slide": 64, "close_every": 256,
           "amount_drops": 250_000_000, "fee_drops": 1_000_000,
           "zipf_theta": 0.99, "planted_per_1024": 4}
INI = """[standalone]
1

[start_up]
{start_up}

[signature_backend]
type=cpu

[hash_backend]
type=cpu

[node_db]
type=segstore
path={workdir}/nodestore
durability=fsync

[database_path]
{workdir}/stellard.db

[spec]
workers=1

[txq]
min_cap=1000000
max_cap=1000000

[tree]
cache_mb=1
"""
# the plain reference: serial apply, full seal
PLAIN_INI = INI + "incremental=0\n\n[close]\ndelta_replay=0\n"


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    """-> (the prepared store's directory, the signed stream)."""
    cache = tmp_path_factory.mktemp("prepared")
    config = {"name": "lazy-resume", "population": POP}
    directory = prepared_state.ensure(config, INI, str(cache))
    entries = sliding.sliding_stream(
        seed=2600000011, pop=POP, params=TRAFFIC,
        count=CLOSES * TRAFFIC["close_every"])
    return directory, entries


def drive(node, meta, entries):
    """Feed the stream, a close every ``close_every`` valid payments ->
    [(ledger hash, state hash, tx hash)] and the pump."""
    pump = nodedrive.Pump(node, 64, closes_done=meta["closes_done"])
    hashes = []
    valid = 0
    for blob, planted, _s, _d, _txid in entries:
        pump.submit(SerializedTransaction.from_bytes(blob))
        valid += 0 if planted else 1
        if valid == TRAFFIC["close_every"]:
            led, _results, _ms = pump.close()
            hashes.append((led.hash(), led.account_hash, led.tx_hash))
            valid = 0
    node.close_pipeline.flush(timeout=120)
    return hashes, pump


def outcomes_ok(pump, entries):
    for _blob, planted, _s, _d, txid in entries:
        ter, applied = pump.outcomes[txid]
        want = (int(TER.temINVALID), False) if planted else (0, True)
        assert (ter, applied) == want


def plain_hashes(store, tmp):
    """The plain path's hashes: the stored ledger loaded eagerly into a
    cpu/hashlib node that applies serially and seals in full."""
    directory, entries = store
    workdir, meta = prepared.copy_for_run(directory, str(tmp))
    ini = nodedrive.ini_text(PLAIN_INI, workdir=os.path.join(workdir, "db"),
                             start_up="fresh")
    node = Node(Config.from_ini(ini)).setup()
    try:
        led = Ledger.load(node.nodestore,
                          bytes.fromhex(meta["last_ledger"]["hash"]),
                          hash_batch=node.hasher, lazy=False)
        assert led.state_map._source is None
        node.ledger_master.load_ledger(led)
        hashes, pump = drive(node, meta, entries)
        outcomes_ok(pump, entries)
        dj = node.ledger_master.delta_replay_json()
        assert dj["closes"] == 0 and dj["incremental_seals"] == 0
    finally:
        node.stop()
    inner_node_cache().clear()
    shutil.rmtree(workdir, ignore_errors=True)
    return hashes


@pytest.fixture(scope="module")
def reference(store, tmp_path_factory):
    return plain_hashes(store, tmp_path_factory.mktemp("plain"))


@pytest.fixture(params=["native", "python"])
def merge(request, monkeypatch):
    if request.param == "python":
        shamap._resolve_native()
        monkeypatch.setattr(shamap, "_native_merge", None)
        monkeypatch.setattr(shamap, "_native_merge_stub_ok", False)
    else:
        shamap._resolve_native()
        if not shamap._native_merge_stub_ok:
            pytest.skip("the native merge has no stub door here")
    return request.param


@pytest.fixture
def lazy_run(store, merge, tmp_path):
    """One run of the lazily resumed node -> what the tests compare."""
    directory, entries = store
    workdir, meta = prepared.copy_for_run(directory, str(tmp_path))
    ini = nodedrive.ini_text(INI, workdir=os.path.join(workdir, "db"),
                             start_up="load")
    cache = inner_node_cache()
    cache.clear()
    before = cache.get_json()
    node = Node(Config.from_ini(ini)).setup()
    try:
        assert node.ledger_master.closed_ledger().state_map._source is not None
        over = []
        orig_put = cache.put

        def put(*a, **kw):
            orig_put(*a, **kw)
            over.append(cache.resident_bytes - cache.limit_bytes)

        cache.put = put
        try:
            hashes, pump = drive(node, meta, entries)
        finally:
            del cache.put
        # one empty close behind the flush: its cycle takes what the
        # last ledger's persist faulted, and nothing runs after it
        node.ops.accept_ledger()
        after = cache.get_json()
        spans = [ev for ev in node.tracer.chrome_trace()["traceEvents"]
                 if ev.get("ph") == "X" and ev["name"] == "close.total"]
        return {
            "hashes": hashes, "pump": pump, "entries": entries,
            "cache_before": before, "cache_after": after,
            "over_budget": max(over), "close_spans": spans,
            "delta_replay": node.ledger_master.delta_replay_json(),
        }
    finally:
        node.stop()
        cache.clear()


class TestLazyNodeAgainstEagerPlainNode:
    def test_byte_identical_hashes_at_every_close(self, lazy_run, reference):
        assert len(lazy_run["hashes"]) == CLOSES == len(reference)
        for k, (got, want) in enumerate(zip(lazy_run["hashes"], reference)):
            assert got == want, f"close {k}"
        outcomes_ok(lazy_run["pump"], lazy_run["entries"])

    def test_every_close_sealed_from_the_building_tree(self, lazy_run):
        dj = lazy_run["delta_replay"]
        assert dj["building_fold_failures"] == 0
        assert dj["closes"] == CLOSES
        assert dj["incremental_seals"] == CLOSES

    def test_faults_and_evicts_within_the_budget(self, lazy_run):
        a, b = lazy_run["cache_after"], lazy_run["cache_before"]
        assert a["faults"] > b["faults"]
        assert a["fault_s"] > b["fault_s"]
        assert a["evictions"] > b["evictions"]
        # never over the budget once an insert's eviction has run
        assert lazy_run["over_budget"] <= 0
        per_close = [ev["args"]["evictions"]
                     for ev in lazy_run["close_spans"][:CLOSES]]
        assert len(per_close) == CLOSES
        assert all(n > 0 for n in per_close[2:]), per_close

    def test_close_total_carries_the_caches_differences(self, lazy_run):
        a, b = lazy_run["cache_after"], lazy_run["cache_before"]
        spans = lazy_run["close_spans"]
        assert len(spans) == CLOSES + 1
        for key in ("faults", "evictions"):
            assert sum(ev["args"][key] for ev in spans) == a[key] - b[key] > 0
        assert sum(ev["args"]["fault_s"] for ev in spans) == pytest.approx(
            a["fault_s"] - b["fault_s"], abs=1e-4)
        assert all(0 < ev["args"]["resident_bytes"] <= a["limit_bytes"]
                   for ev in spans)


def lazy_state(n=600):
    """-> (a lazily opened tree of ``n`` items, the same tree eager)."""
    db = make_database(type="memory")
    eager = SHAMap()
    eager.bulk_update(sets=[
        SHAMapItem(bytes([i % 251, i // 251]) + bytes(30), b"v%d" % i)
        for i in range(n)])
    from stellard_tpu.nodestore.core import NodeObjectType

    eager.flush(db.store_fn(NodeObjectType.ACCOUNT_NODE), db.flushed)

    def fetch(h):
        o = db.fetch(h)
        return o.data if o else None

    lazy = SHAMap.from_store(eager.get_hash(), fetch, lazy=True,
                             store_known=db.flushed)
    return lazy, eager


class TestBuildingTreeOverStubs:
    def record(self, items):
        rec = SpecRecord(TER.tesSUCCESS, TER.tesSUCCESS, True, {}, [],
                         [(it.tag, it) for it in items], None, 0)
        rec.index = 0
        return rec

    def test_attach_building_over_a_stub_root_folds_a_record(self):
        inner_node_cache().clear()
        lazy, eager = lazy_state()
        assert type(lazy.root) is LazyInner
        assert any(type(c) is Stub for c in lazy.root.children)
        led = Ledger(seq=3, state_map=lazy)
        spec = SpecState(led)
        spec.attach_building(led.state_map, None)
        assert spec.building._source is lazy._source is not None
        items = [SHAMapItem(bytes([7, 1]) + bytes(30), b"new"),
                 SHAMapItem(bytes([200, 9]) + bytes([1] * 30), b"fresh")]
        assert spec.fold_building(self.record(items)) == 2
        assert spec.building is not None and spec.fold_failures == 0
        eager.bulk_update(sets=items)
        assert spec.building.get_hash() == eager.get_hash()
        # the parent tree is untouched: the building tree is a snapshot
        assert lazy.get(items[0].tag).data == b"v%d" % (7 + 251)

    def test_an_injected_fold_failure_is_counted(self, monkeypatch):
        lazy, _eager = lazy_state(n=40)
        spec = SpecState(Ledger(seq=3, state_map=lazy))
        spec.attach_building(lazy, None)

        def boom(*a, **kw):
            raise RuntimeError("injected")

        monkeypatch.setattr(spec.building, "bulk_update", boom)
        rec = self.record([SHAMapItem(bytes([1, 1]) + bytes(30), b"x")])
        assert spec.fold_building(rec) == 0
        assert spec.building is None and spec.fold_failures == 1


class TestNoWalkOfALazyState:
    """``len(SHAMap)`` walks every leaf; on a lazily opened tree that
    faults the whole state in. Nothing on the way from boot to a close
    may ask such a map for its truth."""

    def test_a_ledger_built_over_a_lazy_map_faults_nothing(self):
        lazy, _eager = lazy_state()
        cache = inner_node_cache()
        cache.clear()
        before = cache.faults
        led = Ledger(seq=5, state_map=lazy)
        led.closed = True
        child = led.open_successor()
        snap = child.snapshot()
        assert cache.faults == before
        assert snap.state_map.root is lazy.root
        assert child.state_map._source is lazy._source

    def test_an_empty_map_handed_over_is_kept(self):
        tx_map, state_map = SHAMap(shamap.TNType.TX_MD), SHAMap()
        led = Ledger(seq=2, tx_map=tx_map, state_map=state_map)
        assert led.tx_map is tx_map and led.state_map is state_map

    def test_the_drivers_probe_agrees(self):
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "benchmarks", "drivers"))
        import resume

        assert resume.opens_without_a_walk() is True


class TestFoldFailureCounter:
    def test_ledger_master_counts_what_fold_building_swallows(
            self, store, tmp_path, monkeypatch):
        directory, entries = store
        workdir, meta = prepared.copy_for_run(directory, str(tmp_path))
        ini = nodedrive.ini_text(INI, workdir=os.path.join(workdir, "db"),
                                 start_up="load")
        calls = []
        real = SpecState.attach_building

        def attach(self, state_map, hash_batch):
            real(self, state_map, hash_batch)
            if not calls:  # the first open ledger's tree fails its folds

                def boom(*a, **kw):
                    raise RuntimeError("injected")

                self.building.bulk_update = boom
            calls.append(self)

        monkeypatch.setattr(SpecState, "attach_building", attach)
        node = Node(Config.from_ini(ini)).setup()
        try:
            n = 2 * TRAFFIC["close_every"] + sum(
                1 for e in entries[:2 * TRAFFIC["close_every"] + 8] if e[1])
            hashes, _pump = drive(node, meta, entries[:n])
            dj = node.ledger_master.delta_replay_json()
        finally:
            node.stop()
            inner_node_cache().clear()
        assert len(hashes) == 2 and dj["closes"] == 2
        assert dj["building_fold_failures"] == 1
        assert dj["incremental_seals"] == 1  # the second close's


class TestFaultSeconds:
    def test_fault_s_grows_with_faults(self):
        cache = HotNodeCache(limit_bytes=1 << 20)

        def loader(key):
            time.sleep(0.002)
            return shamap.Leaf(SHAMapItem(key, b"x" * 40),
                               shamap.TNType.ACCOUNT_STATE), 80

        assert cache.get_json()["fault_s"] == 0.0
        seen = []
        for i in range(5):
            cache.get_or_load(bytes([i]) * 32, loader)
            seen.append((cache.faults, cache.fault_s))
        assert [f for f, _s in seen] == [1, 2, 3, 4, 5]
        assert all(b[1] - a[1] >= 0.002 for a, b in zip(seen, seen[1:]))
        cache.get_or_load(bytes([0]) * 32, loader)  # a hit costs nothing
        assert (cache.faults, cache.fault_s) == seen[-1]
        assert cache.get_json()["fault_s"] == pytest.approx(
            seen[-1][1], abs=1e-6)

    def test_a_failed_load_counts_no_seconds(self):
        cache = HotNodeCache(limit_bytes=1 << 20)

        def loader(key):
            raise KeyError(key.hex())

        with pytest.raises(KeyError):
            cache.get_or_load(bytes(32), loader)
        assert cache.faults == 1 and cache.fault_s == 0.0


class TestSlidingStream:
    PARAMS = [
        {"senders": 4096, "slide": 512, "close_every": 2048, "window": 96},
        {"senders": 512, "slide": 64, "close_every": 256, "window": 64},
        {"senders": 256, "slide": 32, "close_every": 64, "window": 32},
    ]

    @pytest.mark.parametrize("p", PARAMS, ids=lambda p: str(p["senders"]))
    def test_window_slides_and_nobody_sends_twice_in_flight(self, p):
        closes = 12
        pos = sliding.sender_positions(
            p["senders"], p["slide"], p["close_every"],
            closes * p["close_every"])
        for j in range(closes):
            mine = pos[j * p["close_every"]:(j + 1) * p["close_every"]]
            assert min(mine) >= j * p["slide"]
            assert max(mine) < j * p["slide"] + p["senders"]
        for k in range(len(pos) - p["window"]):
            span = pos[k:k + p["window"]]
            assert len(set(span)) == len(span), k
        # round robin: between two payments of one sender the cursor
        # went round what was left of the window
        last = {}
        for k, q in enumerate(pos):
            if q in last:
                assert k - last[q] >= p["senders"] - p["slide"]
            last[q] = k

    def test_same_seed_same_stream_and_shape(self):
        pop = dict(POP, accounts=2048)
        tr = dict(TRAFFIC, senders=256, slide=32, close_every=64)
        a = sliding.sliding_stream(seed=2147483777, pop=pop, params=tr,
                                   count=512)
        b = sliding.sliding_stream(seed=2147483777, pop=pop, params=tr,
                                   count=512)
        c = sliding.sliding_stream(seed=2147483778, pop=pop, params=tr,
                                   count=512)
        assert a == b and a != c
        valid = [e for e in a if not e[1]]
        planted = [e for e in a if e[1]]
        assert len(valid) == 512 and len(planted) == 4
        assert all(s != d for _b, _p, s, d, _t in a)
        assert all(0 <= s < 2048 and 0 <= d < 2048 for _b, _p, s, d, _t in a)
        for k in range(len(valid) - 96):
            senders = [e[2] for e in valid[k:k + 96]]
            assert len(set(senders)) == 96
        # a planted entry follows its source: same sender, same sequence
        for i, e in enumerate(a):
            if e[1]:
                src = SerializedTransaction.from_bytes(a[i - 1][0])
                bad = SerializedTransaction.from_bytes(e[0])
                assert (bad.account, bad.sequence) == (src.account,
                                                       src.sequence)
                assert e[4] != a[i - 1][4]

    def test_a_stream_longer_than_the_population_is_refused(self):
        pop = dict(POP, accounts=300)
        tr = dict(TRAFFIC, senders=256, slide=32, close_every=64)
        with pytest.raises(ValueError):
            sliding.sliding_stream(seed=1, pop=pop, params=tr, count=64 * 8)


def test_prepared_state_resumes_as_a_stored_ledger(store, tmp_path):
    """What the builder writes is what ``start_up=load`` resumes: the
    CLF pointer, the txdb header, a state whose coins add up."""
    directory, _entries = store
    with open(os.path.join(directory, "meta.json")) as fh:
        meta = json.load(fh)
    workdir, _ = prepared.copy_for_run(directory, str(tmp_path))
    ini = nodedrive.ini_text(INI, workdir=os.path.join(workdir, "db"),
                             start_up="load")
    node = Node(Config.from_ini(ini)).setup()
    try:
        led = node.ledger_master.closed_ledger()
        assert led.hash().hex() == meta["last_ledger"]["hash"]
        assert led.seq == meta["last_ledger"]["seq"] == 2
        assert node.txdb.get_ledger_header()["hash"] == led.hash()
        # the book index was seeded from the mirror's (empty) offer
        # list: no walk of the state, at boot or at the first close
        idx = node.path_plane.index
        assert idx.seeded == 1 and idx.full_rebuilds == 0
        assert idx.seq == led.seq
        faults = inner_node_cache().faults
        node.ops.accept_ledger()
        assert idx.full_rebuilds == 0
        assert inner_node_cache().faults - faults < 64
        total = sum(STObject.from_bytes(it.data)[sfBalance].drops()
                    for it in led.state_map.items())
        assert total == led.tot_coins
    finally:
        node.stop()
        inner_node_cache().clear()
