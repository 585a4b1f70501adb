"""Out-of-core state plane (ISSUE 13): lazy node faulting
(state/shamap.py Stub/LazyInner/NodeSource), the bounded epoch-aware
hot-node cache (state/hotcache.py), and history shards
(nodestore/shards.py) — byte-identity between lazy and eager trees,
single-flight concurrent faulting, byte-bounded eviction with epoch
preference, shard seal/verify/serve, and the below-floor account_tx
routing."""

from __future__ import annotations

import hashlib
import random
import threading
from collections import OrderedDict

import pytest

from stellard_tpu.state import hotcache
from stellard_tpu.state.hotcache import HotNodeCache, node_cost
from stellard_tpu.state.shamap import (
    SHAMap,
    SHAMapItem,
    LazyInner,
    Stub,
    configure_inner_cache,
    inner_node_cache,
)
from stellard_tpu.utils.hashes import sha512_half


def _tag(s) -> bytes:
    return hashlib.sha256(f"{s}".encode()).digest()


def _build(n: int, prefix: str = "k") -> tuple[SHAMap, dict]:
    m = SHAMap()
    m.bulk_update(sets=[
        SHAMapItem(_tag(f"{prefix}{i}"), f"payload-{i}".encode())
        for i in range(n)
    ])
    store: dict[bytes, bytes] = {}
    m.get_hash()
    m.flush(store.__setitem__)
    return m, store


@pytest.fixture(autouse=True)
def _fresh_cache():
    cache = inner_node_cache()
    cache.clear()
    configure_inner_cache(64)
    yield
    cache.clear()
    configure_inner_cache(64)


class TestLazyFaulting:
    def test_open_is_root_only(self):
        m, store = _build(2000)
        cache = inner_node_cache()
        cache.faults = 0
        lz = SHAMap.from_store(m.get_hash(), store.get, lazy=True)
        assert type(lz.root) is LazyInner
        assert cache.faults == 1  # the root, nothing else
        assert lz.get_hash() == m.get_hash()  # hash needs no walk
        assert cache.faults == 1

    def test_point_reads_fault_on_demand(self):
        m, store = _build(2000)
        cache = inner_node_cache()
        lz = SHAMap.from_store(m.get_hash(), store.get, lazy=True)
        before = cache.faults
        assert lz.get(_tag("k7")).data == b"payload-7"
        path_faults = cache.faults - before
        assert 0 < path_faults <= 8  # O(depth), not O(tree)
        # re-read: pure cache hits
        before = cache.faults
        assert lz.get(_tag("k7")).data == b"payload-7"
        assert cache.faults == before
        assert lz.get(_tag("absent-key")) is None

    def test_walk_and_len_parity(self):
        m, store = _build(500)
        lz = SHAMap.from_store(m.get_hash(), store.get, lazy=True)
        assert len(lz) == 500
        assert [l.item.tag for l in lz.leaves()] == \
            [l.item.tag for l in m.leaves()]

    def test_succ_cursor_parity(self):
        m, store = _build(300)
        lz = SHAMap.from_store(m.get_hash(), store.get, lazy=True)
        k = b"\x00" * 32
        walked = []
        while True:
            item = lz.succ(k)
            if item is None:
                break
            walked.append(item.tag)
            k = item.tag
        assert walked == sorted(l.item.tag for l in m.leaves())

    @pytest.mark.parametrize("use_native", [False, True])
    def test_mutation_byte_identity(self, use_native, monkeypatch):
        if use_native:
            from stellard_tpu.native import load_stser

            if load_stser() is None:
                pytest.skip("native stser unavailable")
        else:
            import stellard_tpu.state.shamap as sm

            monkeypatch.setattr(sm, "_native_merge", None)
            monkeypatch.setattr(sm, "_native_resolved", True)
        m, store = _build(800)
        lz = SHAMap.from_store(m.get_hash(), store.get, lazy=True)
        sets = [SHAMapItem(_tag(f"new{i}"), b"new-%d" % i)
                for i in range(50)]
        dels = [_tag(f"k{i}") for i in range(100, 160)]
        m.bulk_update(sets=sets, deletes=dels)
        lz.bulk_update(sets=sets, deletes=dels)
        assert lz.get_hash() == m.get_hash()
        # per-key mutations too (set_item / del_item fold-up paths)
        m.set_item(SHAMapItem(_tag("solo"), b"solo"))
        lz.set_item(SHAMapItem(_tag("solo"), b"solo"))
        m.del_item(_tag("k3"))
        lz.del_item(_tag("k3"))
        assert lz.get_hash() == m.get_hash()

    def test_compare_faults_only_the_delta(self):
        m, store = _build(2000)
        lz = SHAMap.from_store(m.get_hash(), store.get, lazy=True)
        other = m.snapshot()
        other.set_item(SHAMapItem(_tag("k17"), b"CHANGED"))
        cache = inner_node_cache()
        before = cache.faults
        delta = lz.compare(other)
        assert set(delta) == {_tag("k17")}
        # shared subtrees short-circuit on hashes: the walk faults a
        # path, not the tree
        assert cache.faults - before <= 10

    def test_flush_same_store_never_faults_cold_tail(self):
        m, store = _build(1000)
        known = set(store)  # "this store already holds these"
        lz = SHAMap.from_store(m.get_hash(), store.get, lazy=True,
                               store_known=known)
        lz.set_item(SHAMapItem(_tag("extra"), b"extra"))
        cache = inner_node_cache()
        out: dict[bytes, bytes] = {}
        before = cache.faults
        n = lz.flush(out.__setitem__, known=known)
        # only the dirty path was written, and flushing faulted nothing
        assert 0 < n <= 10
        assert cache.faults == before
        for h, blob in out.items():
            assert sha512_half(blob) == h

    def test_flush_to_foreign_store_materializes_everything(self):
        m, store = _build(300)
        lz = SHAMap.from_store(m.get_hash(), store.get, lazy=True,
                               store_known=set(store))
        other: dict[bytes, bytes] = {}
        n = lz.flush(other.__setitem__)
        assert n == len(store)
        assert set(other) == set(store)

    def test_corrupt_node_detected_at_fault_time(self):
        m, store = _build(200)
        victim = next(iter(store))
        store[victim] = store[victim] + b"x"
        lz = SHAMap.from_store(m.get_hash(), store.get, lazy=True)
        with pytest.raises((ValueError, KeyError)):
            for leaf in lz.leaves():
                pass

    def test_missing_node_raises_keyerror_at_fault(self):
        m, store = _build(200)
        h = m.get_hash()
        lz = SHAMap.from_store(h, store.get, lazy=True)
        # drop an interior node AFTER the lazy open
        victims = [k for k in store if k != h]
        for v in victims[:50]:
            del store[v]
        inner_node_cache().clear()
        with pytest.raises(KeyError):
            for leaf in lz.leaves():
                pass


class TestConcurrentFaulting:
    def test_two_threads_share_one_node_one_fetch(self):
        """Satellite pin: two threads faulting the same hash must share
        ONE node object, counters consistent, no double-fetch."""
        m, store = _build(400)
        fetches = {"n": 0}
        gate = threading.Event()

        def slow_fetch(h):
            fetches["n"] += 1
            gate.wait(1.0)  # widen the race window
            return store.get(h)

        lz = SHAMap.from_store(m.get_hash(), store.get, lazy=True)
        cache = inner_node_cache()
        cache.clear()
        lz._source.fetch = slow_fetch
        fetches["n"] = 0
        faults0, hits0, misses0 = cache.faults, cache.hits, cache.misses
        target = _tag("k5")
        results: list = []
        errors: list = []

        def walk():
            try:
                results.append(lz.get_leaf(target))
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=walk) for _ in range(6)]
        for t in threads:
            t.start()
        gate.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors, errors
        assert len(results) == 6
        # same leaf OBJECT, not six parses of it
        assert all(r is results[0] for r in results)
        # every level fetched at most once across all six threads
        per_key = fetches["n"]
        distinct = cache.faults - faults0
        assert per_key == distinct, (per_key, distinct)
        # counters consistent: every lookup was a hit, a fault, or a
        # shared-flight wait — nothing double-counted
        j = cache.get_json()
        assert (j["hits"] - hits0) + (j["misses"] - misses0) >= distinct

    def test_failed_load_does_not_poison_the_key(self):
        m, store = _build(100)
        lz = SHAMap.from_store(m.get_hash(), store.get, lazy=True)
        cache = inner_node_cache()
        cache.clear()
        real = dict(store)
        broken = {"on": True}

        def flaky(h):
            if broken["on"]:
                return None  # transient miss
            return real.get(h)

        lz._source.fetch = flaky
        with pytest.raises(KeyError):
            lz.get(_tag("k1"))
        broken["on"] = False
        assert lz.get(_tag("k1")).data == b"payload-1"


class _Leaf:
    """A node whose cost `node_cost` reads off its item."""

    def __init__(self, size: int):
        self.item = SHAMapItem(b"\0" * 32, b"x" * size)


class WalkingCache:
    """The oracle: `HotNodeCache` as it was before eviction kept an
    index, its `_evict_locked` word for word (passes 0 and 1 list every
    candidate by walking the whole table), without the lock and the
    in-flight latches a single thread does not need."""

    def __init__(self, limit_bytes: int):
        self.limit_bytes = int(limit_bytes)
        self._data: "OrderedDict[bytes, list]" = OrderedDict()
        self.resident_bytes = 0
        self.epoch = 0
        self._eager_count = 0
        self.hits = self.misses = self.faults = 0
        self.evictions = self.evicted_bytes = 0
        self.epoch_first_evictions = 0

    def set_limit(self, limit_bytes: int) -> None:
        self.limit_bytes = max(0, int(limit_bytes))
        self._evict_locked()

    def advance_epoch(self, epoch: int) -> None:
        if epoch > self.epoch:
            self.epoch = epoch

    def get(self, key: bytes):
        entry = self._data.get(key)
        if entry is None:
            self.misses += 1
            return None
        entry[2] = self.epoch
        self._data.move_to_end(key)
        self.hits += 1
        return entry[0]

    def put(self, key, node, blob_len=0, *, cold=False, eager=False):
        cost = node_cost(node, blob_len)
        old = self._data.pop(key, None)
        if old is not None:
            self.resident_bytes -= old[1]
            if old[3]:
                self._eager_count -= 1
        epoch = self.epoch - 1 if cold else self.epoch
        self._data[key] = [node, cost, epoch, eager]
        self.resident_bytes += cost
        if eager:
            self._eager_count += 1
        self._evict_locked()

    def get_or_load(self, key, loader, cold=False):
        node = self.get(key)
        if node is None:
            self.faults += 1
            node, blob_len = loader(key)
            self.put(key, node, blob_len, cold=cold)
        return node

    def _evict_locked(self) -> None:
        if self._eager_count > hotcache.EAGER_ENTRY_CAP:
            victims = [k for k, e in self._data.items() if e[3]]
            for key in victims:
                if self._eager_count <= hotcache.EAGER_ENTRY_CAP:
                    break
                _n, cost, _e, _eager = self._data.pop(key)
                self.resident_bytes -= cost
                self._eager_count -= 1
                self.evictions += 1
                self.evicted_bytes += cost
        if self.resident_bytes <= self.limit_bytes:
            return
        cur = self.epoch
        if any(e[2] < cur for e in self._data.values()):
            victims = [k for k, e in self._data.items() if e[2] < cur]
            for key in victims:
                if self.resident_bytes <= self.limit_bytes:
                    return
                _node, cost, _e, eager = self._data.pop(key)
                self.resident_bytes -= cost
                if eager:
                    self._eager_count -= 1
                self.evictions += 1
                self.evicted_bytes += cost
                self.epoch_first_evictions += 1
        while self.resident_bytes > self.limit_bytes and self._data:
            _key, (_node, cost, _e, eager) = self._data.popitem(last=False)
            self.resident_bytes -= cost
            if eager:
                self._eager_count -= 1
            self.evictions += 1
            self.evicted_bytes += cost

    def clear(self) -> None:
        self._data.clear()
        self.resident_bytes = 0
        self._eager_count = 0


# what binds in each regime: (eager cap, byte budget, do epochs move)
REGIMES = {
    "eager_cap": (6, 1 << 30, True),
    "bytes_one_epoch": (1 << 30, 9_000, False),
    "bytes_old_epochs": (1 << 30, 9_000, True),
    "both": (6, 9_000, True),
}
COMPARED = ("resident_bytes", "_eager_count", "evictions", "evicted_bytes",
            "epoch_first_evictions", "hits", "misses", "faults", "epoch")


def _random_ops(rng: random.Random, steps: int, limit: int, epochs: bool):
    """`steps` operations over a key space small enough that `get`s hit
    and `put`s meet live keys (whose eager flag they may flip)."""
    keys = [_tag(f"op{i}") for i in range(40)]
    epoch = 0
    for _ in range(steps):
        key = rng.choice(keys)
        roll = rng.random()
        node = _Leaf(rng.randrange(0, 900)) if rng.random() < 0.5 else object()
        blob_len = rng.randrange(0, 600)
        if roll < 0.45:
            yield "put", (key, node, blob_len), {
                "eager": rng.random() < 0.5,
                "cold": epochs and rng.random() < 0.2}
        elif roll < 0.65:
            yield "get", (key,), {}
        elif roll < 0.85:
            yield "get_or_load", (key, lambda _k, n=node, b=blob_len: (n, b)), {
                "cold": epochs and rng.random() < 0.3}
        elif roll < 0.93:
            if epochs:
                epoch += rng.choice((-1, 0, 1, 1, 2))  # some stand still
                yield "advance_epoch", (epoch,), {}
        elif roll < 0.99:
            yield "set_limit", (rng.choice((limit, limit // 2, 3 * limit)),), {}
        else:
            yield "clear", (), {}


class TestHotNodeCache:
    @pytest.mark.parametrize("seed", range(6))
    @pytest.mark.parametrize("regime", sorted(REGIMES))
    def test_same_victims_as_the_walking_cache(self, monkeypatch, regime, seed):
        cap, limit, epochs = REGIMES[regime]
        monkeypatch.setattr(hotcache, "EAGER_ENTRY_CAP", cap)
        new, oracle = HotNodeCache("t", limit_bytes=limit), WalkingCache(limit)
        rng = random.Random(f"{regime}-{seed}")
        for step, (op, args, kwargs) in enumerate(
                _random_ops(rng, 1500, limit, epochs)):
            got = getattr(new, op)(*args, **kwargs)
            want = getattr(oracle, op)(*args, **kwargs)
            assert got is want, (step, op)
            # the same survivors in the same order, stamps and flags too
            assert [(k, e[1:]) for k, e in new._data.items()] \
                == [(k, e[1:]) for k, e in oracle._data.items()], (step, op)
            for name in COMPARED:
                assert getattr(new, name) == getattr(oracle, name), \
                    (step, op, name)
            # and the bookkeeping says what a walk would find
            assert list(new._eager) == [k for k, e in new._data.items() if e[3]]
            assert new._cur_count == sum(
                e[2] == new.epoch for e in new._data.values())
        assert new.evictions > 100  # the regime did bind
        assert (new.epoch_first_evictions > 0) == (
            regime in ("bytes_old_epochs", "both"))
        if regime == "bytes_one_epoch":
            assert (new.evict_scans, new.evict_scanned) == (0, 0)

    def test_eviction_examines_no_more_than_it_evicts(self):
        # at the shipped cap: a `put` past it examines its one victim,
        # where the walking cache examined the 4,097 entries of the table
        c = HotNodeCache("t", limit_bytes=1 << 30)
        node = object()
        for i in range(20_000):
            c.put(i.to_bytes(32, "big"), node, eager=True)
        assert c.evictions == 20_000 - hotcache.EAGER_ENTRY_CAP
        assert c.evict_scans == c.evictions
        assert c.evict_scanned <= c.evictions + 1
        # a lazy cache at its byte budget with every entry of the current
        # epoch: pass 1 has nothing to look for, and does not look
        lazy = HotNodeCache("t", limit_bytes=100 * 1200)
        lazy.advance_epoch(3)
        for i in range(10_000):
            lazy.put(i.to_bytes(32, "big"), node)
        assert lazy.evictions == 10_000 - 100
        assert (lazy.evict_scans, lazy.evict_scanned) == (0, 0)
        assert lazy.epoch_first_evictions == 0

    def test_byte_bound_evicts_lru(self):
        c = HotNodeCache("t", limit_bytes=10_000)

        class N:
            pass

        for i in range(100):
            c.put(_tag(i), N(), blob_len=0)
        assert c.resident_bytes <= 10_000
        assert c.evictions > 0
        # the most recently inserted keys survive
        assert c.get(_tag(99)) is not None
        assert c.get(_tag(0)) is None

    def test_epoch_entries_evicted_first(self):
        c = HotNodeCache("t", limit_bytes=1_000_000)

        class N:
            pass

        old = [_tag(f"old{i}") for i in range(20)]
        for k in old:
            c.put(k, N())
        c.advance_epoch(5)
        new = [_tag(f"new{i}") for i in range(20)]
        for k in new:
            c.put(k, N())
        # touch one OLD entry under the new epoch: it is promoted
        c.get(old[0])
        c.set_limit(c.resident_bytes - 1)  # force one eviction round
        # victims came from the old epoch, not the serving snapshot's
        assert c.epoch_first_evictions > 0
        assert all(c.get(k) is not None for k in new)
        assert c.get(old[0]) is not None  # promoted by the touch

    def test_get_or_load_single_flight_counters(self):
        c = HotNodeCache("t", limit_bytes=1 << 20)
        calls = {"n": 0}

        def loader(key):
            calls["n"] += 1
            return object(), 100

        k = _tag("x")
        a = c.get_or_load(k, loader)
        b = c.get_or_load(k, loader)
        assert a is b and calls["n"] == 1
        assert c.faults == 1 and c.hits == 1

    def test_eager_entries_capped_by_count(self):
        from stellard_tpu.state import hotcache as hc

        c = HotNodeCache("t", limit_bytes=1 << 30)  # byte bound inert

        class N:
            pass

        cap = 8
        orig = hc.EAGER_ENTRY_CAP
        hc.EAGER_ENTRY_CAP = cap
        try:
            for i in range(3 * cap):
                c.put(_tag(f"e{i}"), N(), eager=True)
            assert c._eager_count == cap
            assert c.evictions == 2 * cap
            # oldest eager entries were the victims; newest survive
            assert c.get(_tag(f"e{3 * cap - 1}")) is not None
            assert c.get(_tag("e0")) is None
            # byte-budget eviction keeps the eager count consistent
            c.set_limit(0)
            assert c._eager_count == 0 and c.resident_bytes == 0
            c.put(_tag("again"), N(), eager=True)
            c.clear()
            assert c._eager_count == 0
        finally:
            hc.EAGER_ENTRY_CAP = orig

    def test_cold_puts_are_first_eviction_victims(self):
        c = HotNodeCache("t", limit_bytes=1 << 20)

        class N:
            pass

        c.advance_epoch(7)
        hot = [_tag(f"hot{i}") for i in range(10)]
        for k in hot:
            c.put(k, N())
        # cold faults (a historical-ledger scan) stamp one epoch BEHIND
        # current, so they lose to the serving snapshot's working set
        # even within one epoch
        cold = [_tag(f"cold{i}") for i in range(10)]
        for k in cold:
            c.put(k, N(), cold=True)
        promoted = cold[0]
        c.get(promoted)  # a hit proves the entry shared: promote it
        c.set_limit(c.resident_bytes - 1)
        assert c.epoch_first_evictions > 0
        assert all(c.get(k) is not None for k in hot)
        assert c.get(promoted) is not None


class TestHistoryShards:
    def _ledger_chain(self, tmp_path, n_ledgers=6, accounts=30):
        """A real mini-chain persisted into a segstore Database:
        returns (db, headers ascending)."""
        from stellard_tpu.nodestore.core import make_database
        from stellard_tpu.state.ledger import Ledger
        from stellard_tpu.protocol.keys import KeyPair

        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           async_writes=False)
        master = KeyPair.from_passphrase("masterpassphrase")
        led = Ledger.genesis(master.account_id)
        headers = []
        for i in range(n_ledgers):
            led.close(close_time=1000 + 30 * i, close_resolution=30)
            led.save(db)
            headers.append({
                "hash": led.hash(), "seq": led.seq,
                "parent_hash": led.parent_hash,
                "account_hash": led.account_hash,
                "tx_hash": led.tx_hash,
            })
            nxt = led.open_successor()
            nxt.write_entry(
                _tag(f"acct-{i}"),
                led.read_entry(
                    __import__("stellard_tpu.state.indexes",
                               fromlist=["indexes"]
                               ).account_root_index(master.account_id)
                ),
            )
            led = nxt
        return db, headers

    def test_rotate_seal_verify_and_serve(self, tmp_path):
        from stellard_tpu.nodestore.shards import (
            SHARD_SEG_BASE,
            CombinedSegmentSource,
            HistoryShardStore,
            rotate_into_shards,
        )
        from stellard_tpu.node.inbound import iter_segment_records

        db, headers = self._ledger_chain(tmp_path)
        ss = HistoryShardStore(str(tmp_path / "shards"))
        retired, retained = headers[:4], headers[4:]
        sid = rotate_into_shards(db, ss, retired, retained)
        assert sid is not None
        # offline verification contract: per-record hashes + crc +
        # header chain, from the file alone
        report = ss.verify(sid)
        assert report["ok"], report
        # the live store really lost the retired-only nodes
        assert db.fetch(retired[0]["hash"]) is None
        assert db.fetch(retained[0]["hash"]) is not None
        # the combined manifest serves the shard over the same door,
        # every record self-verifying through the catch-up iterator
        src = CombinedSegmentSource(db.backend, ss)
        rows = src.segments()
        shard_rows = [r for r in rows if r["id"] >= SHARD_SEG_BASE]
        assert len(shard_rows) == 1
        meta, raw = src.fetch_segment(shard_rows[0]["id"])
        assert meta["size"] == len(raw) > 0
        n = 0
        for key, _tb, blob in iter_segment_records(raw):
            assert sha512_half(blob) == key
            n += 1
        assert n == meta["size"] // 40 or n > 0
        # chunked reads reassemble byte-identically
        out = bytearray()
        while len(out) < meta["size"]:
            _m, chunk = src.fetch_segment(
                shard_rows[0]["id"], offset=len(out), length=97
            )
            out += chunk
        assert bytes(out) == raw
        # the retired headers resolve FROM THE SHARD records (a cold
        # node ingesting them can rebuild the retired range)
        keys = {key for key, _tb, _blob in iter_segment_records(raw)}
        assert retired[0]["hash"] in keys
        db.close()
        ss.close()

    def test_index_survives_reopen(self, tmp_path):
        from stellard_tpu.nodestore.shards import HistoryShardStore, \
            rotate_into_shards

        db, headers = self._ledger_chain(tmp_path)
        ss = HistoryShardStore(str(tmp_path / "shards"))
        rotate_into_shards(db, ss, headers[:3], headers[3:])
        rng = ss.range()
        ss.close()
        ss2 = HistoryShardStore(str(tmp_path / "shards"))
        assert ss2.range() == rng
        assert ss2.verify(ss2.shards()[0]["id"])["ok"]
        db.close()
        ss2.close()

    def test_account_tx_rows_roundtrip(self, tmp_path):
        """Shard-served account_tx rows: the acct index pages in
        (ledger_seq, txn_seq) order with the exclusive marker, and tx
        blobs decode on demand from the shard records."""
        from stellard_tpu.nodestore.core import make_database
        from stellard_tpu.nodestore.shards import HistoryShardStore
        from stellard_tpu.state.ledger import Ledger
        from stellard_tpu.protocol.keys import KeyPair
        from stellard_tpu.protocol.formats import TxType
        from stellard_tpu.protocol.sfields import sfAmount, sfDestination
        from stellard_tpu.protocol.stamount import STAmount
        from stellard_tpu.protocol.sttx import SerializedTransaction

        master = KeyPair.from_passphrase("masterpassphrase")
        dest = KeyPair.from_passphrase("shard-dest").account_id
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           async_writes=False)
        led = Ledger.genesis(master.account_id)
        acct_rows = []
        headers = []
        txids_by_seq: dict[int, list[bytes]] = {}
        for seq_i in range(4):
            led.close(close_time=1000 + 30 * seq_i, close_resolution=30)
            led.save(db)
            headers.append({
                "hash": led.hash(), "seq": led.seq,
                "parent_hash": led.parent_hash,
                "account_hash": led.account_hash,
                "tx_hash": led.tx_hash,
            })
            led = led.open_successor()
            for t in range(2):
                tx = SerializedTransaction.build(
                    TxType.ttPAYMENT, master.account_id,
                    seq_i * 2 + t + 1, 10,
                    {sfAmount: STAmount.from_drops(1000),
                     sfDestination: dest},
                )
                tx.sign(master)
                txid = led.add_transaction(tx.serialize(), b"\x01\x02")
                acct_rows.append(
                    (master.account_id, led.seq, t, txid)
                )
                txids_by_seq.setdefault(led.seq, []).append(txid)
        led.close(close_time=2000, close_resolution=30)
        led.save(db)
        headers.append({
            "hash": led.hash(), "seq": led.seq,
            "parent_hash": led.parent_hash,
            "account_hash": led.account_hash,
            "tx_hash": led.tx_hash,
        })
        ss = HistoryShardStore(str(tmp_path / "shards"))
        from stellard_tpu.nodestore.shards import collect_retired

        def fetch(h):
            o = db.fetch(h, populate_cache=False)
            return o.data if o else None

        records = collect_retired(fetch, headers, set())
        ss.seal(headers[0]["seq"], headers[-1]["seq"], records,
                acct_rows, first_hash=headers[0]["hash"],
                last_hash=headers[-1]["hash"])
        rows = ss.account_tx(master.account_id, 1, 100, limit=100,
                             forward=True)
        assert [r["txid"] for r in rows] == [
            txid for _a, _s, _t, txid in acct_rows
        ]
        for r in rows:
            assert r["raw"] and r["meta"] == b"\x01\x02"
            assert "shard" in r
        # exclusive marker resume, both directions
        after = (rows[2]["ledger_seq"], rows[2]["txn_seq"])
        fwd = ss.account_tx(master.account_id, 1, 100, forward=True,
                            after=after)
        assert [r["txid"] for r in fwd] == [r["txid"] for r in rows[3:]]
        back = ss.account_tx(master.account_id, 1, 100, forward=False,
                             after=after)
        assert [r["txid"] for r in back] == [
            r["txid"] for r in reversed(rows[:2])
        ]
        db.close()
        ss.close()


class TestAccountTxShardRouting:
    def _ctx(self, floor, shard_range, marker=None, min_l=1, max_l=None):
        from types import SimpleNamespace

        from stellard_tpu.protocol.keys import KeyPair
        from stellard_tpu.rpc.handlers import Context, Role

        acct = KeyPair.from_passphrase("masterpassphrase")
        shardstore = SimpleNamespace(
            range=lambda: shard_range,
            account_tx=lambda *a, **k: [],
        )
        txdb = SimpleNamespace(
            retain_floor=floor,
            account_transactions=lambda *a, **k: [],
        )
        node = SimpleNamespace(txdb=txdb, shardstore=shardstore,
                               close_pipeline=None)
        params = {"account": acct.human_account_id,
                  "ledger_index_min": min_l}
        if max_l is not None:
            params["ledger_index_max"] = max_l
        if marker is not None:
            params["marker"] = marker
        return Context(node, params, Role.ADMIN)

    def test_window_below_oldest_shard_fails_cleanly(self):
        """History trimmed BEFORE shards were enabled is gone
        everywhere: a window or marker below the first sealed shard
        must keep the lgrIdxInvalid contract, never a quietly
        complete-looking empty page."""
        from stellard_tpu.rpc.handlers import RPCError, do_account_tx

        # shards cover [5, 9], floor 10: window entirely below shard 5
        with pytest.raises(RPCError):
            do_account_tx(self._ctx(10, (5, 9), min_l=1, max_l=3))
        # marker resuming below the oldest shard
        with pytest.raises(RPCError):
            do_account_tx(self._ctx(10, (5, 9),
                                    marker={"ledger": 2, "seq": 0}))
        # straddling window clamps to the oldest shard and echoes it
        out = do_account_tx(self._ctx(10, (5, 9), min_l=1, max_l=20))
        assert out["ledger_index_min"] == 5

    def test_no_shards_keeps_floor_contract(self):
        from stellard_tpu.rpc.handlers import RPCError, do_account_tx

        with pytest.raises(RPCError):
            do_account_tx(self._ctx(10, None, min_l=1, max_l=3))
        out = do_account_tx(self._ctx(10, None, min_l=1, max_l=20))
        assert out["ledger_index_min"] == 10


class TestNativeScan:
    def test_segrecs_scan_matches_python_iter(self, tmp_path):
        from stellard_tpu.native import load_native, scan_segment_records
        from stellard_tpu.nodestore.shards import (
            _iter_records_py, _pack_records,
        )

        lib = load_native()
        if lib is None or not getattr(lib, "has_segrecs_scan", False):
            pytest.skip("native segrecs_scan unavailable")
        records = []
        for i in range(64):
            blob = b"N" * (i % 7 + 1) + _tag(i)
            records.append((sha512_half(blob), i % 5, blob))
        img = _pack_records(records) + b"\x03torn"
        path = tmp_path / "recs.bin"
        path.write_bytes(img)
        native = scan_segment_records(str(path))
        py = list(_iter_records_py(img))
        assert [(k, t, o, ln) for k, t, o, ln in native] == py
        for (k, _t, off, ln), (_ek, _et, eblob) in zip(native, records):
            assert img[off: off + ln] == eblob