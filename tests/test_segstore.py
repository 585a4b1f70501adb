"""Segmented log-structured NodeStore (nodestore/segstore.py) + the
storage-plane satellites: one-append packed flush, durability modes,
checkpointed open (tail-only replay, pinned record counts), torn-tail
crash recovery, online deletion (mark-and-sweep) with compaction and
the disk-bounded invariant, the segment-granular read door, cpplog
iteration, and sqlite WAL hygiene."""

from __future__ import annotations

import hashlib
import os
import random
import struct
import zlib
from array import array

import numpy as np
import pytest

from stellard_tpu.nodestore import (
    NodeObject,
    NodeObjectType,
    SegStoreBackend,
    make_database,
)
from stellard_tpu.nodestore.segstore import (
    _CKPT_MAGIC,
    _PyIndex,
    _pack_records_py,
    _record_locs,
)
from stellard_tpu.utils.hashes import sha512_half


def _blobs(n, tag="n", size=40):
    """Content-addressed test corpus: prefix-format-looking blobs keyed
    by their real sha512-half (fetch_segment verification depends on
    blob == hashed bytes)."""
    out = []
    for i in range(n):
        blob = b"MIN" + hashlib.sha256(f"{tag}:{i}".encode()).digest() * (
            max(1, size // 32)
        )
        out.append((sha512_half(blob), blob))
    return out


def _flat(pairs):
    buf = bytearray()
    offsets = [0]
    keys = []
    for k, b in pairs:
        keys.append(k)
        buf += b
        offsets.append(len(buf))
    return keys, bytes(buf), offsets


def _store_packed(db, pairs, type=NodeObjectType.ACCOUNT_NODE):
    keys, buf, offsets = _flat(pairs)
    return db.store_packed(type, keys, buf, offsets)


NATIVE_MODES = [False]
try:
    from stellard_tpu.native import load_native

    _lib = load_native()
    if _lib is not None and getattr(_lib, "has_segstore", False):
        NATIVE_MODES.append(True)
except Exception:  # noqa: BLE001
    pass


@pytest.fixture(params=NATIVE_MODES, ids=lambda p: "native" if p else "py")
def use_native(request):
    return request.param


class TestSegStoreBasics:
    def test_packed_roundtrip_and_dedup(self, tmp_path, use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           use_native=use_native)
        pairs = _blobs(300)
        assert _store_packed(db, pairs) == 300
        # content-addressed: a second flush of the same nodes is a no-op
        assert _store_packed(db, pairs) == 0
        for k, b in pairs:
            obj = db.fetch(k)
            assert obj.data == b
            assert obj.type == NodeObjectType.ACCOUNT_NODE
        assert db.fetch(b"\x00" * 32) is None
        assert db.backend.count() == 300
        db.close()

    def test_store_batch_matches_packed(self, tmp_path, use_native):
        """The NodeObject batch door and the flat-buffer door must
        produce byte-identical stores."""
        pairs = _blobs(64)
        db_a = make_database(type="segstore", path=str(tmp_path / "a"),
                             use_native=use_native)
        _store_packed(db_a, pairs)
        db_b = make_database(type="segstore", path=str(tmp_path / "b"),
                             use_native=use_native)
        db_b.backend.store_batch([
            NodeObject(NodeObjectType.ACCOUNT_NODE, k, b) for k, b in pairs
        ])
        for k, b in pairs:
            assert db_a.fetch(k).data == db_b.fetch(k).data == b
        sa = sorted((o.hash, o.data) for o in db_a.backend.iterate())
        sb = sorted((o.hash, o.data) for o in db_b.backend.iterate())
        assert sa == sb
        db_a.close()
        db_b.close()

    def test_in_batch_duplicates_collapse(self, tmp_path, use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           use_native=use_native)
        pairs = _blobs(8)
        doubled = pairs + pairs
        assert _store_packed(db, doubled) == 8
        assert db.backend.count() == 8
        db.close()

    def test_segment_roll_and_fetch_across(self, tmp_path, use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           segment_bytes=1 << 16, use_native=use_native)
        pairs = _blobs(2000, size=64)
        for start in range(0, 2000, 100):
            _store_packed(db, pairs[start:start + 100])
        segs = db.backend.segments()
        assert len(segs) > 1  # rolled at least once
        assert sum(1 for s in segs if s["active"]) == 1
        for k, b in pairs:
            assert db.fetch(k).data == b
        db.close()

    def test_native_py_file_format_parity(self, tmp_path):
        """A store written by the pure-Python paths opens and reads
        under the native paths, and vice versa — one on-disk format."""
        if True not in NATIVE_MODES:
            pytest.skip("native toolchain unavailable")
        pairs = _blobs(200)
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           use_native=False)
        _store_packed(db, pairs)
        db.close()
        db2 = make_database(type="segstore", path=str(tmp_path / "ns"),
                            use_native=True)
        assert db2.backend.count() == 200
        for k, b in pairs:
            assert db2.fetch(k).data == b
        more = _blobs(50, tag="native-side")
        _store_packed(db2, more)
        db2.close()
        db3 = make_database(type="segstore", path=str(tmp_path / "ns"),
                            use_native=False)
        assert db3.backend.count() == 250
        for k, b in pairs + more:
            assert db3.fetch(k).data == b
        db3.close()

    def test_bad_durability_rejected(self, tmp_path):
        with pytest.raises(ValueError):
            SegStoreBackend(str(tmp_path / "ns"), durability="yolo")


class TestDurabilityModes:
    def test_fsync_per_batch(self, tmp_path, use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           durability="fsync", use_native=use_native)
        for chunk in range(4):
            _store_packed(db, _blobs(10, tag=f"c{chunk}"))
        be = db.backend
        assert be.appends == 4
        assert be.fsyncs >= 4  # one per batch (rolls/checkpoints add)
        db.close()

    def test_batch_group_commit_shares_fsyncs(self, tmp_path, use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           durability="batch", group_commit_ms=10_000.0,
                           use_native=use_native)
        for chunk in range(8):
            _store_packed(db, _blobs(10, tag=f"c{chunk}"))
        be = db.backend
        assert be.appends == 8
        assert be.fsyncs == 0  # window far in the future: all deferred
        db.sync()  # the explicit durability barrier forces one
        assert be.fsyncs == 1
        db.close()

    def test_async_defers_to_sync(self, tmp_path, use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           durability="async", use_native=use_native)
        _store_packed(db, _blobs(10))
        assert db.backend.fsyncs == 0
        db.sync()
        assert db.backend.fsyncs == 1
        db.close()


class TestCheckpointedOpen:
    def test_clean_close_reopens_with_zero_replay(self, tmp_path,
                                                  use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           use_native=use_native)
        pairs = _blobs(500)
        _store_packed(db, pairs)
        db.close()  # close writes a checkpoint
        db2 = make_database(type="segstore", path=str(tmp_path / "ns"),
                            use_native=use_native)
        be = db2.backend
        assert be.opened_from_checkpoint
        assert be.replayed_records == 0  # the whole point of the ckpt
        assert be.count() == 500
        for k, b in pairs:
            assert db2.fetch(k).data == b
        db2.close()

    def test_tail_only_replay_counts_pinned(self, tmp_path, use_native):
        """Records appended after the last checkpoint — and ONLY those —
        replay on open."""
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           use_native=use_native)
        _store_packed(db, _blobs(300, tag="covered"))
        db.backend.checkpoint()
        tail = _blobs(37, tag="tail")
        _store_packed(db, tail)
        # crash: no close(), no final checkpoint
        db.backend._active_f.flush()
        db2 = make_database(type="segstore", path=str(tmp_path / "ns"),
                            use_native=use_native)
        be = db2.backend
        assert be.opened_from_checkpoint
        assert be.replayed_records == 37  # the tail, nothing else
        assert be.count() == 337
        for k, b in tail:
            assert db2.fetch(k).data == b
        db2.close()

    def test_corrupt_checkpoint_degrades_to_full_replay(self, tmp_path,
                                                        use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           use_native=use_native)
        pairs = _blobs(120)
        _store_packed(db, pairs)
        db.close()
        ckpt = tmp_path / "ns" / "index.ckpt"
        blob = bytearray(ckpt.read_bytes())
        blob[len(blob) // 2] ^= 0xFF  # flip a byte: crc must catch it
        ckpt.write_bytes(bytes(blob))
        db2 = make_database(type="segstore", path=str(tmp_path / "ns"),
                            use_native=use_native)
        be = db2.backend
        assert not be.opened_from_checkpoint
        assert be.replayed_records == 120  # full scan
        for k, b in pairs:
            assert db2.fetch(k).data == b
        db2.close()

    def test_checkpoint_referencing_missing_segment_discarded(
            self, tmp_path, use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           segment_bytes=1 << 16, use_native=use_native)
        pairs = _blobs(1500, size=64)
        for start in range(0, 1500, 100):
            _store_packed(db, pairs[start:start + 100])
        db.close()
        segs = sorted(
            p for p in os.listdir(tmp_path / "ns") if p.endswith(".seg")
        )
        assert len(segs) > 1
        os.remove(tmp_path / "ns" / segs[0])
        db2 = make_database(type="segstore", path=str(tmp_path / "ns"),
                            use_native=use_native)
        # degraded to a full replay of what remains, not stale index
        # entries pointing at a missing file
        assert not db2.backend.opened_from_checkpoint
        resolvable = sum(1 for k, _ in pairs if db2.fetch(k) is not None)
        assert 0 < resolvable < 1500
        db2.close()


def _needs_native():
    if True not in NATIVE_MODES:
        pytest.skip("native toolchain unavailable")


def _seam_corpus(n, seed):
    """n records of 40-700 byte blobs (a few MB at thousands): packed
    keys, type bytes, one flat buffer and its n+1 offsets."""
    rng = random.Random(seed)
    keys = rng.randbytes(32 * n)
    types = bytes(rng.choice((1, 3, 4)) for _ in range(n))
    offsets = [0]
    for _ in range(n):
        offsets.append(offsets[-1] + rng.randrange(40, 700))
    return keys, types, rng.randbytes(offsets[-1]), offsets


def _slice(keys, buf, offsets, a, b):
    """Records [a, b) of a corpus as their own flat batch."""
    base = offsets[a]
    return (keys[32 * a: 32 * b], buf[base: offsets[b]],
            [o - base for o in offsets[a: b + 1]])


def _loop_locs(sid, base, offsets):
    """The per-record loop the append path once built its locations
    with: the reference `_record_locs` is held to."""
    locs, off = [], base
    for i in range(len(offsets) - 1):
        locs.append((sid << 44) | off)
        off += 37 + 1 + (offsets[i + 1] - offsets[i])
    return locs


def _entries(blob):
    return sorted(blob[i: i + 40] for i in range(0, len(blob), 40))


class TestNativeSeam:
    """Buffers cross the native seam as buffers: pack_records, dump,
    put_batch and the append's locations give exactly what the
    pure-Python mirrors give."""

    @pytest.mark.parametrize("form", ["bytes", "memoryview", "offsets_u64"])
    def test_pack_records_matches_python_mirror(self, form):
        _needs_native()
        from stellard_tpu.native import SegIdxNative

        keys, types, buf, offsets = _seam_corpus(8000, seed=1)
        want = _pack_records_py(keys, types, buf, offsets)
        assert len(want) > 2_000_000
        buf_in, offsets_in = buf, offsets
        if form == "memoryview":
            # a view that starts inside a larger buffer
            buf_in = memoryview(b"\xee" * 100 + buf)[100:]
        elif form == "offsets_u64":
            offsets_in = array("Q", offsets)
        got = SegIdxNative().pack_records(keys, types, buf_in, offsets_in)
        assert type(got) is bytes
        assert got == want

    @pytest.mark.parametrize("fault", ["short_offsets", "past_buffer",
                                       "decreasing", "short_keys"])
    def test_pack_records_refuses_an_inconsistent_batch(self, fault):
        """The C loop copies whatever ranges it is given: a batch whose
        offsets or keys do not fit is refused before the call."""
        _needs_native()
        from stellard_tpu.native import SegIdxNative

        keys, types, buf, offsets = _seam_corpus(50, seed=8)
        if fault == "short_offsets":
            offsets = offsets[:-1]
        elif fault == "past_buffer":
            offsets = offsets[:-1] + [len(buf) + 1]
        elif fault == "decreasing":
            offsets = list(offsets)
            offsets[10], offsets[11] = offsets[11], offsets[10]
        else:
            keys = keys[:-32]
        with pytest.raises(ValueError):
            SegIdxNative().pack_records(keys, types, buf, offsets)

    def test_partly_deduplicated_append_matches_python_store(self, tmp_path):
        """The mask path: a batch of which a third is already stored
        lands the same segment bytes and index under both paths."""
        _needs_native()
        keys, _, buf, offsets = _seam_corpus(6000, seed=2)
        n = len(offsets) - 1
        first = [i for i in range(n) if i % 3 == 0]
        old_keys = b"".join(keys[32 * i: 32 * i + 32] for i in first)
        old_buf = b"".join(buf[offsets[i]: offsets[i + 1]] for i in first)
        old_offsets = [0]
        for i in first:
            old_offsets.append(old_offsets[-1] + offsets[i + 1] - offsets[i])
        rest = [i for i in range(n) if i % 3]
        t = bytes([int(NodeObjectType.ACCOUNT_NODE)])
        want = _pack_records_py(
            old_keys, t * len(first), old_buf, old_offsets
        ) + _pack_records_py(
            b"".join(keys[32 * i: 32 * i + 32] for i in rest), t * len(rest),
            b"".join(buf[offsets[i]: offsets[i + 1]] for i in rest),
            [0] + [sum(offsets[j + 1] - offsets[j] for j in rest[:k + 1])
                   for k in range(len(rest))],
        )
        dumps = []
        for native in (False, True):
            be = SegStoreBackend(str(tmp_path / f"n{native}"),
                                 durability="async", use_native=native)
            assert be.store_packed(NodeObjectType.ACCOUNT_NODE, old_keys,
                                   old_buf, old_offsets) == len(first)
            assert be.store_packed(NodeObjectType.ACCOUNT_NODE, keys, buf,
                                   offsets) == len(rest)
            assert be.dedup_skips == len(first)
            be._active_f.flush()
            seg = tmp_path / f"n{native}" / "seg-00000001.seg"
            assert seg.read_bytes() == want
            dumps.append(_entries(be._idx.dump()))
            for i in range(0, n, 97):
                obj = be.fetch(keys[32 * i: 32 * i + 32])
                assert obj.data == buf[offsets[i]: offsets[i + 1]]
            be.close()
        assert dumps[0] == dumps[1] and len(dumps[0]) == n

    def test_dump_matches_python_index_and_loads_back(self):
        _needs_native()
        from stellard_tpu.native import SegIdxNative

        n = 120_000
        rng = random.Random(3)
        keys = rng.randbytes(32 * n)
        locs = array("Q", (((i % 9) << 44) | (i * 41) for i in range(n)))
        nat, py = SegIdxNative(), _PyIndex()
        nat.put_batch(keys, locs)
        py.put_batch(keys, locs)
        for i in range(0, n, 113):  # tombstones are not dumped
            k = keys[32 * i: 32 * i + 32]
            assert nat.remove(k) and py.remove(k)
        blob = nat.dump()
        assert type(blob) is bytes
        assert len(blob) == 40 * len(py)
        assert _entries(blob) == _entries(py.dump())
        back = SegIdxNative()
        back.load(blob)
        assert len(back) == len(py)
        assert _entries(back.dump()) == _entries(blob)
        for i in range(1, n, 997):
            k = keys[32 * i: 32 * i + 32]
            assert back.get(k) == py.get(k) == (locs[i] if i % 113 else None)

    @pytest.mark.parametrize("form", ["list", "array_q", "numpy"])
    def test_put_batch_takes_any_u64_form(self, form):
        _needs_native()
        from stellard_tpu.native import SegIdxNative

        keys = random.Random(5).randbytes(32 * 3000)
        locs = [(7 << 44) | (i * 600) for i in range(3000)]
        arg = {"list": locs, "array_q": array("Q", locs),
               "numpy": np.array(locs, dtype=np.uint64)}[form]
        idx = SegIdxNative()
        idx.put_batch(keys, arg)
        assert len(idx) == 3000
        for i in range(0, 3000, 7):
            assert idx.get(keys[32 * i: 32 * i + 32]) == locs[i]
        with pytest.raises(ValueError):
            idx.put_batch(keys[:32], [(1 << 64) - 2])

    @pytest.mark.parametrize("sid,base,first", [
        (1, 0, 0), (7, 12_345, 0), (3, 1 << 30, 999),
        ((1 << 19) + 5, 1 << 40, 17),
    ])
    def test_record_locs_equal_the_per_record_loop(self, sid, base, first):
        rng = random.Random(sid)
        offsets = [first]
        for _ in range(5000):
            offsets.append(offsets[-1] + rng.randrange(0, 900))
        want = _loop_locs(sid, base, offsets)
        for form in (offsets, array("Q", offsets),
                     np.array(offsets, dtype=np.uint64)):
            got = _record_locs(sid, base, form)
            assert type(got[0]) is int
            assert list(got) == want

    def test_append_hands_put_batch_the_loop_locations(self, tmp_path,
                                                       use_native):
        be = SegStoreBackend(str(tmp_path / "ns"), durability="async",
                             use_native=use_native)
        seen = []
        put = be._idx.put_batch
        be._idx.put_batch = lambda k, locs: (seen.append(list(locs)),
                                             put(k, locs))
        keys, _, buf, offsets = _seam_corpus(900, seed=6)
        for a in range(0, 900, 300):
            base = be._segs[be._active_id].size
            k, b, o = _slice(keys, buf, offsets, a, a + 300)
            be.store_packed(NodeObjectType.ACCOUNT_NODE, k, b, o)
            assert seen[-1] == _loop_locs(be._active_id, base, o)
        assert len(seen) == 3
        be.close()

    def test_checkpoint_inside_multichunk_append_reopens_without_replay(
            self, tmp_path, use_native):
        """Every chunk crosses the checkpoint mark, so the store
        checkpoints inside its appends; the file is the one-blob format
        [head | stats | entries | crc32] and a crash behind the last
        chunk reopens with nothing to replay."""
        root = tmp_path / "ns"
        be = SegStoreBackend(str(root), checkpoint_bytes=1 << 16,
                             use_native=use_native)
        keys, _, buf, offsets = _seam_corpus(1200, seed=4)
        for a in range(0, 1200, 200):
            k, b, o = _slice(keys, buf, offsets, a, a + 200)
            assert o[-1] > 1 << 16
            be.store_packed(NodeObjectType.ACCOUNT_NODE, k, b, o)
        assert be.checkpoints == 6
        entries = be._idx.dump()
        segs = sorted(be._segs.items())
        body = _CKPT_MAGIC + struct.pack(
            "<IIIQQ", 1, len(segs), be._active_id,
            be._segs[be._active_id].size, len(entries) // 40,
        ) + b"".join(struct.pack("<IQQ", sid, s.size, s.live_bytes)
                     for sid, s in segs) + entries
        assert (root / "index.ckpt").read_bytes() == body + struct.pack(
            "<I", zlib.crc32(body) & 0xFFFFFFFF)
        be._active_f.flush()  # crash: no close(), no final checkpoint
        be2 = SegStoreBackend(str(root), use_native=use_native)
        assert be2.opened_from_checkpoint
        assert be2.replayed_records == 0
        assert be2.count() == 1200
        for i in range(1200):
            obj = be2.fetch(keys[32 * i: 32 * i + 32])
            assert obj.data == buf[offsets[i]: offsets[i + 1]]
        be2.close()


class TestTornTailRecovery:
    def test_torn_tail_truncated_on_reopen(self, tmp_path, use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           use_native=use_native)
        _store_packed(db, _blobs(50, tag="pre-ckpt"))
        db.backend.checkpoint()
        survivors = _blobs(20, tag="post")
        _store_packed(db, survivors)
        db.backend._active_f.flush()
        seg = sorted(
            p for p in os.listdir(tmp_path / "ns") if p.endswith(".seg")
        )[-1]
        path = tmp_path / "ns" / seg
        clean = path.stat().st_size
        # simulated kill mid-append: a header claiming more bytes than
        # exist, plus partial body
        with open(path, "ab") as f:
            f.write(struct.pack("<IB", 500, 0) + b"\xAA" * 40)
        db2 = make_database(type="segstore", path=str(tmp_path / "ns"),
                            use_native=use_native)
        assert path.stat().st_size == clean  # torn record truncated away
        assert db2.backend.replayed_records == 20
        for k, b in survivors:
            assert db2.fetch(k).data == b
        # appends after recovery land on the clean boundary and resolve
        more = _blobs(10, tag="after-recovery")
        _store_packed(db2, more)
        db2.close()
        db3 = make_database(type="segstore", path=str(tmp_path / "ns"),
                            use_native=use_native)
        for k, b in survivors + more:
            assert db3.fetch(k).data == b
        db3.close()

    def test_cpplog_torn_tail_still_recovers(self, tmp_path):
        """cpplog keeps its own torn-tail truncation (test_native pins
        the fine detail); this pins the shared crash-recovery contract
        both durable backends honor: reopen after a torn append resolves
        every previously-synced record."""
        try:
            db = make_database(type="cpplog",
                               path=str(tmp_path / "ns.cpplog"))
        except (RuntimeError, OSError):
            pytest.skip("native toolchain unavailable")
        pairs = _blobs(30)
        db.backend.store_batch([
            NodeObject(NodeObjectType.ACCOUNT_NODE, k, b) for k, b in pairs
        ])
        db.close()
        with open(tmp_path / "ns.cpplog", "ab") as f:
            f.write(struct.pack("<IB", 999, 0) + b"\xBB" * 21)
        db2 = make_database(type="cpplog", path=str(tmp_path / "ns.cpplog"))
        for k, b in pairs:
            assert db2.fetch(k).data == b
        db2.close()


class TestOnlineDeletion:
    def test_sweep_removes_only_dead(self, tmp_path, use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           use_native=use_native)
        keep = _blobs(40, tag="keep")
        dead = _blobs(60, tag="dead")
        _store_packed(db, keep + dead)
        db.begin_sweep()
        removed = db.apply_sweep({k for k, _ in keep})
        assert removed == 60
        for k, b in keep:
            assert db.fetch(k).data == b
        for k, _ in dead:
            assert db.fetch(k) is None
        db.close()

    def test_sweep_purges_flushed_known_set(self, tmp_path, use_native):
        """The façade's `flushed` set must forget swept keys, or a later
        flush would skip re-writing a node a new ledger re-created."""
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           use_native=use_native)
        pairs = _blobs(10)
        _store_packed(db, pairs)
        db.flushed.update(k for k, _ in pairs)
        db.begin_sweep()
        db.apply_sweep(set())
        assert not (db.flushed & {k for k, _ in pairs})
        # re-stored after the sweep: resolvable again
        assert _store_packed(db, pairs) == 10
        for k, b in pairs:
            assert db.fetch(k).data == b
        db.close()

    def test_mid_sweep_append_survives(self, tmp_path, use_native):
        """A key appended between begin_sweep and apply_sweep must
        survive even when the mark never saw it (recent-key guard +
        compare-and-delete)."""
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           use_native=use_native)
        old = _blobs(20, tag="old")
        _store_packed(db, old)
        db.begin_sweep()
        racing = _blobs(5, tag="racing")
        _store_packed(db, racing)
        # re-append of an existing (dead-listed) key mid-sweep: the
        # fresh record's loc differs from the sweep snapshot's
        _store_packed(db, old[:3])
        removed = db.apply_sweep(set())  # mark saw nothing live
        assert removed == 17  # 20 old minus the 3 re-appended
        for k, b in racing + old[:3]:
            assert db.fetch(k).data == b
        db.close()

    def test_sweep_durable_across_reopen(self, tmp_path, use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           use_native=use_native)
        keep = _blobs(15, tag="keep")
        dead = _blobs(15, tag="dead")
        _store_packed(db, keep + dead)
        db.begin_sweep()
        db.apply_sweep({k for k, _ in keep})
        db.close()
        db2 = make_database(type="segstore", path=str(tmp_path / "ns"),
                            use_native=use_native)
        assert db2.backend.count() == 15
        for k, _ in dead:
            assert db2.fetch(k) is None
        for k, b in keep:
            assert db2.fetch(k).data == b
        db2.close()


class TestCompaction:
    def test_live_ratio_triggers_rewrite(self, tmp_path, use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           segment_bytes=1 << 14, compact_ratio=0.5,
                           use_native=use_native)
        keep = _blobs(30, tag="keep", size=64)
        dead = _blobs(300, tag="dead", size=64)
        for start in range(0, 300, 30):
            _store_packed(db, dead[start:start + 30])
        _store_packed(db, keep)
        be = db.backend
        segs_before = len(be.segments())
        disk_before = be.disk_bytes()
        db.begin_sweep()
        db.apply_sweep({k for k, _ in keep})
        be.compact()
        assert be.compactions >= 1
        assert be.disk_bytes() < disk_before
        # disk bounded within 2x the live set after compaction
        assert be.disk_bytes() <= 2 * be.live_bytes() + (1 << 12)
        assert len(be.segments()) <= segs_before
        for k, b in keep:
            assert db.fetch(k).data == b
        assert be.count() == 30
        db.close()
        # and the compacted store reopens intact
        db2 = make_database(type="segstore", path=str(tmp_path / "ns"),
                            use_native=use_native)
        for k, b in keep:
            assert db2.fetch(k).data == b
        db2.close()

    def test_compaction_preserves_byte_identity(self, tmp_path,
                                                use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           segment_bytes=1 << 13, use_native=use_native)
        pairs = _blobs(200, size=48)
        for start in range(0, 200, 20):
            _store_packed(db, pairs[start:start + 20])
        db.begin_sweep()
        db.apply_sweep({k for k, _ in pairs[::2]})  # half dead
        db.backend.compact()
        for k, b in pairs[::2]:
            obj = db.fetch(k)
            assert obj.data == b
            assert sha512_half(obj.data) == k  # moved bytes re-verify
        db.close()


class TestSegmentReadDoor:
    def test_fetch_segment_serves_verifiable_ranges(self, tmp_path,
                                                    use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           segment_bytes=1 << 14, use_native=use_native)
        pairs = _blobs(300, size=64)
        for start in range(0, 300, 30):
            _store_packed(db, pairs[start:start + 30])
        be = db.backend
        want = dict(pairs)
        seen = 0
        for meta in be.segments():
            got = be.fetch_segment(meta["id"])
            assert got is not None
            m, raw = got
            assert len(raw) == m["size"]
            # every record in the raw range parses and its blob hashes
            # to its key — a catch-up receiver can verify offline
            off = 0
            while off + 37 <= len(raw):
                body_len = struct.unpack_from("<I", raw, off)[0]
                assert off + 37 + body_len <= len(raw)
                key = raw[off + 5: off + 37]
                blob = raw[off + 38: off + 37 + body_len]
                assert sha512_half(blob) == key
                assert want[key] == blob
                seen += 1
                off += 37 + body_len
        assert seen == 300
        assert be.fetch_segment(999999) is None
        db.close()

    def test_fetch_segment_offset_length_edges(self, tmp_path,
                                               use_native):
        """Chunked-transfer edge cases: zero-length reads, offsets at
        and past the end, and a length spanning the end — meta must
        always carry the FULL size, data exactly the clamped range."""
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           use_native=use_native)
        _store_packed(db, _blobs(40, size=64))
        be = db.backend
        sid = be.segments()[0]["id"]
        meta, full = be.fetch_segment(sid)
        size = meta["size"]
        assert size == len(full) > 0
        # zero-length read: empty data, full size in meta
        m, data = be.fetch_segment(sid, offset=0, length=0)
        assert data == b"" and m["size"] == size
        # offset exactly at end: empty, not an error
        m, data = be.fetch_segment(sid, offset=size, length=1 << 20)
        assert data == b"" and m["size"] == size
        # offset PAST the end (a hostile/raced chunk request): empty
        m, data = be.fetch_segment(sid, offset=size + 1000, length=64)
        assert data == b"" and m["size"] == size
        # negative offset clamps to 0
        m, data = be.fetch_segment(sid, offset=-5, length=10)
        assert data == full[:10]
        # length spanning past the end clamps to the tail
        m, data = be.fetch_segment(sid, offset=size - 7, length=1 << 20)
        assert data == full[-7:]
        # chunked reassembly reproduces the segment byte-for-byte
        out = bytearray()
        while len(out) < size:
            _m, chunk = be.fetch_segment(sid, offset=len(out), length=13)
            assert chunk
            out += chunk
        assert bytes(out) == full
        db.close()

    def test_fetch_segment_spanning_seal_boundary(self, tmp_path,
                                                  use_native):
        """A reader paging one segment while appends roll into the NEXT
        must see a stable byte range: sealed segments never change, and
        every record in any chunk still verifies."""
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           segment_bytes=1 << 16, use_native=use_native)
        pairs = _blobs(600, size=96)
        for start in range(0, 600, 50):
            _store_packed(db, pairs[start:start + 50])
        be = db.backend
        metas = be.segments()
        assert len(metas) >= 2, "workload must span a seal boundary"
        sealed = [m for m in metas if not m["active"]][0]
        m1, first = be.fetch_segment(sealed["id"])
        # a request whose length crosses the sealed segment's end is
        # clamped at the seal — bytes never bleed into the next segment
        m2, clamped = be.fetch_segment(sealed["id"], offset=0,
                                       length=m1["size"] + 4096)
        assert clamped == first
        # appending more (rolls may happen) never mutates a sealed range
        _store_packed(db, _blobs(100, tag="later", size=96))
        _m, again = be.fetch_segment(sealed["id"])
        assert again == first
        db.close()

    def test_fetch_segment_concurrent_with_compaction(self, tmp_path,
                                                      use_native):
        """Readers chunk-paging a segment while compaction rewrites and
        DELETES it must either get a valid chunk or a clean None (the
        manifest row is gone) — never a torn read or a crash."""
        import threading

        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           segment_bytes=1 << 16, use_native=use_native)
        pairs = _blobs(800, size=128)
        for start in range(0, 800, 40):
            _store_packed(db, pairs[start:start + 40])
        be = db.backend
        sealed = [m for m in be.segments() if not m["active"]]
        assert sealed
        target = sealed[0]["id"]
        # kill most of the sealed segments' liveness so compaction
        # rewrites them
        live_keys = {k for k, _ in pairs[:40]}
        db.begin_sweep()
        db.apply_sweep(live_keys)
        errors: list[BaseException] = []
        stop = threading.Event()

        def reader():
            try:
                while not stop.is_set():
                    got = be.fetch_segment(target, offset=0, length=512)
                    if got is None:
                        continue  # compacted away: clean miss
                    meta, data = got
                    assert len(data) <= 512
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=reader) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            be.compact()
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
        assert not errors, errors
        # every LIVE node still fetches byte-identically post-compaction
        for k, blob in pairs[:40]:
            obj = db.fetch(k)
            assert obj is not None and obj.data == blob
        db.close()


class TestCppLogIterate:
    def test_iterate_returns_every_record(self, tmp_path):
        try:
            db = make_database(type="cpplog",
                               path=str(tmp_path / "it.cpplog"))
        except (RuntimeError, OSError):
            pytest.skip("native toolchain unavailable")
        pairs = _blobs(40)
        db.backend.store_batch([
            NodeObject(NodeObjectType.ACCOUNT_NODE, k, b) for k, b in pairs
        ])
        got = sorted((o.hash, int(o.type), o.data)
                     for o in db.backend.iterate())
        want = sorted((k, int(NodeObjectType.ACCOUNT_NODE), b)
                      for k, b in pairs)
        assert got == want
        db.close()

    def test_iterate_python_fallback_scan(self, tmp_path):
        """The file-scan fallback (stale native library without the
        iterate symbol) must return the same records."""
        try:
            db = make_database(type="cpplog",
                               path=str(tmp_path / "it2.cpplog"))
        except (RuntimeError, OSError):
            pytest.skip("native toolchain unavailable")
        pairs = _blobs(25)
        db.backend.store_batch([
            NodeObject(NodeObjectType.ACCOUNT_NODE, k, b) for k, b in pairs
        ])
        got = sorted((k, t, b) for k, t, b in db.backend._scan_log())
        want = sorted((k, int(NodeObjectType.ACCOUNT_NODE), b)
                      for k, b in pairs)
        assert got == want
        db.close()

    def test_iterate_roundtrips_compressed_records(self, tmp_path):
        try:
            db = make_database(type="cpplog",
                               path=str(tmp_path / "itz.cpplog"),
                               compression="zlib")
        except (RuntimeError, OSError):
            pytest.skip("native toolchain unavailable")
        # highly compressible blobs so the zlib flag actually fires
        pairs = [(sha512_half(b"Z" * (100 + i)), b"Z" * (100 + i))
                 for i in range(10)]
        db.backend.store_batch([
            NodeObject(NodeObjectType.ACCOUNT_NODE, k, b) for k, b in pairs
        ])
        got = sorted((o.hash, o.data) for o in db.backend.iterate())
        assert got == sorted(pairs)
        db.close()


class TestSqliteWalHygiene:
    def test_wal_stays_bounded_under_flood(self, tmp_path):
        path = str(tmp_path / "nodes.sqlite")
        db = make_database(type="sqlite", path=path)
        db.backend.WAL_CHECKPOINT_BYTES = 1 << 16  # test-scale threshold
        for chunk in range(40):
            pairs = _blobs(50, tag=f"wal{chunk}", size=96)
            db.backend.store_batch([
                NodeObject(NodeObjectType.ACCOUNT_NODE, k, b)
                for k, b in pairs
            ])
        assert db.backend.wal_checkpoints >= 1
        wal = os.path.getsize(path + "-wal")
        # bounded: far below the ~400KB written; TRUNCATE resets to a
        # small tail (the post-checkpoint commits)
        assert wal < 2 * db.backend.WAL_CHECKPOINT_BYTES, wal
        db.close()

    def test_synchronous_passthrough_and_validation(self, tmp_path):
        db = make_database(type="sqlite",
                           path=str(tmp_path / "s.sqlite"),
                           synchronous="off")
        level = db.backend._conn.execute("PRAGMA synchronous").fetchone()[0]
        assert level == 0  # OFF
        db.close()
        with pytest.raises(ValueError):
            make_database(type="sqlite",
                          path=str(tmp_path / "s2.sqlite"),
                          synchronous="everything")


class TestDatabaseFacade:
    def test_store_packed_falls_back_for_plain_backends(self):
        db = make_database(type="memory")
        pairs = _blobs(20)
        assert _store_packed(db, pairs) == 20
        for k, b in pairs:
            assert db.fetch(k).data == b

    def test_get_json_shape(self, tmp_path, use_native):
        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           use_native=use_native)
        _store_packed(db, _blobs(10))
        db.fetch(_blobs(10)[0][0])
        db.fetch(b"\x01" * 32)
        j = db.get_json()
        assert j["backend"] == "segstore"
        assert j["backend_fetches"] >= 1
        assert j["backend_misses"] >= 1
        bs = j["backend_stats"]
        for field in ("appends", "records", "bytes_appended", "fsyncs",
                      "segments", "disk_bytes", "live_bytes",
                      "live_ratio", "checkpoints", "compactions",
                      "sweeps", "replayed_records", "durability"):
            assert field in bs, field
        db.close()

    def test_sweep_unsupported_backend_raises(self):
        db = make_database(type="memory")
        with pytest.raises(NotImplementedError):
            db.begin_sweep()
        with pytest.raises(NotImplementedError):
            db.apply_sweep(set())


class TestLedgerThroughSegstore:
    def test_ledger_save_load_roundtrip(self, tmp_path, use_native):
        from stellard_tpu.protocol.keys import KeyPair
        from stellard_tpu.state.ledger import Ledger

        db = make_database(type="segstore", path=str(tmp_path / "ns"),
                           use_native=use_native)
        genesis = Ledger.genesis(
            KeyPair.from_passphrase("masterpassphrase").account_id
        )
        h = genesis.save(db)
        db.sync()
        loaded = Ledger.load(db, h)
        assert loaded.hash() == h
        assert loaded.state_map.get_hash() == genesis.state_map.get_hash()
        # delta-only on re-save: the known-set short-circuits everything
        before = db.backend.records
        genesis.save(db)
        assert db.backend.records == before
        db.close()

    def test_flush_packed_matches_store_many(self, tmp_path, use_native):
        """SHAMap.flush through the packed door lands byte-identical
        nodes to the store_many door (the pre-PR path)."""
        import hashlib as _h

        from stellard_tpu.state.shamap import SHAMap, SHAMapItem, TNType

        m = SHAMap(TNType.ACCOUNT_STATE)
        for i in range(200):
            tag = _h.sha256(f"flush:{i}".encode()).digest()
            m.set_item(SHAMapItem(tag, _h.sha512(tag).digest()))
        db_p = make_database(type="segstore", path=str(tmp_path / "p"),
                             use_native=use_native)
        n_p = m.flush(
            db_p.store_fn(NodeObjectType.ACCOUNT_NODE), set(),
            store_packed=db_p.store_packed_fn(NodeObjectType.ACCOUNT_NODE),
        )
        db_m = make_database(type="memory")
        n_m = m.flush(
            db_m.store_fn(NodeObjectType.ACCOUNT_NODE), set(),
            store_many=db_m.store_many_fn(NodeObjectType.ACCOUNT_NODE),
        )
        assert n_p == n_m
        db_m.sync()
        for obj in db_m.backend.iterate():
            got = db_p.fetch(obj.hash)
            assert got is not None and got.data == obj.data
        db_p.close()


class TestNodeDbConfig:
    def test_node_db_stanza_parses(self):
        from stellard_tpu.node.config import Config

        cfg = Config.from_ini(
            "[node_db]\n"
            "type=segstore\n"
            "path=/tmp/x\n"
            "durability=batch\n"
            "group_commit_ms=12.5\n"
            "segment_mb=8\n"
            "checkpoint_mb=4\n"
            "compact_ratio=0.25\n"
            "online_delete=256\n"
            "online_delete_interval=64\n"
        )
        assert cfg.node_db_type == "segstore"
        assert cfg.node_db_durability == "batch"
        assert cfg.node_db_group_commit_ms == 12.5
        assert cfg.node_db_segment_mb == 8
        assert cfg.node_db_checkpoint_mb == 4
        assert cfg.node_db_compact_ratio == 0.25
        assert cfg.node_db_online_delete == 256
        assert cfg.node_db_online_delete_interval == 64

    def test_bad_durability_rejected(self):
        from stellard_tpu.node.config import Config

        with pytest.raises(ValueError):
            Config.from_ini("[node_db]\ntype=segstore\ndurability=fast\n")

    def test_online_delete_requires_liveness_backend(self, tmp_path):
        from stellard_tpu.node.config import Config
        from stellard_tpu.node.node import Node

        with pytest.raises(ValueError):
            Node(Config(node_db_type="memory", node_db_online_delete=8))


class TestNodeOnSegstore:
    def test_flood_with_online_deletion_bounded_and_resolvable(
            self, tmp_path):
        """End-to-end: a standalone node on segstore floods payments
        with online deletion on; retained ledgers stay fully
        resolvable, early history is swept, disk stays within 2x the
        live set."""
        import threading

        from stellard_tpu.node.config import Config
        from stellard_tpu.node.node import Node
        from stellard_tpu.protocol.formats import TxType
        from stellard_tpu.protocol.keys import KeyPair
        from stellard_tpu.protocol.sfields import sfAmount, sfDestination
        from stellard_tpu.protocol.stamount import STAmount
        from stellard_tpu.protocol.sttx import SerializedTransaction
        from stellard_tpu.state.ledger import Ledger

        node = Node(Config(
            node_db_type="segstore",
            node_db_path=str(tmp_path / "nodestore"),
            node_db_online_delete=3,
            node_db_online_delete_interval=2,
            node_db_segment_mb=1,
            database_path=str(tmp_path / "stellard.db"),
        )).setup()
        try:
            master = KeyPair.from_passphrase("masterpassphrase")
            dests = [KeyPair.from_passphrase(f"od-{i}").account_id
                     for i in range(4)]
            done = threading.Semaphore(0)

            def cb(tx, ter, applied):
                done.release()

            seq = 1
            for _close in range(8):
                txs = []
                for i in range(20):
                    tx = SerializedTransaction.build(
                        TxType.ttPAYMENT, master.account_id, seq, 10,
                        {sfAmount: STAmount.from_drops(250_000_000),
                         sfDestination: dests[i % len(dests)]},
                    )
                    tx.sign(master)
                    txs.append(tx)
                    seq += 1
                for tx in txs:
                    node.ops.submit_transaction(tx, cb)
                for _ in txs:
                    done.acquire()
                node.close_ledger()
            deadline = 30.0
            import time as _t

            while node.online_deleter.get_json()["sweeps_completed"] < 1 \
                    and deadline > 0:
                _t.sleep(0.1)
                deadline -= 0.1
            node.close_pipeline.flush(timeout=30)
            od = node.online_deleter.get_json()
            assert od["sweeps_completed"] >= 1, od
            lcl = node.ledger_master.closed_ledger()
            lo = od["last_retain_floor"]
            resolved = 0
            for s in range(lo, lcl.seq + 1):
                hdr = node.txdb.get_ledger_header(seq=s)
                if hdr is None:
                    continue
                led = Ledger.load(node.nodestore, hdr["hash"])
                assert led.hash() == hdr["hash"]
                resolved += 1
            assert resolved >= 2
            # early history swept: the first post-genesis close's full
            # tree is gone from the store. Its txdb header may ALSO be
            # gone now — SQL rows rotate with the same horizon
            # ([node_db] sql_trim, default on)
            hdr1 = node.txdb.get_ledger_header(seq=2)
            if hdr1 is not None:
                with pytest.raises(KeyError):
                    Ledger.load(node.nodestore, hdr1["hash"])
            # the SQL mirror is bounded by the retention window, not the
            # whole run: rows below the retain floor were deleted on the
            # drain worker (ISSUE 9 satellite — disk-bound pin)
            assert od["sql_trim"] and od["sql_rows_trimmed"] > 0, od
            rows = node.txdb.counts()
            window = lcl.seq - lo + 1
            assert rows["ledgers"] <= window + 1, (rows, lo, lcl.seq)
            assert rows["transactions"] <= 20 * (window + 1), rows
            assert rows["account_transactions"] <= 2 * 20 * (window + 1)
            bs = node.nodestore.get_json()["backend_stats"]
            assert bs["disk_bytes"] <= 2 * max(bs["live_bytes"], 1) \
                + (1 << 16), bs
            # observability: the node_store block rides get_counts
            from stellard_tpu.rpc.handlers import Context, Role, dispatch

            counts = dispatch(
                Context(node, {}, Role.ADMIN), "get_counts"
            )
            assert counts["node_store"]["backend"] == "segstore"
            assert counts["node_store"]["online_delete"][
                "sweeps_completed"] >= 1
        finally:
            node.stop()

class TestSqlTrim:
    """TxDatabase.trim_below: the SQL half of online deletion."""

    def _db_with_history(self, n_ledgers=6, txs_per=3):
        from stellard_tpu.node.txdb import TxDatabase

        db = TxDatabase()

        class _L:
            def __init__(self, seq):
                self.seq = seq
                self.parent_hash = bytes([seq - 1]) * 32
                self.tot_coins = 0
                self.close_time = seq * 10
                self.parent_close_time = (seq - 1) * 10
                self.close_resolution = 10
                self.close_flags = 0
                self.account_hash = bytes([seq]) * 32
                self.tx_hash = bytes([seq]) * 32

            def hash(self):
                return bytes([self.seq]) * 32

        for seq in range(1, n_ledgers + 1):
            led = _L(seq)
            rows = []
            for i in range(txs_per):
                txid = bytes([seq, i]) + bytes(30)
                rows.append((
                    txid, "Payment", bytes([i]) * 20, i + 1, seq,
                    "tesSUCCESS", b"raw", b"meta",
                    [bytes([i]) * 20, bytes([i + 1]) * 20], i,
                ))
            db.save_ledger(led, rows)
            db.save_validation(led.hash(), b"\x07" * 32, seq * 10, b"v")
        return db

    def test_trim_below_deletes_history_keeps_window(self):
        db = self._db_with_history(n_ledgers=6, txs_per=3)
        before = db.counts()
        assert before == {
            "transactions": 18, "account_transactions": 36, "ledgers": 6,
        }
        deleted = db.trim_below(4)
        assert deleted["ledgers"] == 3
        assert deleted["transactions"] == 9
        assert deleted["account_transactions"] == 18
        assert deleted["validations"] == 3
        after = db.counts()
        assert after == {
            "transactions": 9, "account_transactions": 18, "ledgers": 3,
        }
        # the retained window is untouched and fully queryable
        assert db.get_ledger_header(seq=3) is None
        assert db.get_ledger_header(seq=4) is not None
        assert db.get_transaction(bytes([4, 0]) + bytes(30)) is not None
        assert db.get_transaction(bytes([3, 0]) + bytes(30)) is None
        # idempotent: a second trim at the same horizon is a no-op
        assert sum(db.trim_below(4).values()) == 0
        db.close()

    def test_account_tx_walk_survives_trim(self):
        db = self._db_with_history(n_ledgers=6, txs_per=3)
        db.trim_below(4)
        rows = db.account_transactions(bytes([0]) * 20)
        assert rows and all(r["ledger_seq"] >= 4 for r in rows)
        db.close()
