"""An archive's cold start (ISSUE 36): the offline contract of a history
shard through the hash plane, the import off the link's reader thread,
the spans and counters of an import, and the cell ``catchup.deep``'s
own files at the rehearsal's toy size.

The plain reference for the verdicts is the ``hashlib`` loop
(``verify_shard_blob`` with no hasher); for the rows it is the writer's
own record of what it sealed.
"""

from __future__ import annotations

import importlib.util
import os
import struct
import sys
import threading
import time
import types
import zlib

import jax
import pytest

jax.config.update("jax_platforms", "cpu")

REPO = os.path.join(os.path.dirname(__file__), "..")
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(__file__))

from test_archive import _file_blob, _sealed_chain  # noqa: E402
from yardstick import manifest, nodedrive, readers  # noqa: E402

from stellard_tpu.crypto.backend import (  # noqa: E402
    CpuHasher, TpuHasher, WatchdogHasher,
)
from stellard_tpu.node.archive import (  # noqa: E402
    ArchiveTxDatabase, ShardBackfill, feed_shard,
)
from stellard_tpu.node.config import Config  # noqa: E402
from stellard_tpu.node.node import Node  # noqa: E402
from stellard_tpu.node.tracer import Tracer  # noqa: E402
from stellard_tpu.nodestore import shards as shards_mod  # noqa: E402
from stellard_tpu.nodestore.shards import (  # noqa: E402
    HistoryShardStore, VerifyStats, verify_shard_blob,
)
from stellard_tpu.overlay.wire import SegmentData  # noqa: E402
from stellard_tpu.utils.hashes import sha512_half  # noqa: E402

N_RANDOM = 300  # random node records beside the chain's own


# --------------------------------------------------------------------------
# seeded shard images, clean and broken


def refix_crc(image: bytearray) -> bytes:
    image[-4:] = struct.pack("<I", zlib.crc32(bytes(image[:-4])) & 0xFFFFFFFF)
    return bytes(image)


def record_spans(image: bytes) -> list[tuple[int, int]]:
    """(offset of a record's header, its body length) per record."""
    rec_off, rec_len = struct.unpack_from("<QQ", image, 8 + 12)
    out, off = [], rec_off
    while off + 37 <= rec_off + rec_len:
        (body_len,) = struct.unpack_from("<I", image, off)
        out.append((off, body_len))
        off += 37 + body_len
    return out


@pytest.fixture(scope="module")
def images(tmp_path_factory):
    """-> {kind: image} over one sealed chain of 6 ledgers with payments
    and ``N_RANDOM`` seeded random node records of one to five SHA-512
    blocks; ``oversized`` carries one record past the device ladder's
    largest block count."""
    import random

    tmp = tmp_path_factory.mktemp("images")
    env = _sealed_chain(tmp, splits=((1, 6),), txs_per_ledger=3)
    src = env["ss"]
    rng = random.Random(36)
    base = list(src.iter_records(env["sids"][0]))

    def noise(n_bytes):
        blob = b"MIN\x00" + rng.randbytes(n_bytes)
        return (sha512_half(blob), 3, blob)

    extra = [noise(rng.choice((60, 200, 512, 600))) for _ in range(N_RANDOM)]
    headers = env["headers"]

    def seal(records, name):
        ss = HistoryShardStore(str(tmp / name))
        sid = ss.seal(1, 6, records, env["acct_rows"],
                      first_hash=headers[0]["hash"],
                      last_hash=headers[-1]["hash"])
        image = _file_blob(ss, sid)
        ss.close()
        return image

    clean = seal(base + extra, "clean")
    out = {"clean": clean}
    # one record byte flipped under a CRC made good again: only the
    # content hash can catch it
    flipped = bytearray(clean)
    off, body_len = record_spans(clean)[len(base) + 7]
    flipped[off + 38 + body_len // 2] ^= 0x40
    out["flipped"] = refix_crc(flipped)
    # the header of ledger 3 left out: every record verifies, the chain
    # does not
    drop = headers[2]["hash"]
    out["broken_chain"] = seal(
        [r for r in base if r[0] != drop] + extra, "chain")
    # the last record claims more bytes than the section holds
    cut = bytearray(clean)
    off, body_len = record_spans(clean)[-1]
    struct.pack_into("<I", cut, off, body_len + 64)
    out["truncated"] = refix_crc(cut)
    # one record of 17 blocks: past LEAF_BLOCK_LADDER, the host takes it
    out["oversized"] = seal(base + extra + [noise(2100)], "oversized")
    src.close()
    return out


def routed_hasher():
    # the node's own wiring of a device hasher, floor at the default
    return WatchdogHasher(TpuHasher(mesh="0"), CpuHasher(),
                          min_device_nodes=64).flat_hasher()


ARMS = {
    "plain": lambda: None,
    "routed": routed_hasher,
    "device": lambda: TpuHasher(mesh="0"),
}
KINDS = ("clean", "flipped", "broken_chain", "truncated", "oversized")


@pytest.fixture(scope="module")
def arms():
    return {name: make() for name, make in ARMS.items()}


class TestContractThroughTheHashPlane:
    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("arm", ["routed", "device"])
    def test_every_arm_reports_as_the_plain_loop(self, images, arms, arm,
                                                 kind):
        want = verify_shard_blob(images[kind])
        stats = VerifyStats()
        got = verify_shard_blob(images[kind], arms[arm], None, stats)
        assert got == want
        assert got["ok"] is (kind in ("clean", "oversized"))
        sv = stats.get_json()
        if kind == "truncated":
            # the scan stops at the torn record: everything before it
            # is still checked
            assert sv["records"] == want["records"] < N_RANDOM + 30
        else:
            assert sv["records"] == want["records"] >= N_RANDOM
        assert sv["bad_records"] == want["bad_records"] == (
            1 if kind == "flipped" else 0)
        assert sv["device_records"] + sv["host_records"] == sv["records"]
        if arm == "device":
            # the device arm itself: every record on it but the one the
            # ladder cannot hold
            assert sv["host_records"] <= (1 if kind == "oversized" else 0)

    def test_what_each_broken_image_fails_by(self, images):
        reports = {k: verify_shard_blob(images[k]) for k in KINDS}
        assert reports["flipped"]["header_chain_ok"]
        assert reports["flipped"]["error"] == "content verification failed"
        assert not reports["broken_chain"]["header_chain_ok"]
        assert reports["broken_chain"]["bad_records"] == 0
        assert reports["truncated"]["bad_records"] == 0
        assert reports["truncated"]["header_chain_ok"]
        assert "rec_off" not in reports["truncated"]

    def test_a_short_or_wrong_verdict_is_a_bad_record(self, images):
        class Lying(CpuHasher):
            def hash_packed(self, buf, offsets):
                out = super().hash_packed(buf, offsets)
                out[3] = out[3][:31]  # not 32 bytes
                out[5] = b"\x00" * 32  # 32 bytes, not the key
                return out[:-1]  # and one verdict missing

        got = verify_shard_blob(images["clean"], Lying())
        assert not got["ok"] and got["bad_records"] == 3

    def test_slabs_are_near_equal_and_bounded(self, monkeypatch):
        monkeypatch.setattr(shards_mod, "HASH_SLAB_RECORDS", 100)
        for n in (0, 1, 99, 100, 101, 250, 1000, 1001):
            slabs = shards_mod._slabs(n)
            sizes = [hi - lo for lo, hi in slabs]
            assert sum(sizes) == n and max(sizes, default=0) <= 100
            assert slabs[0][0] == 0 and slabs[-1][1] == n
            assert max(sizes) - min(sizes) <= 1
            # never under half the bound: the router's power-of-two
            # bucket of the bound, whatever the shard's size
            if n > 100:
                assert min(sizes) >= 50


class TestBothDoors:
    """``verify_shard_blob`` (raw bytes) and ``HistoryShardStore.verify``
    (a held shard) run ONE copy of the contract."""

    @pytest.fixture
    def held(self, tmp_path, images):
        def make(kind):
            ss = HistoryShardStore(str(tmp_path / kind))
            res = ss.import_shard(images["clean"])
            assert res["ok"]
            path = os.path.join(ss.root, f"shard-{res['id']:06d}.shard")
            with open(path, "wb") as fh:
                fh.write(images[kind])
            return ss, res["id"]
        return make

    @pytest.mark.parametrize("door", ["blob", "store"])
    @pytest.mark.parametrize("kind", KINDS)
    def test_the_two_doors_agree(self, images, held, door, kind):
        want_ok = kind == "clean"  # the store's index row is the clean one's
        if door == "blob":
            report = verify_shard_blob(images[kind])
            want_ok = kind in ("clean", "oversized")
        else:
            ss, sid = held(kind)
            report = ss.verify(sid)
            assert report["id"] == sid and ss.verifies == 1
            ss.close()
        assert report["ok"] is want_ok, report
        plain = verify_shard_blob(images[kind])
        for field in ("lo", "hi", "records", "bad_records",
                      "header_chain_ok", "first_hash_ok", "last_hash_ok"):
            assert report.get(field) == plain.get(field), field

    def test_the_store_holds_a_file_to_its_index_row(self, held):
        # a sound shard of the same range that is not the one indexed
        ss, sid = held("oversized")
        report = ss.verify(sid)
        assert not report["ok"]
        assert report["error"] == "file differs from the store index"
        assert report["bad_records"] == 0 and report["header_chain_ok"]
        assert ss.verify(99) == {"ok": False, "error": "unknown shard"}
        ss.close()

    @pytest.mark.parametrize("arm", sorted(ARMS))
    def test_a_store_handed_a_hasher_verifies_through_it(
            self, tmp_path, images, arms, arm):
        ss = HistoryShardStore(str(tmp_path / "s"), hasher=arms[arm])
        res = ss.import_shard(images["clean"])
        assert res["ok"] and ss.verify(res["id"])["ok"]
        sv = ss.get_json()["shard_verify"]
        assert sv["records"] == 2 * res["records"] and sv["batches"] == 2
        if arm == "device":
            assert sv["device_records"] == sv["records"]
        if arm == "plain":
            assert sv["host_records"] == sv["records"]
        ss.close()


# --------------------------------------------------------------------------
# the import off the reader thread


class Harness:
    """A ShardBackfill over source shard stores served in-process, its
    imports dispatched to threads as the node's job queue would."""

    def __init__(self, tmp_path, sources: dict, threaded: bool,
                 corrupt=(), tracer=None):
        self.sources = sources
        self.corrupt = set(corrupt)
        self.dst = HistoryShardStore(str(tmp_path / "dst"), tracer=tracer)
        self.txdb = ArchiveTxDatabase(":memory:")
        self.installed: list = []
        self.condemned: list = []
        self.fed: list = []
        self.threads: list = []
        self.sb = ShardBackfill(
            send=self.send, peers=lambda: sorted(self.sources),
            shardstore=self.dst, clock=time.monotonic,
            on_imported=self.on_imported,
            on_condemn=self.condemned.append,
            dispatch=self.dispatch if threaded else None,
            tracer=tracer)
        self.outbox: list = []

    def dispatch(self, work) -> bool:
        t = threading.Thread(target=work, daemon=True)
        self.threads.append(t)
        t.start()
        return True

    def on_imported(self, res):
        self.installed.append((res["lo"], res["hi"]))
        fed = feed_shard(self.dst, res["id"], txdb=self.txdb,
                         tracer=self.sb.tracer)
        self.fed.append(fed)
        return fed

    def send(self, peer, msg):
        self.outbox.append((peer, msg))

    def pump(self, timeout=20.0):
        """Deliver requests until the session ends."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if not self.outbox:
                if self.sb.state in ("done", "fallback"):
                    return
                time.sleep(0.001)
                continue
            peer, msg = self.outbox.pop(0)
            ss = self.sources[peer]
            if msg.seg_id < 0:
                rows = [(d["id"], d["size"], d["live_bytes"], False,
                         d["lo"], d["hi"], d["file_bytes"])
                        for d in ss.segments()]
                self.sb.on_manifest(peer, rows, epoch=7)
                continue
            meta, data = ss.fetch_segment(msg.seg_id, offset=msg.offset,
                                          length=4096)
            if peer in self.corrupt and msg.offset == 0:
                data = bytearray(data)
                data[60] ^= 0xFF  # inside the sealed first hash: CRC breaks
                data = bytes(data)
            self.sb.on_data(peer, SegmentData(
                seg_id=msg.seg_id, total=meta["size"], offset=msg.offset,
                data=data, snap_epoch=7))
        raise AssertionError(f"session did not end: {self.sb.get_json()}")

    def close(self):
        for t in self.threads:
            t.join(timeout=5)
        self.dst.close()
        self.txdb.close()
        for ss in self.sources.values():
            ss.close()


SPLITS = ((1, 2), (3, 4), (5, 6))


class TestImportOffTheReader:
    @pytest.mark.parametrize("threaded", [False, True])
    def test_backfill_installs_oldest_first(self, tmp_path, threaded):
        env = _sealed_chain(tmp_path, splits=SPLITS)
        h = Harness(tmp_path, {b"p1": env["ss"]}, threaded)
        h.sb.start()
        h.pump()
        assert h.installed == list(SPLITS)
        assert h.dst.contiguous_floor() == 6
        j = h.sb.get_json()
        assert j["imported"] == 3 and j["images_held"] == 0
        assert j["completed"] == 1 and j["import_rejects"] == 0
        h.close()

    @pytest.mark.parametrize("threaded", [False, True])
    def test_a_bad_image_retains_nothing_and_condemns_first(
            self, tmp_path, threaded):
        env = _sealed_chain(tmp_path, splits=SPLITS)
        good = HistoryShardStore(str(tmp_path / "good"))
        for sid in env["sids"]:
            assert good.import_shard(_file_blob(env["ss"], sid))["ok"]
        # b"p1" (tried first) corrupts every file it serves
        h = Harness(tmp_path, {b"p1": env["ss"], b"p2": good}, threaded,
                    corrupt=[b"p1"])
        before = sorted(os.listdir(h.dst.root))
        h.sb.start()
        h.pump()
        j = h.sb.get_json()
        assert h.condemned == [b"p1"] and j["garbage_peers"] == 1
        assert j["import_rejects"] == 1 and j["imported"] == 3
        # nothing of the condemned peer's was installed: everything came
        # again from the other, oldest first
        assert h.installed == list(SPLITS)
        assert h.dst.import_rejects == 1
        left = sorted(set(os.listdir(h.dst.root)) - set(before))
        assert left == ["shard-000001.shard", "shard-000002.shard",
                        "shard-000003.shard", "shards.json"]
        for row in h.dst.shards():
            assert h.dst.verify(row["id"])["ok"]
        h.close()

    def test_every_bad_image_is_refused_with_zero_bytes(self, tmp_path,
                                                       images, arms):
        for arm, hasher in arms.items():
            ss = HistoryShardStore(str(tmp_path / arm), hasher=hasher)
            before = sorted(os.listdir(ss.root))
            for kind in ("flipped", "broken_chain", "truncated"):
                res = ss.import_shard(images[kind])
                assert not res["ok"], (arm, kind)
                assert sorted(os.listdir(ss.root)) == before
            assert ss.import_rejects == 3 and ss.range() is None
            ss.close()

    def test_the_next_file_is_asked_for_while_one_is_imported(self, tmp_path):
        env = _sealed_chain(tmp_path, splits=SPLITS)
        gate = threading.Event()
        h = Harness(tmp_path, {b"p1": env["ss"]}, threaded=True)
        real = h.on_imported

        def slow(res):
            gate.wait(5)
            return real(res)

        h.sb.on_imported = slow
        h.sb.start()
        t = threading.Thread(target=h.pump, daemon=True)
        t.start()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and h.sb.get_json()[
                "images_held"] < 2:
            time.sleep(0.005)
        j = h.sb.get_json()
        # one image in work, one waiting behind it, the third NOT asked
        # for: memory stays bounded by two images
        assert j["images_held"] == 2 and j["queue"] == 1 and j["imported"] == 0
        assert h.sb._want is None
        gate.set()
        t.join(timeout=20)
        assert h.installed == list(SPLITS)
        h.close()


# --------------------------------------------------------------------------
# spans and counters of an import


def complete_spans(tracer):
    return [ev for ev in tracer.chrome_trace()["traceEvents"]
            if ev["ph"] == "X"]


class TestSpansAndCounters:
    @pytest.fixture
    def traced(self, tmp_path):
        env = _sealed_chain(tmp_path, splits=SPLITS)
        tracer = Tracer(capacity=4096, enabled=True)
        h = Harness(tmp_path, {b"p1": env["ss"]}, threaded=True,
                    tracer=tracer)
        h.sb.start()
        h.pump()
        h.close()
        return h, complete_spans(tracer)

    def test_the_spans_nest(self, traced):
        _h, events = traced
        by_id = {ev["args"]["span"]: ev for ev in events}
        names = {ev["name"] for ev in events}
        assert {"archive.shard", "archive.fetch", "archive.import",
                "shard.verify", "shard.verify.crc", "shard.verify.hash",
                "shard.install", "archive.feed", "archive.feed.nodestore",
                "archive.feed.headers", "archive.feed.txdb"} <= names
        parents = {
            "archive.fetch": "archive.shard",
            "archive.import": "archive.shard",
            "shard.verify": "archive.import",
            "shard.verify.crc": "shard.verify",
            "shard.verify.hash": "shard.verify",
            "shard.install": "archive.import",
            "archive.feed": "archive.import",
            "archive.feed.nodestore": "archive.feed",
            "archive.feed.headers": "archive.feed",
            "archive.feed.txdb": "archive.feed",
        }
        for ev in events:
            want = parents.get(ev["name"])
            if want is None:
                continue
            up = by_id[ev["args"]["parent"]]
            assert up["name"] == want, ev["name"]
            assert up["ts"] <= ev["ts"] + 1
            assert ev["ts"] + ev["dur"] <= up["ts"] + up["dur"] + 1
        assert sum(1 for ev in events if ev["name"] == "archive.shard") == 3
        # the importing thread's clock is on its spans
        for ev in events:
            if ev["name"] in ("archive.import", "shard.verify",
                              "archive.feed"):
                assert "cpu_us" in ev["args"], ev["name"]

    def test_the_spans_add_up_to_the_counters(self, traced):
        h, events = traced
        sv = h.dst.get_json()["shard_verify"]
        hashed = [ev for ev in events if ev["name"] == "shard.verify.hash"]
        assert sum(ev["args"]["records"] for ev in hashed) == sv["records"]
        assert len(hashed) == sv["batches"] == 3
        assert {ev["args"]["arm"] for ev in hashed} == {"host"}
        assert sv["host_records"] == sv["records"]
        assert sv["device_records"] == sv["bad_records"] == 0
        shards = [ev for ev in events if ev["name"] == "archive.shard"]
        j = h.sb.get_json()
        assert sum(ev["args"]["bytes"] for ev in shards) == j["bytes"]
        assert sum(ev["args"]["records"] for ev in shards) == sv["records"]
        assert (sum(ev["args"]["txs"] for ev in shards)
                == sum(f["txs"] for f in h.fed) > 0)
        fed = [ev for ev in events if ev["name"] == "archive.feed.nodestore"]
        assert sum(ev["args"]["records"] for ev in fed) == sv["records"]

    def test_the_layer_readers_read_them(self, traced):
        h, events = traced
        j = h.sb.get_json()
        sv = h.dst.get_json()["shard_verify"]
        sources = {"spans": events, "counters": {
            "window_s": 2.0, "backfill.bytes": j["bytes"],
            **{f"shard_verify.{k}": v for k, v in sv.items()}}}
        m = manifest.load(os.path.join(REPO, "BENCHMARK.json"))
        mine = [x["name"] for x in manifest.metrics_of(
            m, "catchup.deep", "per_layer")
            if x["workloads"] == ["catchup.deep"]]
        assert len(mine) == 10
        got = {name: readers.read_metric(
            manifest.reader_file(BENCH, name), sources) for name in mine}
        shares = [got[f"archive.{k}_share"] for k in (
            "fetch", "verify", "install", "feed_nodestore", "feed_txdb")]
        assert all(s is not None and 0 < s < 100 for s in shares), got
        assert 0 <= got["archive.overlap_share"] <= 100
        assert 0 < got["archive.import_cpu_share"] <= 110
        assert got["archive.fetch_mb_per_s"] == pytest.approx(
            j["bytes"] / 2.0 / 1e6)
        assert got["shardhash.device_share"] == 0.0
        assert got["shardhash.records_per_batch"] == pytest.approx(
            sv["records"] / 3)
        # a program from before records none of it: every reader is silent
        for name in mine:
            assert readers.read_metric(
                manifest.reader_file(BENCH, name),
                {"spans": [], "counters": {}}) is None, name


# --------------------------------------------------------------------------
# a cold archive beside a first one, over TCP


class TestTwoArchives:
    def test_a_cold_archive_backfills_from_a_first_one(self, tmp_path):
        from stellard_tpu.testkit.tcpnet import free_ports, wait_until

        env = _sealed_chain(tmp_path, n_ledgers=9,
                            splits=((1, 3), (4, 6), (7, 9)),
                            txs_per_ledger=4)
        src_rows = {sid: env["ss"].acct_rows(sid) for sid in env["sids"]}
        src_blobs = {txid: env["ss"].tx_blob(sid, txid)
                     for sid, rows in src_rows.items()
                     for _a, _l, _t, txid in rows}
        env["ss"].close()
        up_port, cold_port = free_ports(2)

        def cfg(name, port, shard_dir, upstream=()):
            return Config(
                standalone=False, node_mode="archive",
                signature_backend="cpu", node_db_type="segstore",
                node_db_path=str(tmp_path / f"{name}-ns"),
                database_path=str(tmp_path / f"{name}.db"),
                archive_path=shard_dir, archive_rescan_s=2.0,
                peer_port=port, node_upstream=list(upstream), rpc_port=0)

        # the upstream: an archive on the sealed shards, no upstream of
        # its own, no validated tip
        up = Node(cfg("up", up_port, str(tmp_path / "src-shards")))
        up.setup().serve()
        cold = None
        try:
            assert len(up.shardstore.shards()) == 3
            cold = Node(cfg("cold", cold_port, str(tmp_path / "cold-shards"),
                            [f"127.0.0.1 {up_port}"])).setup().serve()
            threading.Thread(target=cold.run, daemon=True).start()
            assert wait_until(
                lambda: cold.read_plane.archive_floor >= 9, 60, 0.05)
            sb = cold.overlay.node.shard_backfill
            j = sb.get_json()
            assert j["imported"] == 3 and j["import_rejects"] == 0
            assert [(s["lo"], s["hi"]) for s in cold.shardstore.shards()] \
                == [(1, 3), (4, 6), (7, 9)]
            assert cold.overlay.node.rounds_completed == 0
            assert cold.ledger_master.closed_ledger().seq == 1
            # the rebuilt SQL index equals the writer's sealed rows
            want = sorted((a, l, t, x) for rows in src_rows.values()
                          for a, l, t, x in rows)
            got = sorted(cold.txdb.account_tx_index(1, 9))
            assert got == want and len(want) == 32
            for txid, (raw, meta) in src_blobs.items():
                row = cold.txdb.get_transaction(txid)
                assert (row["raw"], row["meta"]) == (raw, meta)
            # the records reached the nodestore in batches
            assert cold.nodestore.fetch(env["headers"][4]["hash"]) is not None
            # the import ran on the job queue, and the next file was
            # asked for before the one in work was done
            events = complete_spans(cold.tracer)
            imports = sorted((ev for ev in events
                              if ev["name"] == "archive.import"),
                             key=lambda ev: ev["ts"])
            fetches = sorted((ev for ev in events
                              if ev["name"] == "archive.fetch"),
                             key=lambda ev: ev["ts"])
            assert len(imports) == len(fetches) == 3
            net_tids = {ev["tid"] for ev in fetches}
            assert not net_tids & {ev["tid"] for ev in imports}
            for k in (0, 1):
                assert fetches[k + 1]["ts"] <= (
                    imports[k]["ts"] + imports[k]["dur"])
            counts = nodedrive.rpc(cold.http_server.port, "get_counts", {})
            sv = counts["history_shards"]["shard_verify"]
            assert sv["records"] == sum(
                s["records"] for s in cold.shardstore.shards())
            assert sv["bad_records"] == 0 and sv["batches"] == 3
        finally:
            if cold is not None:
                cold.stop()
            up.stop()


# --------------------------------------------------------------------------
# the cell's driver, at the rehearsal's sizes, on the host arms


class TestDriverRehearsal:
    def test_the_manifest_holds_the_cell(self):
        m = manifest.load(os.path.join(REPO, "BENCHMARK.json"))
        manifest.validate(m, REPO)
        assert len(m["workloads"]) == 7
        cell = m["workloads"][-1]
        assert (cell["name"], cell["config"], cell["traffic"],
                cell["chips"]) == ("catchup.deep", "archive-shards", "deep", 1)
        assert {x["name"] for x in manifest.metrics_of(
            m, "catchup.deep", "end_to_end")} == {
                "catchup_tx_per_s", "setup_s"}
        files = manifest.cell_files(m, "catchup.deep", REPO)
        cfg = files["config"]
        for key in ("source", "deployment", "guarantees", "population",
                    "history", "assumed", "reduced"):
            assert cfg[key], key
        assert sorted(cfg["reduced"]) == sorted(
            m["configs"][-1]["reduced"]) == [
                "accounts", "ledgers_per_shard", "shards", "upstreams"]
        hist = cfg["history"]
        assert (hist["shards"], hist["ledgers_per_shard"],
                hist["txs_per_ledger"]) == (8, 64, 256)
        state = manifest.cell_files(m, "state-1m.zipf", REPO)["config"]
        assert cfg["population"] == state["population"]

    def test_the_driver_runs_the_cell_and_holds_it_correct(self, tmp_path):
        m = manifest.load(os.path.join(REPO, "BENCHMARK.json"))
        files = manifest.cell_files(m, "catchup.deep", REPO, rehearsal=True)
        spec = importlib.util.spec_from_file_location(
            "backfill_driver", files["driver_path"])
        driver = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(driver)
        from yardstick.capture import Capture

        said = []
        ctx = types.SimpleNamespace(
            seed=3600000019, seconds=2.0, trace=False, rehearsal=True,
            config=files["config"],
            # the cell's INI on the host arms (no JAX in a tier-1 test)
            ini_template=nodedrive.plain_reference_ini(files["ini"]),
            traffic=files["traffic"], cache_dir=str(tmp_path / "cache"),
            work_root=str(tmp_path / "work"), say=said.append)
        cap = Capture(True, str(tmp_path / "trace"))
        cap.start = cap.finish = lambda: None  # spans, no profiler
        ctx.capture = lambda: cap
        result = driver.run(ctx)
        assert result["problems"] == []
        assert result["correct"] is True
        counters = result["sources"]["counters"]
        n_shards = files["config"]["history"]["shards"]
        assert result["attempted"] == counters["shards"] >= 1
        assert result["failed"] == 0
        assert counters["backfill.imported"] == counters["shards"]
        assert counters["shard_verify.records"] == counters["records"]
        assert counters["txs"] == counters["shards"] * (
            files["config"]["history"]["ledgers_per_shard"]
            * files["config"]["history"]["txs_per_ledger"])
        assert result["end_to_end"]["catchup_tx_per_s"] > 0
        assert counters["shards"] <= n_shards
        for name in ("archive.fetch_share", "archive.verify_share",
                     "archive.feed_txdb_share", "archive.fetch_mb_per_s",
                     "shardhash.device_share",
                     "shardhash.records_per_batch"):
            assert readers.read_metric(
                manifest.reader_file(BENCH, name),
                result["sources"]) is not None, name
        assert any("device-path check" in line for line in said)
