"""Traffic kind ``backfill``: an archive's cold start. The measured node
is a ``Node`` built from the cell's INI (``[node] mode=archive``,
``[archive] backfill=1``) in this process, where it holds the chip: an
empty nodestore, an empty transaction database, an empty shard
directory. Its one upstream is a first archive, a child process pinned
to ``JAX_PLATFORMS=cpu`` (``python -m stellard_tpu --conf <ini>`` of
the configuration's ``upstream_ini``), started onto the prepared shard
directory (``yardstick/prepared_shards.py``) with no upstream of its
own: it advertises the sealed shards in its manifest and has no
validated tip to follow. The load is the backfill's own closed loop
(``node/archive.ShardBackfill``): whole shard files over ``GetSegments``
on TCP+TLS, one file in flight, in the program's chunks, oldest first,
no think time.

Set-up is everything before the dial: the prepared data found or built,
the upstream started and serving, the measured node built
(``Node(...).setup()``) and its device prewarm joined, and the hash
plane's router shown the flat batches of the prepared shards
(``show_router``). The dial is ``node.serve()``, which starts the
overlay: the window opens there.

The window closes at the first shard that COUNTS after ``--seconds``,
or when the last one counts. A shard counts when the node's read plane
has the verified floor over it (``read_plane.archive_floor``: the
program moves it behind the verification, the install with its
``fsync`` and the feed of the nodestore and the SQL index).
``catchup_tx_per_s`` is the transactions of the shards that counted
over the window's seconds. Every wait has a deadline: where no shard
counts within ``stall_factor`` x ``--seconds`` the run stops with
``correct`` false and says where it stood.

Behind the window the backfill runs on to the last shard, and the node
is held to the configuration's guarantees: every installed shard file's
SHA-256 against the prepared one, and the plain ``hashlib`` contract
over it again; ``account_tx`` (seeded accounts, paged by marker over
the whole range), ``tx`` (seeded ids) and ``ledger`` (first and last of
every shard, with its transactions) over RPC against the writer's
untrimmed database; the floor; no consensus round, no closed ledger;
and the device-path check (``check_device_path``). ``--seed`` draws the
samples and the corrupted image; the data set does not depend on it.
"""

from __future__ import annotations

import inspect
import os
import random
import struct
import subprocess
import sys
import threading
import time
import zlib

from yardstick import nodedrive, prepared_shards, stats
from yardstick.capture import WINDOW

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
POLL_S = 0.01
BACKFILL_COUNTERS = ("imported", "bytes", "requests", "timeouts", "retries",
                     "import_rejects", "garbage_peers", "duplicates")
# The upstream is `python -m stellard_tpu --conf <ini>` behind two lines
# of its own, as a quorum cell's peers are: it asks the kernel for
# SIGKILL when the driver's process dies, however it dies.
UPSTREAM = (
    "import ctypes, os, runpy, signal, sys\n"
    "ctypes.CDLL(None).prctl(1, signal.SIGKILL)\n"
    "if os.getppid() != int(sys.argv[1]): sys.exit(3)\n"
    "sys.argv = ['stellard_tpu', '--conf', sys.argv[2]]\n"
    "runpy.run_module('stellard_tpu', run_name='__main__')\n"
)


class Stalled(Exception):
    """A wait of the driver ran out: the run ends with ``correct``
    false and this line."""


def start_upstream(ctx, shard_dir: str, peer_port: int, rpc_port: int):
    workdir = os.path.join(ctx.work_root, "upstream")
    os.makedirs(workdir)
    with open(os.path.join(BENCH, "configs",
                           ctx.config["upstream_ini"])) as fh:
        ini = fh.read()
    for key, value in (("workdir", workdir), ("shards", shard_dir),
                       ("peer_port", str(peer_port)),
                       ("rpc_port", str(rpc_port))):
        ini = ini.replace("{" + key + "}", value)
    path = os.path.join(workdir, "upstream.cfg")
    with open(path, "w") as fh:
        fh.write(ini)
    with open(os.path.join(workdir, "upstream.log"), "w") as log:
        return subprocess.Popen(
            [sys.executable, "-c", UPSTREAM, str(os.getpid()), path],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            stdout=log, stderr=log)


def stop_child(proc, grace: float = 20.0) -> None:
    if proc is None or proc.poll() is not None:
        return
    proc.terminate()
    try:
        proc.wait(timeout=grace)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


def wait_upstream(proc, rpc_port: int, shards: int, timeout: float) -> None:
    """The upstream answers its door and holds every prepared shard."""
    deadline = time.monotonic() + timeout
    held = None
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise SystemExit(f"benchmark: the upstream archive exited with "
                             f"{proc.returncode} before it served")
        try:
            counts = nodedrive.rpc(rpc_port, "get_counts", {}, timeout=5)
            held = (counts.get("history_shards") or {}).get("shards")
            if held == shards:
                return
        except OSError:
            pass
        time.sleep(0.2)
    raise SystemExit(f"benchmark: the upstream archive did not serve "
                     f"{shards} shards within {timeout:.0f}s (holds {held})")


def read_image(prepared_dir: str, row: dict) -> bytes:
    with open(prepared_shards.shard_path(prepared_dir, row), "rb") as fh:
        return fh.read()


def store_takes_hasher() -> bool:
    """True when the program under test hands its shard store a hasher
    (the hash plane's door to the offline contract); a program from
    before has the plain loop alone."""
    from stellard_tpu.nodestore.shards import HistoryShardStore

    return "hasher" in inspect.signature(HistoryShardStore.__init__).parameters


def show_router(ctx, node, prepared_dir: str, meta: dict) -> None:
    """Before the window, where the hash plane's router has never priced
    a flat batch of this size: the prepared shards' records through the
    node's own routed hasher, by the program's own contract
    (``verify_shard_blob``), as the import will hand them over. The
    router explores an unmeasured arm with the first batch large enough
    to be routed and discards that arm's first (compile-laden) sample,
    so the first shard's batches price both arms; the others load,
    before the window, the device program of every batch shape the
    window's shards will bring, whichever arm the router then prefers."""
    from stellard_tpu.nodestore.shards import verify_shard_blob

    hasher = node.shardstore.hasher
    t0 = time.perf_counter()
    for row in meta["shards"]:
        report = verify_shard_blob(read_image(prepared_dir, row), hasher)
        if not report["ok"]:
            raise SystemExit(f"benchmark: prepared shard {row['id']} fails "
                             f"the contract through the router: {report}")
    model = (node.hasher.get_json() if hasattr(node.hasher, "get_json")
             else {}).get("flat_model") or {}
    ctx.say(f"the router was shown {len(meta['shards'])} shards "
            f"({meta['records']} records) in "
            f"{time.perf_counter() - t0:.1f}s: host "
            f"{model.get('host_unit_ms')} ms a record, device by bucket "
            f"{ {b: s['ewma_ms'] for b, s in (model.get('buckets') or {}).items()} }")


def program_counters(node) -> dict:
    """The backfill's and the shard contract's counters as they stand
    now. A counter the program under test lacks is left out, and the
    metric that reads it finds nothing."""
    out = {}
    bj = node.overlay.node.shard_backfill.get_json()
    out.update({f"backfill.{k}": bj[k] for k in BACKFILL_COUNTERS if k in bj})
    sv = node.shardstore.get_json().get("shard_verify") or {}
    out.update({f"shard_verify.{k}": v for k, v in sv.items()})
    return out


class FloorWatch:
    """When each prepared shard counted: a thread reads the read plane's
    verified floor every 10 ms (a bare attribute load) and notes the
    time at which it passed each shard's last ledger; it drains the
    node's span ring every two seconds, so a long run cannot wrap it."""

    def __init__(self, node, rows: list, cap):
        self.node, self.rows, self.cap = node, rows, cap
        self.counted: list[tuple[int, float]] = []  # shard index, time
        self._stop = threading.Event()
        self._cv = threading.Condition()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="floor-watch")

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        drained = time.perf_counter()
        while not self._stop.wait(POLL_S):
            floor = self.node.read_plane.archive_floor
            now = time.perf_counter()
            k = len(self.counted)
            if k < len(self.rows) and floor >= self.rows[k]["hi"]:
                with self._cv:
                    while k < len(self.rows) and floor >= self.rows[k]["hi"]:
                        self.counted.append((k, now))
                        k += 1
                    self._cv.notify_all()
            if now - drained > 2.0:
                drained = now
                self.cap.collect_spans(self.node.tracer)

    def wait_count(self, n: int, deadline: float) -> bool:
        """-> whether ``n`` shards counted before ``deadline``
        (``time.perf_counter()``'s clock)."""
        with self._cv:
            while len(self.counted) < n:
                left = deadline - time.perf_counter()
                if left <= 0:
                    return False
                self._cv.wait(timeout=min(left, 1.0))
        return True

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)


def where_it_stood(node) -> str:
    bj = node.overlay.node.shard_backfill.get_json()
    return (f"backfill {bj.get('state')}, imported {bj.get('imported')}, "
            f"requests {bj.get('requests')}, timeouts {bj.get('timeouts')}, "
            f"retries {bj.get('retries')}, rejects "
            f"{bj.get('import_rejects')}, queue {bj.get('queue')}, floor "
            f"{node.read_plane.archive_floor}, peers "
            f"{len(node.overlay.segment_peers())}")


def measure(ctx, node, watch: FloorWatch, upstream) -> dict:
    """The window, from the dial: -> its times and what counted."""
    rows = watch.rows
    stall_s = float(ctx.traffic["stall_factor"]) * ctx.seconds
    t0 = time.perf_counter()
    watch.start()
    node.serve()  # the dial: the overlay starts and calls the upstream
    # the daemon's run loop (heartbeat, sweeps), as `python -m
    # stellard_tpu` runs it behind setup().serve()
    threading.Thread(target=node.run, daemon=True, name="node-run").start()
    n = 0
    while True:
        if not watch.wait_count(n + 1, time.perf_counter() + stall_s):
            raise Stalled(
                f"no shard counted within {stall_s:.0f}s of "
                f"{'the dial' if n == 0 else f'shard {n}'}: "
                f"{where_it_stood(node)}; upstream "
                f"{'running' if upstream.poll() is None else 'exited'}")
        n = len(watch.counted)
        t_last = watch.counted[n - 1][1]
        if n == len(rows) or t_last - t0 >= ctx.seconds:
            break
    hits = watch.counted[:n]  # later ones fall behind the window
    return {"t0": t0, "t1": t_last, "counted": [k for k, _t in hits],
            "count_s": [t - t0 for _k, t in hits]}


def corrupt_image(image: bytes, seed: int, flips: int) -> tuple[bytes, int]:
    """``image`` with one seeded byte flipped inside the node blob of
    each of ``flips`` seeded records, under a whole-file CRC made good
    again, so that only the content hash can catch it -> (the image,
    its records). The shard format is the program's documented one
    (``nodestore/shards.py``: magic, header, records in the segstore
    layout, account rows, CRC)."""
    rec_off, rec_len = struct.unpack_from("<QQ", image, 8 + 12)
    spans = []
    off, end = rec_off, rec_off + rec_len
    while off + 37 <= end:
        (body_len,) = struct.unpack_from("<I", image, off)
        spans.append((off + 38, body_len - 1))
        off += 37 + body_len
    rng = random.Random(seed)
    out = bytearray(image)
    for blob_off, blob_len in rng.sample(spans, min(flips, len(spans))):
        # behind the 4-byte prefix: a ledger header stays a header, so
        # the chain check reads on and the content hash alone objects
        out[blob_off + rng.randrange(4, blob_len)] ^= 0x01 << rng.randrange(8)
    out[-4:] = struct.pack("<I", zlib.crc32(memoryview(out)[:-4]) & 0xFFFFFFFF)
    return bytes(out), len(spans)


def check_device_path(ctx, node, prepared_dir: str, meta: dict, cap,
                      problems: list) -> None:
    """Behind the window, in every run: one seeded prepared shard with
    ``corrupt_records`` record bytes flipped under a CRC made good is
    imported into a scratch shard store that was handed the hash
    plane's DEVICE arm itself (not the router, which may rightly price
    the host cheaper), then its clean twin. The flipped image must be
    rejected with zero bytes retained and exactly the flipped records
    bad, the twin installed, and every record of both hashed on the
    device: the chip's verdicts are really used. Without this nothing
    in the cell would show that the device plane the configuration
    names is alive and answers right on this data."""
    from stellard_tpu.nodestore.shards import HistoryShardStore

    flips = int(ctx.traffic["corrupt_records"])
    rng = random.Random(ctx.seed + 5)
    row = rng.choice(meta["shards"])
    clean = read_image(prepared_dir, row)
    bad, records = corrupt_image(clean, ctx.seed + 6, flips)
    device_arm = getattr(node.hasher, "inner", node.hasher)
    scratch = os.path.join(ctx.work_root, "scratch-shards")
    store = HistoryShardStore(scratch, hasher=device_arm)
    try:
        t0 = time.perf_counter()
        with cap.annotate("check_device_path"):
            rejected = store.import_shard(bad)
            left = sorted(os.listdir(scratch))
            installed = store.import_shard(clean)
        ms = (time.perf_counter() - t0) * 1000.0
        sv = store.get_json()["shard_verify"]
    finally:
        store.close()
    report = rejected.get("report") or {}
    if rejected.get("ok") or report.get("bad_records") != flips:
        problems.append(
            f"device-path check: the image of shard {row['id']} with "
            f"{flips} flipped records answered ok={rejected.get('ok')}, "
            f"bad_records={report.get('bad_records')}")
    if [f for f in left if f != HistoryShardStore.INDEX_NAME]:
        problems.append(f"device-path check: a rejected image left {left}")
    if not installed.get("ok") or installed.get("duplicate"):
        problems.append(f"device-path check: the clean twin answered "
                        f"{installed}")
    if sv["device_records"] != 2 * records and not ctx.rehearsal:
        problems.append(
            f"device-path check: {sv['device_records']} of {2 * records} "
            f"records were hashed on the device")
    if sv["records"] != 2 * records or sv["bad_records"] != flips:
        problems.append(f"device-path check: the contract counted {sv}")
    state = node.hasher.get_json() if hasattr(node.hasher, "get_json") else {}
    if state.get("wedged"):
        problems.append("hash plane: the device is wedged")
    ctx.say(f"device-path check: shard {row['id']} ({records} records) "
            f"flipped and clean through the device arm, {ms:.0f} ms: "
            f"{sv}")


def check_account_tx(port: int, ref, accounts: list, lo: int, hi: int,
                     limit: int, problems: list) -> int:
    """``account_tx`` of each account, forward and binary, paged by
    marker over the whole range, against the writer's rows -> rows
    compared."""
    from stellard_tpu.protocol.keys import encode_account_id

    n = 0
    for account in accounts:
        got, marker = [], None
        for _page in range(1 << 16):
            params = {"account": encode_account_id(account),
                      "ledger_index_min": lo, "ledger_index_max": hi,
                      "forward": True, "binary": True, "limit": limit}
            if marker is not None:
                params["marker"] = marker
            res = nodedrive.rpc(port, "account_tx", params)
            if res.get("status") != "success":
                problems.append(f"account_tx {account.hex()[:12]}: {res}")
                break
            got.extend((int(t["ledger_index"]), t["tx_blob"], t.get("meta"))
                       for t in res["transactions"])
            marker = res.get("marker")
            if marker is None:
                break
        want = [(r["ledger_seq"], r["raw"].hex().upper(),
                 r["meta"].hex().upper() if r["meta"] else None)
                for r in ref.account_transactions(
                    account, lo, hi, limit=1 << 30, forward=True)]
        n += len(want)
        if got != want:
            problems.append(
                f"account_tx {account.hex()[:12]}: {len(got)} rows, the "
                f"writer has {len(want)}; first difference at "
                f"{next((i for i, (a, b) in enumerate(zip(got, want)) if a != b), min(len(got), len(want)))}")
    return n


def check_tx(port: int, ref, txids: list, problems: list) -> None:
    from stellard_tpu.protocol.stobject import STObject
    from stellard_tpu.protocol.sttx import SerializedTransaction

    for txid in txids:
        h = txid.hex().upper()
        res = nodedrive.rpc(port, "tx", {"transaction": h})
        row = ref.get_transaction(txid)
        want = SerializedTransaction.from_bytes(row["raw"]).obj.to_json()
        want_meta = STObject.from_bytes(row["meta"]).to_json()
        got = {k: res.get(k) for k in want}
        if (got != want or res.get("ledger_index") != row["ledger_seq"]
                or res.get("meta") != want_meta or res.get("hash") != h):
            problems.append(f"tx {h[:16]}: ledger_index="
                            f"{res.get('ledger_index')} (writer "
                            f"{row['ledger_seq']}), error={res.get('error')}")


def check_ledgers(port: int, ref, seqs: list, problems: list) -> None:
    """``ledger`` with its transactions, by sequence: the header fields
    against the writer's header row, the transaction ids against the
    writer's rows of that ledger (the tree is loaded from the nodestore
    the feed filled)."""
    for seq in seqs:
        res = nodedrive.rpc(port, "ledger", {"ledger_index": seq,
                                             "transactions": True})
        led = res.get("ledger") or {}
        hdr = ref.get_ledger_header(seq=seq)
        want = {"hash": hdr["hash"].hex().upper(),
                "parent_hash": hdr["parent_hash"].hex().upper(),
                "account_hash": hdr["account_hash"].hex().upper(),
                "transaction_hash": hdr["tx_hash"].hex().upper(),
                "total_coins": str(hdr["total_coins"]),
                "close_time": hdr["close_time"]}
        got = {k: led.get(k) for k in want}
        ids = sorted(r[3].hex().upper()
                     for r in ref.account_tx_index(seq, seq))
        if got != want or sorted(set(led.get("transactions") or ())) != \
                sorted(set(ids)):
            problems.append(
                f"ledger {seq}: answered {got.get('hash', '')[:16]} with "
                f"{len(led.get('transactions') or ())} transactions, the "
                f"writer has {want['hash'][:16]} with {len(set(ids))}; "
                f"error={res.get('error')}")


def check_installed(node, prepared_dir: str, meta: dict,
                    problems: list) -> None:
    """Every installed shard file: its SHA-256 against the prepared
    one's, and the plain ``hashlib`` contract over it again."""
    from stellard_tpu.nodestore.shards import verify_shard_blob

    by_range = {(r["lo"], r["hi"]): r for r in meta["shards"]}
    installed = node.shardstore.shards()
    if sorted((s["lo"], s["hi"]) for s in installed) != sorted(by_range):
        problems.append(f"installed shards {[(s['lo'], s['hi']) for s in installed]}, "
                        f"prepared {sorted(by_range)}")
    if [s["lo"] for s in installed] != sorted(s["lo"] for s in installed):
        problems.append("the shards were not installed oldest first")
    for s in installed:
        path = os.path.join(node.shardstore.root, f"shard-{s['id']:06d}.shard")
        want = by_range.get((s["lo"], s["hi"]))
        if want is None:
            continue
        if prepared_shards.sha256_file(path) != want["sha256"]:
            problems.append(f"installed shard [{s['lo']}, {s['hi']}] differs "
                            f"from the prepared file")
        with open(path, "rb") as fh:
            report = verify_shard_blob(fh.read())
        if not report["ok"] or report["records"] != want["records"]:
            problems.append(f"installed shard [{s['lo']}, {s['hi']}] fails "
                            f"the plain contract: {report}")


def read_back(ctx, node, prepared_dir: str, meta: dict,
              problems: list) -> None:
    """The archive's answers over its RPC door against the writer's
    untrimmed database."""
    from stellard_tpu.node.txdb import TxDatabase

    tr = ctx.traffic
    lo, hi = meta["first_seq"], meta["last_sealed_seq"]
    port = node.http_server.port
    ref = TxDatabase(os.path.join(prepared_dir, "reference.db"))
    try:
        rng = random.Random(ctx.seed + 1)
        accounts, txids = [], []
        seqs = list(range(lo, hi + 1))
        for seq in rng.sample(seqs, min(int(tr["account_sample"]),
                                        len(seqs))):
            rows = ref.account_tx_index(seq, seq)
            accounts.append(rng.choice(rows)[0])
        rng = random.Random(ctx.seed + 3)
        per = -(-int(tr["tx_sample"]) // len(meta["shards"]))
        for row in meta["shards"]:  # of one seeded ledger a shard
            seq = rng.randint(row["lo"], row["hi"])
            ids = sorted({r[3] for r in ref.account_tx_index(seq, seq)})
            txids.extend(rng.sample(ids, min(per, len(ids))))
        accounts = sorted(set(accounts))
        t0 = time.perf_counter()
        n_rows = check_account_tx(port, ref, accounts, lo, hi,
                                  int(tr["page_limit"]), problems)
        check_tx(port, ref, txids, problems)
        check_ledgers(port, ref, sorted(
            {s for row in meta["shards"] for s in (row["lo"], row["hi"])}),
            problems)
        counts = ref.counts()
        mine = node.txdb.counts()
        ctx.say(f"read back {len(accounts)} accounts ({n_rows} rows), "
                f"{len(txids)} transactions, {2 * len(meta['shards'])} "
                f"ledgers in {time.perf_counter() - t0:.1f}s; the archive's "
                f"index holds {mine}, the writer's {counts}")
    finally:
        ref.close()


def run(ctx) -> dict:
    tr, cfg = ctx.traffic, ctx.config
    if not store_takes_hasher():
        raise SystemExit(
            "benchmark: this program's shard store takes no hasher: an "
            "archive's import never reaches the hash plane there, so no "
            "record of this cell can reach the chip and its device-path "
            "check cannot run; the cell cannot run on it")
    problems: list[str] = []
    cap = ctx.capture()

    from stellard_tpu.testkit.tcpnet import free_ports

    up_peer, up_rpc, my_peer = free_ports(3)
    upstream = node = watch = None
    window = None
    t_dial = time.perf_counter()
    try:
        # the node first: its device prewarm (31-33 s from a warm
        # compile cache, three times that from a cold one) runs while
        # the prepared data is found or, once in a checkout, built
        workdir = os.path.join(ctx.work_root, "db")
        os.makedirs(workdir)
        ini = nodedrive.ini_text(ctx.ini_template, workdir=workdir,
                                 start_up="fresh")
        ini = ini.replace("{peer_port}", str(my_peer)).replace(
            "{upstream_port}", str(up_peer))
        t0 = time.perf_counter()
        node = nodedrive.boot(ini, serve=False)
        marks = [("boot", time.perf_counter() - t0)]
        prepared_dir = prepared_shards.ensure(cfg, ctx.cache_dir)
        meta = prepared_shards.load_meta(prepared_dir)
        rows = meta["shards"]
        marks.append(("prepared", time.perf_counter() - t0))
        ctx.say(f"prepared: {len(rows)} shards [{meta['first_seq']}, "
                f"{meta['last_sealed_seq']}], {meta['records']} records, "
                f"{meta['bytes']} bytes (built in {meta['build_s']}s)")
        upstream = start_upstream(
            ctx, os.path.join(prepared_dir, "shards"), up_peer, up_rpc)
        libs_ok, libs = nodedrive.host_libraries_ok()
        if not libs_ok:
            problems.append(f"host libraries: {libs}")
        nodedrive.wait_warm(node)
        marks.append(("warm", time.perf_counter() - t0))
        show_router(ctx, node, prepared_dir, meta)
        marks.append(("router", time.perf_counter() - t0))
        wait_upstream(upstream, up_rpc, len(rows),
                      float(tr["upstream_timeout_s"]))
        marks.append(("upstream", time.perf_counter() - t0))
        ctx.say("set-up, seconds from node boot: " + ", ".join(
            f"{k} {v:.1f}" for k, v in marks))

        watch = FloorWatch(node, rows, cap)
        cap.start()
        cap.collect_spans(node.tracer)
        cap.spans.clear()
        before = (nodedrive.counters(node.verify_plane, node.hasher, node),
                  program_counters(node))
        # ---- the measured window ----
        try:
            with cap.annotate(WINDOW):
                t_dial = time.perf_counter()
                window = measure(ctx, node, watch, upstream)
            # ---- end of the window ----
            after = (nodedrive.counters(node.verify_plane, node.hasher, node),
                     program_counters(node))
            # spans are recorded as they end: what the ring holds now
            # ended inside the window (the next shard's are still open)
            cap.collect_spans(node.tracer)
            window_spans = list(cap.spans)
            # the backfill runs on to the last shard, outside the window
            stall_s = float(tr["stall_factor"]) * ctx.seconds
            if not watch.wait_count(len(rows),
                                    time.perf_counter() + stall_s):
                raise Stalled(f"the backfill did not reach the last shard "
                              f"within {stall_s:.0f}s behind the window: "
                              f"{where_it_stood(node)}")
        except Stalled as exc:
            problems.append(str(exc))
            cap.finish()
            return stalled_result(problems, t_dial, window, cap)
        finally:
            watch.stop()

        floor = node.read_plane.archive_floor
        if (floor != meta["last_sealed_seq"]
                or node.shardstore.contiguous_floor() != floor):
            problems.append(
                f"the read plane's floor is {floor}, the store's "
                f"{node.shardstore.contiguous_floor()}, the last sealed "
                f"ledger {meta['last_sealed_seq']}")
        vn = node.overlay.node
        if vn.rounds_completed or node.ledger_master.closed_ledger().seq != 1:
            problems.append(
                f"the archive completed {vn.rounds_completed} rounds and "
                f"stands at ledger "
                f"{node.ledger_master.closed_ledger().seq}: it must never "
                f"close one")
        bj = vn.shard_backfill.get_json()
        if bj["import_rejects"] or bj["garbage_peers"]:
            problems.append(f"the honest upstream was condemned: {bj}")
        check_installed(node, prepared_dir, meta, problems)
        read_back(ctx, node, prepared_dir, meta, problems)
        check_device_path(ctx, node, prepared_dir, meta, cap, problems)
        cap.finish()  # writing the trace out: behind the window
    finally:
        if node is not None:
            node.stop()
        stop_child(upstream)

    counters = nodedrive.delta(after[0], before[0])
    counters.update({k: v - before[1].get(k, 0)
                     for k, v in after[1].items()})
    window_s = window["t1"] - window["t0"]
    counted = [rows[k] for k in window["counted"]]
    txs = sum(r["txs"] for r in counted)
    rejects = counters.get("backfill.import_rejects", 0)
    if len(counted) < int(tr["min_counted"]):
        problems.append(f"{len(counted)} shards counted in the window, the "
                        f"cell asks for {tr['min_counted']}")
    ctx.say(f"window {window_s:.2f}s: {len(counted)} shards counted at "
            f"{[round(s, 2) for s in window['count_s']]}, {txs} "
            f"transactions")
    ctx.say("counters: " + ", ".join(
        f"{k} {counters[k]}" for k in sorted(counters)
        if k.startswith(("backfill.", "shard_verify.", "hash."))))
    counters.update({
        "window_s": window_s, "txs": txs, "shards": len(counted),
        "bytes": sum(r["bytes"] for r in counted),
        "records": sum(r["records"] for r in counted),
    })
    return {
        "correct": not problems,
        "problems": problems,
        # shards whose import ended inside the window: counted or rejected
        "attempted": len(counted) + rejects,
        "failed": rejects,
        "t_first_measured": window["t0"],
        "annotations": ["check_device_path"],
        "end_to_end": {"catchup_tx_per_s": stats.rate(txs, window_s)},
        "sources": {
            "counters": counters,
            "samples": {"count_s": window["count_s"]},
            "spans": window_spans,
            "capture": cap,
        },
    }


def stalled_result(problems: list, t_dial: float, window, cap) -> dict:
    """The line of a run that stalled: ``correct`` false, nothing
    measured."""
    return {
        "correct": False,
        "problems": problems,
        "attempted": 1,
        "failed": 1,
        "t_first_measured": window["t0"] if window else t_dial,
        "annotations": [],
        "end_to_end": {"catchup_tx_per_s": 0.0},
        "sources": {"counters": {}, "samples": {}, "spans": [],
                    "capture": cap},
    }
