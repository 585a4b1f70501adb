#!/usr/bin/env python3
"""Out-of-core state-plane smoke gate (tools/tier1.sh).

End to end, on the REAL node stack:

1. ``base`` phase: a fresh standalone file-backed node floods 100 txs
   (4 closes) through the full async pipeline and stops — a persisted
   chain on disk.
2. The state dir is copied twice and resumed (``start_up=load``, which
   opens the trees LAZILY) with online deletion + history shards on,
   under two ``[tree] cache_mb`` budgets: deliberately tiny (capped)
   and effectively unbounded (uncapped). Each resume floods 200 more
   txs (8 closes).
3. The gate asserts:
   - per-seq state/tx ROOTS byte-identical between capped and
     uncapped (lazy faulting under eviction pressure changes nothing);
   - the capped run actually FAULTED (nonzero
     shamap_inner_cache.faults — anti-vacuity: a smoke that never
     exercised the out-of-core path proves nothing);
   - neither resumed node reports a building-tree fold failure, and
     both sealed closes from the building tree
     (delta_replay.building_fold_failures == 0, incremental_seals > 0);
   - capped-run RSS growth during the flood stays bounded;
   - online deletion rotated with a shard SEAL, and an account_tx for
     a window BELOW the sql_trim retain floor is served from a shard
     (rows carry shard provenance) instead of lgrIdxInvalid.

Exit 0 on pass; 1 with the failures listed otherwise.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BASE_CLOSES = 4
RUN_CLOSES = 8
TXS_PER_CLOSE = 25
CAPPED_MB = 2
UNCAPPED_MB = 4096
RSS_DELTA_CAP_MB = 400.0  # loose sanity bound for a 200-tx smoke


def rss_mb() -> float:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return round(int(line.split()[1]) / 1024.0, 1)
    except OSError:
        pass
    return 0.0


def _mk_node(state_dir: str, *, load: bool, cache_mb: int,
             rotate: bool):
    from stellard_tpu.node.config import Config
    from stellard_tpu.node.node import Node

    cfg = Config(
        node_db_type="segstore",
        node_db_path=os.path.join(state_dir, "nodestore"),
        database_path=os.path.join(state_dir, "stellard.db"),
        node_db_segment_mb=1,
        tree_cache_mb=cache_mb,
    )
    if load:
        cfg.start_up = "load"
    if rotate:
        cfg.node_db_online_delete = 4
        cfg.node_db_online_delete_interval = 2
        cfg.node_db_shards = "1"
    return Node(cfg).setup()


def _flood(node, closes: int, start_seq: int) -> tuple[list[dict], int]:
    import threading

    from stellard_tpu.protocol.formats import TxType
    from stellard_tpu.protocol.keys import KeyPair
    from stellard_tpu.protocol.sfields import sfAmount, sfDestination
    from stellard_tpu.protocol.stamount import STAmount
    from stellard_tpu.protocol.sttx import SerializedTransaction

    master = KeyPair.from_passphrase("masterpassphrase")
    dests = [KeyPair.from_passphrase(f"ooc-smoke-{i}").account_id
             for i in range(8)]
    done = threading.Semaphore(0)

    def cb(tx, ter, applied):
        done.release()

    roots = []
    seq = start_seq
    for _ in range(closes):
        txs = []
        for i in range(TXS_PER_CLOSE):
            tx = SerializedTransaction.build(
                TxType.ttPAYMENT, master.account_id, seq, 10,
                {sfAmount: STAmount.from_drops(1_000_000),
                 sfDestination: dests[i % len(dests)]},
            )
            tx.sign(master)
            txs.append(tx)
            seq += 1
        for tx in txs:
            node.ops.submit_transaction(tx, cb)
        for _ in txs:
            done.acquire()
        closed, _results = node.ops.accept_ledger()
        roots.append({
            "seq": closed.seq,
            "account_hash": closed.account_hash.hex(),
            "tx_hash": closed.tx_hash.hex(),
        })
    node.close_pipeline.flush(timeout=120)
    return roots, seq


def phase_base(state_dir: str) -> None:
    node = _mk_node(state_dir, load=False, cache_mb=UNCAPPED_MB,
                    rotate=False)
    try:
        _roots, seq = _flood(node, BASE_CLOSES, 1)
        print(json.dumps({"phase": "base", "next_seq": seq}), flush=True)
    finally:
        node.stop()


def phase_run(state_dir: str, cache_mb: int, start_seq: int) -> None:
    import time

    from stellard_tpu.rpc.handlers import Context, Role, dispatch

    rss0 = rss_mb()
    node = _mk_node(state_dir, load=True, cache_mb=cache_mb, rotate=True)
    try:
        roots, _seq = _flood(node, RUN_CLOSES, start_seq)
        rss1 = rss_mb()
        # a rotation (sweep + shard seal + sql trim) must have landed:
        # drive extra empty closes until the deleter reports one
        deadline = time.time() + 60
        while time.time() < deadline:
            dj = node.online_deleter.get_json()
            floor = node.txdb.retain_floor
            if dj["sweeps_completed"] >= 1 and dj["shards_sealed"] >= 1 \
                    and floor > 1:
                break
            node.ops.accept_ledger()
            node.close_pipeline.flush(timeout=60)
            time.sleep(0.1)
        deleter = node.online_deleter.get_json()
        floor = node.txdb.retain_floor
        shard_rows = []
        shard_error = ""
        if floor > 1:
            try:
                out = dispatch(
                    Context(node, {
                        "account": _master_address(),
                        "ledger_index_min": 1,
                        "ledger_index_max": floor - 1,
                        "limit": 5,
                    }, Role.ADMIN),
                    "account_tx",
                )
                shard_rows = [
                    t for t in out.get("transactions", [])
                    if "shard" in t
                ]
            except Exception as e:  # noqa: BLE001 — reported, judged by parent
                shard_error = repr(e)[:200]
        counters = dispatch(Context(node, {}, Role.ADMIN), "get_counts")
        print(json.dumps({
            "phase": "run",
            "cache_mb": cache_mb,
            "roots": roots,
            "rss_mb_before": rss0,
            "rss_mb_after": rss1,
            "inner_cache": counters["shamap_inner_cache"],
            "delta_replay": {
                k: counters["delta_replay"][k]
                for k in ("closes", "incremental_seals",
                          "building_fold_failures")
            },
            "history_shards": counters.get("history_shards"),
            "online_delete": deleter,
            "retain_floor": floor,
            "shard_rows": len(shard_rows),
            "shard_error": shard_error,
        }), flush=True)
    finally:
        node.stop()


def _master_address() -> str:
    from stellard_tpu.protocol.keys import KeyPair

    return KeyPair.from_passphrase("masterpassphrase").human_account_id


def _spawn(args: list[str]) -> dict:
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *args],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if r.returncode != 0:
        print(r.stdout[-2000:], file=sys.stderr)
        print(r.stderr[-2000:], file=sys.stderr)
        raise RuntimeError(f"phase {args} failed rc={r.returncode}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run_smoke() -> int:
    top = tempfile.mkdtemp(prefix="oocsmoke-")
    failures: list[str] = []
    try:
        base = os.path.join(top, "base")
        os.makedirs(base)
        b = _spawn(["--phase", "base", "--dir", base])
        next_seq = int(b["next_seq"])
        runs = {}
        for name, mb in (("capped", CAPPED_MB), ("uncapped", UNCAPPED_MB)):
            d = os.path.join(top, name)
            shutil.copytree(base, d)
            runs[name] = _spawn([
                "--phase", "run", "--dir", d, "--cache-mb", str(mb),
                "--start-seq", str(next_seq),
            ])
        cap, unc = runs["capped"], runs["uncapped"]
        if cap["roots"] != unc["roots"]:
            failures.append(
                f"ROOTS DIVERGED between capped and uncapped runs: "
                f"{cap['roots'][:2]} vs {unc['roots'][:2]}"
            )
        faults = cap["inner_cache"]["faults"]
        if faults <= 0:
            failures.append(
                "anti-vacuity: capped run recorded ZERO faults — the "
                "out-of-core path never ran"
            )
        for name, run in runs.items():
            dr = run["delta_replay"]
            if dr["building_fold_failures"] or not dr["incremental_seals"]:
                failures.append(
                    f"the lazily resumed {name} node lost its incremental "
                    f"seal: {dr}"
                )
        delta = cap["rss_mb_after"] - cap["rss_mb_before"]
        if delta > RSS_DELTA_CAP_MB:
            failures.append(
                f"capped-run RSS grew {delta:.0f}MB during a 200-tx "
                f"flood (bound {RSS_DELTA_CAP_MB}MB)"
            )
        if cap["retain_floor"] <= 1:
            failures.append(
                f"online deletion never trimmed (floor="
                f"{cap['retain_floor']}) — the shard leg is vacuous"
            )
        od = cap["online_delete"]
        if od.get("shards_sealed", 0) < 1:
            failures.append(f"no shard sealed: online_delete={od}")
        if cap["shard_rows"] < 1:
            failures.append(
                f"below-floor account_tx served NO shard rows "
                f"(floor={cap['retain_floor']}, "
                f"err={cap['shard_error']!r}, "
                f"shards={cap['history_shards']})"
            )
        print(
            f"ooc smoke: roots_identical={cap['roots'] == unc['roots']} "
            f"faults={faults} rss_delta={delta:.0f}MB "
            f"floor={cap['retain_floor']} "
            f"shard_rows={cap['shard_rows']} "
            f"sealed={od.get('shards_sealed')}"
        )
        for f in failures:
            print(f"ooc smoke FAIL: {f}", file=sys.stderr)
        return 1 if failures else 0
    finally:
        shutil.rmtree(top, ignore_errors=True)


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", choices=("base", "run"), default=None)
    ap.add_argument("--dir", default="")
    ap.add_argument("--cache-mb", type=int, default=UNCAPPED_MB)
    ap.add_argument("--start-seq", type=int, default=1)
    args = ap.parse_args()
    if args.phase == "base":
        phase_base(args.dir)
        return 0
    if args.phase == "run":
        phase_run(args.dir, args.cache_mb, args.start_seq)
        return 0
    return run_smoke()


if __name__ == "__main__":
    sys.exit(main())
