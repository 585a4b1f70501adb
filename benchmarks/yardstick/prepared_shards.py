"""Prepared HISTORY SHARDS: the data set of a deployment whose node does
not make its history but imports it. Built once in a checkout, kept
under ``benchmarks/.cache/prepared/`` and read in place by every run.

A plain ``cpu``/``cpu`` node (serial apply, full seal, ``hashlib``: the
plain reference) is resumed onto a copy of the population's prepared
store (``prepared_state.ensure``: the same cache entry as the
configuration named under ``writer.state_config``, where the checkout
has it) and closes the configuration's ``history``: ``shards`` x
``ledgers_per_shard`` ledgers of ``txs_per_ledger`` sliding-window
payments (``sliding.sliding_stream``, the traffic of ``zipf.json`` at
this ledger size) and ``tip_ledgers`` more, which stay unsealed: a shard
holds what its range RETIRED, and the last range retires against the
tip. The ledgers are then sealed by the program's own sealing, one
shard a range: ``mark_live`` over the retained ledgers,
``collect_retired`` over the range, ``HistoryShardStore.seal`` with the
account index rows the writer's SQL index exports for it
(``TxDatabase.account_tx_index``), which is what ``rotate_into_shards``
and the online deleter do at a rotation. The walk runs newest range
first, so that the state's 1.4 million live nodes are marked once and
each older range adds its own to the mark; the files are then written
oldest first, so that shard ``k`` is the ``k``-th range.

Kept: the shard directory (``shards/``), the writer's UNTRIMMED
transaction database (``reference.db``: every row the writer's closes
persisted, the reference the check compares the archive's answers with)
and, in ``meta.json``, each shard's range, records, bytes and SHA-256.
The writer's nodestore is dropped: nothing reads it again.

The data set is fixed by the configuration and is the same for every
``--seed``.

Run as a script (the builder child, pinned to ``JAX_PLATFORMS=cpu`` so
it can never take the chip): ``prepared_shards.py <out_dir>``, where
``<out_dir>/config.json`` holds the configuration, the writer's INI
template and the directory of the prepared state.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

BUILDER_VERSION = 1
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)


def _say(msg: str) -> None:
    print(f"benchmark/prepared_shards: {msg}", file=sys.stderr, flush=True)


def writer_template(config: dict) -> tuple[dict, str]:
    """-> (the configuration whose prepared state the writer resumes,
    cut to what names its cache entry; its INI template with this
    configuration's ``ini_replace`` applied, as ``manifest.cell_files``
    applies it to a cell's own INI at a rehearsal)."""
    w = config["writer"]
    with open(os.path.join(BENCH, "configs", w["ini"])) as fh:
        ini = fh.read()
    for old, new in config.get("ini_replace", {}).items():
        ini = ini.replace(old, new)
    return {"name": w["state_config"],
            "population": config["population"]}, ini


def key_of(config: dict, ini_template: str) -> str:
    spec = json.dumps(
        ["shards", BUILDER_VERSION, config["population"], config["history"],
         config["writer"], ini_template],
        sort_keys=True,
    )
    return f"{config['name']}-{hashlib.sha256(spec.encode()).hexdigest()[:12]}"


def ensure(config: dict, cache_dir: str) -> str:
    """-> the directory of the prepared shards of this configuration,
    building them (and the population's store) first where the checkout
    does not have them yet."""
    from . import prepared_state

    state_config, ini = writer_template(config)
    root = os.path.join(cache_dir, "prepared")
    final = os.path.join(root, key_of(config, ini))
    if os.path.exists(os.path.join(final, "meta.json")):
        return final
    state_dir = prepared_state.ensure(state_config, ini, cache_dir)
    partial = final + ".partial"
    shutil.rmtree(partial, ignore_errors=True)
    os.makedirs(partial)
    with open(os.path.join(partial, "config.json"), "w") as fh:
        json.dump({"config": config, "ini": ini, "state_dir": state_dir}, fh)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [BENCH, REPO, os.environ.get("PYTHONPATH", "")]))
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), partial],
        env=env, stdout=sys.stderr, cwd=REPO,
    )
    if proc.returncode != 0:
        raise SystemExit(
            f"benchmark: building the prepared shards failed "
            f"(rc={proc.returncode})"
        )
    os.rename(partial, final)
    _say(f"{os.path.basename(final)} built in "
         f"{time.perf_counter() - t0:.1f}s")
    return final


def load_meta(prepared_dir: str) -> dict:
    with open(os.path.join(prepared_dir, "meta.json")) as fh:
        return json.load(fh)


def shard_path(prepared_dir: str, row: dict) -> str:
    return os.path.join(prepared_dir, "shards", row["file"])


def sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as fh:
        for chunk in iter(lambda: fh.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


# --------------------------------------------------------------------------
# the builder child


def write_history(node, pump, pop: dict, hist: dict, t0: float) -> list:
    """Close the history through the transactor -> the closed ledgers'
    sequences in order; raises unless every payment succeeded."""
    from stellard_tpu.protocol.sttx import SerializedTransaction
    from yardstick import nodedrive, sliding

    per = int(hist["txs_per_ledger"])
    ledgers = (int(hist["shards"]) * int(hist["ledgers_per_shard"])
               + int(hist["tip_ledgers"]))
    params = dict(hist, close_every=per, planted_per_1024=0)
    entries = sliding.sliding_stream(
        seed=int(hist["seed"]), pop=pop, params=params, count=ledgers * per)
    _say(f"{len(entries)} payments signed "
         f"({time.perf_counter() - t0:.0f}s)")
    seqs = []
    for k, (blob, *_rest) in enumerate(entries):
        pump.submit(SerializedTransaction.from_bytes(blob))
        if (k + 1) % per:
            continue
        closed, results, _ms = pump.close()
        if len(results) != per or any(
                int(t) != nodedrive.TES_SUCCESS for t in results.values()):
            raise SystemExit(
                f"history ledger {closed.seq} is not {per} successes")
        seqs.append(closed.seq)
        if len(seqs) % int(hist["ledgers_per_shard"]) == 0:
            _say(f"{len(seqs)}/{ledgers} ledgers closed "
                 f"({time.perf_counter() - t0:.0f}s)")
    node.close_pipeline.flush(timeout=600)
    return seqs


def seal_ranges(node, shard_dir: str, ranges: list, tip: list,
                t0: float) -> list:
    """Seal each of ``ranges`` (lists of header dicts, oldest range
    first) against everything newer -> the shard store's rows."""
    from stellard_tpu.nodestore.shards import (
        HistoryShardStore, collect_retired, mark_live,
    )

    db = node.nodestore

    def fetch(h: bytes):
        obj = db.fetch(h, populate_cache=False)
        return obj.data if obj is not None else None

    live: set = set()
    mark_live(fetch, tip, live)
    _say(f"{len(live)} live nodes marked ({time.perf_counter() - t0:.0f}s)")
    retired = []
    for headers in reversed(ranges):
        retired.append(collect_retired(fetch, headers, live))
        mark_live(fetch, headers, live)
    retired.reverse()
    store = HistoryShardStore(shard_dir)
    try:
        for headers, records in zip(ranges, retired):
            lo, hi = headers[0]["seq"], headers[-1]["seq"]
            store.seal(lo, hi, records, node.txdb.account_tx_index(lo, hi),
                       first_hash=headers[0]["hash"],
                       last_hash=headers[-1]["hash"])
            _say(f"shard [{lo}, {hi}] sealed: {len(records)} records "
                 f"({time.perf_counter() - t0:.0f}s)")
        return store.shards()
    finally:
        store.close()


def build(out_dir: str) -> None:
    from yardstick import nodedrive, prepared

    with open(os.path.join(out_dir, "config.json")) as fh:
        spec = json.load(fh)
    config, template = spec["config"], spec["ini"]
    pop, hist = config["population"], config["history"]
    t0 = time.perf_counter()

    work_root = os.path.join(out_dir, "writer")
    workdir, state_meta = prepared.copy_for_run(spec["state_dir"], work_root)
    ini = nodedrive.plain_reference_ini(nodedrive.ini_text(
        template, workdir=os.path.join(workdir, "db"), start_up="load"))
    for old, new in config["writer"].get("ini_replace", {}).items():
        ini = ini.replace(old, new)
    node = nodedrive.boot(ini, serve=False)
    try:
        ok, detail = nodedrive.host_libraries_ok()
        if not ok:
            raise SystemExit(f"host libraries not built: {detail}")
        resumed = node.ledger_master.closed_ledger()
        if resumed.hash().hex() != state_meta["last_ledger"]["hash"]:
            raise SystemExit("the writer did not resume the prepared state")
        pump = nodedrive.Pump(node, window=96,
                              closes_done=state_meta["closes_done"])
        seqs = write_history(node, pump, pop, hist, t0)
        headers = [node.txdb.get_ledger_header(seq=s) for s in seqs]
        per = int(hist["ledgers_per_shard"])
        n_sealed = int(hist["shards"]) * per
        ranges = [headers[i: i + per] for i in range(0, n_sealed, per)]
        rows = seal_ranges(node, os.path.join(out_dir, "shards"), ranges,
                           headers[n_sealed:], t0)
        db_path = node.config.database_path
    finally:
        node.stop()
    for suffix in ("", "-wal", "-shm"):
        if os.path.exists(db_path + suffix):
            shutil.copy(db_path + suffix,
                        os.path.join(out_dir, "reference.db" + suffix))
    shutil.rmtree(work_root)

    for row in rows:
        row["file"] = f"shard-{row['id']:06d}.shard"
        row["sha256"] = sha256_file(shard_path(out_dir, row))
        row["txs"] = per * int(hist["txs_per_ledger"])
    meta = {
        "population": pop,
        "history": hist,
        "shards": rows,
        "first_seq": seqs[0],
        "last_sealed_seq": seqs[n_sealed - 1],
        "tip_seq": seqs[-1],
        "records": sum(r["records"] for r in rows),
        "bytes": sum(r["bytes"] for r in rows),
        "build_s": round(time.perf_counter() - t0, 1),
    }
    with open(os.path.join(out_dir, "meta.json"), "w") as fh:
        json.dump(meta, fh)
    _say(f"{len(rows)} shards, {meta['records']} records, "
         f"{meta['bytes']} bytes, {meta['build_s']}s")


if __name__ == "__main__":
    build(sys.argv[1])
