"""A prepared STATE too large to fund through the transactor in set-up:
the account roots of the whole population written straight into the
state tree by the plain path (``SHAMap.bulk_update``, ``hashlib``), one
ledger closed over it and saved as a node saves a close (``Ledger.save``
into the segstore, the txdb header, the CLF commit), so that
``start_up=load`` resumes it as it resumes any stored ledger. Built once
in a checkout, kept under ``benchmarks/.cache/prepared/`` beside the
histories of ``prepared.py`` and copied for every run by
``prepared.copy_for_run``.

Account ``i`` is the key pair of ``<population>:<i>`` (as
``workload.population_keys`` derives it) with ``funded_drops``; the
master account keeps the rest, so the total of coins is the genesis
total. The store is the same for every ``--seed``.

Run as a script (the builder child, pinned to ``JAX_PLATFORMS=cpu`` so
it can never take the chip): ``prepared_state.py <out_dir>``, where
``<out_dir>/config.json`` holds the configuration and its INI template.
"""

from __future__ import annotations

import functools
import hashlib
import json
import multiprocessing
import os
import shutil
import subprocess
import sys
import time
from concurrent.futures import ProcessPoolExecutor

BUILDER_VERSION = 1
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
CHUNK = 16384  # accounts a worker derives at a time


def _say(msg: str) -> None:
    print(f"benchmark/prepared_state: {msg}", file=sys.stderr, flush=True)


def key_of(config: dict, ini_template: str) -> str:
    spec = json.dumps(
        ["state", BUILDER_VERSION, config["population"], ini_template],
        sort_keys=True,
    )
    return f"{config['name']}-{hashlib.sha256(spec.encode()).hexdigest()[:12]}"


def ensure(config: dict, ini_template: str, cache_dir: str) -> str:
    """-> the directory of the prepared store for this configuration,
    building it first where the checkout does not have it yet."""
    root = os.path.join(cache_dir, "prepared")
    final = os.path.join(root, key_of(config, ini_template))
    if os.path.exists(os.path.join(final, "meta.json")):
        return final
    os.makedirs(root, exist_ok=True)
    partial = final + ".partial"
    shutil.rmtree(partial, ignore_errors=True)
    os.makedirs(partial)
    with open(os.path.join(partial, "config.json"), "w") as fh:
        json.dump({"config": config, "ini": ini_template}, fh)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [BENCH, REPO, os.environ.get("PYTHONPATH", "")]))
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), partial],
        env=env, stdout=sys.stderr, cwd=REPO,
    )
    if proc.returncode != 0:
        raise SystemExit(
            f"benchmark: building the prepared state failed "
            f"(rc={proc.returncode})"
        )
    os.rename(partial, final)
    _say(f"{os.path.basename(final)} built in "
         f"{time.perf_counter() - t0:.1f}s")
    return final


# --------------------------------------------------------------------------
# the builder child


def account_roots(population: str, drops: int, bounds: tuple) -> list:
    """-> [(state index, serialized account root)] of the accounts
    bounds[0]..bounds[1]-1, each as a payment that creates it leaves it
    (sequence 1, no owner)."""
    from stellard_tpu.protocol.formats import LedgerEntryType
    from stellard_tpu.protocol.keys import KeyPair
    from stellard_tpu.protocol.sfields import (
        sfAccount, sfBalance, sfFlags, sfLedgerEntryType, sfOwnerCount,
        sfPreviousTxnID, sfPreviousTxnLgrSeq, sfSequence,
    )
    from stellard_tpu.protocol.stamount import STAmount
    from stellard_tpu.protocol.stobject import STObject
    from stellard_tpu.state import indexes

    balance = STAmount.from_drops(drops)
    out = []
    for i in range(*bounds):
        account_id = KeyPair.from_passphrase(f"{population}:{i}").account_id
        sle = STObject()
        sle[sfLedgerEntryType] = int(LedgerEntryType.ltACCOUNT_ROOT)
        sle[sfAccount] = account_id
        sle[sfBalance] = balance
        sle[sfSequence] = 1
        sle[sfFlags] = 0
        sle[sfOwnerCount] = 0
        sle[sfPreviousTxnID] = b"\x00" * 32
        sle[sfPreviousTxnLgrSeq] = 0
        out.append((indexes.account_root_index(account_id), sle.serialize()))
    return out


def build(out_dir: str) -> None:
    from yardstick import nodedrive, workload

    with open(os.path.join(out_dir, "config.json")) as fh:
        spec = json.load(fh)
    config, template = spec["config"], spec["ini"]
    pop = config["population"]
    n, drops = int(pop["accounts"]), int(pop["funded_drops"])

    from stellard_tpu.node.config import Config
    from stellard_tpu.node.node import MASTER_PASSPHRASE
    from stellard_tpu.node.txdb import TxDatabase
    from stellard_tpu.nodestore.core import make_database
    from stellard_tpu.protocol.keys import KeyPair
    from stellard_tpu.protocol.sfields import sfBalance
    from stellard_tpu.protocol.stamount import STAmount
    from stellard_tpu.state import indexes
    from stellard_tpu.state.clf import CLFMirror, LedgerSqlDatabase
    from stellard_tpu.state.ledger import Ledger
    from stellard_tpu.state.shamap import SHAMapItem

    workdir = os.path.join(out_dir, "db")
    os.makedirs(workdir)
    cfg = Config.from_ini(
        nodedrive.ini_text(template, workdir=workdir, start_up="load"))
    t0 = time.perf_counter()

    master = KeyPair.from_passphrase(MASTER_PASSPHRASE).account_id
    genesis = Ledger.genesis(master)
    genesis.close(0, genesis.close_resolution)
    genesis.accepted = True
    led = genesis.open_successor()
    bounds = [(lo, min(lo + CHUNK, n)) for lo in range(0, n, CHUNK)]
    with ProcessPoolExecutor(
            max_workers=min(len(bounds), os.cpu_count() or 1),
            mp_context=multiprocessing.get_context("spawn")) as pool:
        for k, roots in enumerate(pool.map(
                functools.partial(account_roots, pop["name"], drops),
                bounds)):
            led.state_map.bulk_update(
                sets=[SHAMapItem(tag, blob) for tag, blob in roots])
            if (k + 1) % 16 == 0 or k + 1 == len(bounds):
                _say(f"{bounds[k][1]}/{n} account roots "
                     f"({time.perf_counter() - t0:.0f}s)")
    root_index = indexes.account_root_index(master)
    sle = led.read_entry(root_index)
    sle[sfBalance] = STAmount.from_drops(sle[sfBalance].drops() - n * drops)
    led.write_entry(root_index, sle)
    led.close(workload.PIN_CLOSE_TIME, led.close_resolution)
    led.accepted = True
    ledger_hash = led.hash()
    _say(f"sealed ({time.perf_counter() - t0:.0f}s)")

    db = make_database(type=cfg.node_db_type, path=cfg.node_db_path,
                       durability="async", async_writes=False)
    try:
        genesis.save(db)
        led.save(db)
    finally:
        db.close()
    _say(f"saved ({time.perf_counter() - t0:.0f}s)")
    txdb = TxDatabase(cfg.database_path)
    try:
        txdb.save_ledger_header(genesis)
        txdb.save_ledger_header(led)
    finally:
        txdb.close()
    clf_db = LedgerSqlDatabase(cfg.database_path + ".clf")
    try:
        CLFMirror(clf_db).commit_ledger_close(led)
    finally:
        clf_db.close()

    size = sum(os.path.getsize(os.path.join(d, f))
               for d, _dirs, files in os.walk(workdir) for f in files)
    meta = {
        "population": pop,
        "closes_done": 1,
        "last_ledger": {"seq": led.seq, "hash": ledger_hash.hex()},
        "store_bytes": size,
        "build_s": round(time.perf_counter() - t0, 1),
    }
    with open(os.path.join(out_dir, "meta.json"), "w") as fh:
        json.dump(meta, fh)
    _say(f"{n} accounts, {size} bytes, {meta['build_s']}s")


if __name__ == "__main__":
    build(sys.argv[1])
