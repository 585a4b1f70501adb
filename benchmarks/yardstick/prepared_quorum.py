"""The ledger a quorum starts from: the account roots of the whole
population written into the state tree by the plain path, one ledger
closed over it and saved as a node saves a close, in the manner of
``prepared_state.py`` (whose ``account_roots`` derives the entries).
Built once in a checkout under ``benchmarks/.cache/prepared/`` by a
child pinned to ``JAX_PLATFORMS=cpu`` and copied once a VALIDATOR a
run, so that all of them boot ``start_up=load`` onto the SAME ledger,
byte for byte, and the first round opens over it.

The ledger is closed at ``CLOSE_TIME``, a network time that lies in the
past of any run (``prepared_state`` pins 900,000,000, which a validator
reading the wall clock would take for a parent closed in its future).

Run as a script: ``prepared_quorum.py <out_dir>``, where
``<out_dir>/config.json`` holds the configuration and the INI text of
one validator, its placeholders filled in.
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
CLOSE_TIME = 700_000_000  # 2022-03-07, seconds since 2000
NET_KEYS = ("validation_seed", "validators", "peer_port", "ips", "rpc_port",
            "websocket_port")


def _say(msg: str) -> None:
    print(f"benchmark/prepared_quorum: {msg}", file=sys.stderr, flush=True)


def validator_keys(config: dict) -> list:
    """The validators' key pairs, from the configuration's fixed
    passphrases."""
    from stellard_tpu.protocol.keys import KeyPair

    net = config["net"]
    return [KeyPair.from_passphrase(f"{net['key_passphrase']}{i}")
            for i in range(int(net["validators"]))]


def net_ini(template: str, i: int, keys: list, peer_ports: list,
            rpc_port: int = 0, websocket_port: int = 0) -> str:
    """``template`` with validator ``i``'s identity, its trust in the
    others and their addresses filled in (``{workdir}`` and
    ``{start_up}`` are left for ``nodedrive.ini_text``)."""
    others = [j for j in range(len(keys)) if j != i]
    fill = {
        "validation_seed": keys[i].human_seed,
        "validators": "\n".join(keys[j].human_node_public for j in others),
        "peer_port": str(peer_ports[i]),
        "ips": "\n".join(f"127.0.0.1 {peer_ports[j]}" for j in others),
        "rpc_port": str(rpc_port),
        "websocket_port": str(websocket_port),
    }
    out = template
    for k in NET_KEYS:
        out = out.replace("{" + k + "}", fill[k])
    return out


def key_of(config: dict) -> str:
    spec = json.dumps(["quorum", BUILDER_VERSION, CLOSE_TIME,
                       config["population"]], sort_keys=True)
    return f"{config['name']}-{hashlib.sha256(spec.encode()).hexdigest()[:12]}"


def ensure(config: dict, ini_template: str, cache_dir: str) -> str:
    """-> the directory of the prepared store for this configuration,
    building it first where the checkout does not have it yet."""
    root = os.path.join(cache_dir, "prepared")
    final = os.path.join(root, key_of(config))
    if os.path.exists(os.path.join(final, "meta.json")):
        return final
    os.makedirs(root, exist_ok=True)
    partial = final + ".partial"
    shutil.rmtree(partial, ignore_errors=True)
    os.makedirs(partial)
    n = int(config["net"]["validators"])
    ini = net_ini(ini_template, 0, validator_keys(config), [1] * n)
    with open(os.path.join(partial, "config.json"), "w") as fh:
        json.dump({"config": config, "ini": ini}, fh)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [BENCH, REPO, os.environ.get("PYTHONPATH", "")]))
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), partial],
        env=env, stdout=sys.stderr, cwd=REPO,
    )
    if proc.returncode != 0:
        raise SystemExit(
            f"benchmark: building the quorum's first ledger failed "
            f"(rc={proc.returncode})"
        )
    os.rename(partial, final)
    _say(f"{os.path.basename(final)} built in "
         f"{time.perf_counter() - t0:.1f}s")
    return final


def copy_for(prepared_dir: str, workdir: str) -> dict:
    """One validator's own copy of the prepared store at
    ``workdir/db`` -> the store's meta."""
    shutil.copytree(os.path.join(prepared_dir, "db"),
                    os.path.join(workdir, "db"))
    with open(os.path.join(prepared_dir, "meta.json")) as fh:
        return json.load(fh)


# --------------------------------------------------------------------------
# the builder child


def build(out_dir: str) -> None:
    from yardstick import nodedrive
    from yardstick.prepared_state import account_roots

    with open(os.path.join(out_dir, "config.json")) as fh:
        spec = json.load(fh)
    config, ini = spec["config"], spec["ini"]
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
        nodedrive.ini_text(ini, workdir=workdir, start_up="load"))
    t0 = time.perf_counter()

    master = KeyPair.from_passphrase(MASTER_PASSPHRASE).account_id
    genesis = Ledger.genesis(master)
    genesis.close(0, genesis.close_resolution)
    genesis.accepted = True
    led = genesis.open_successor()
    led.state_map.bulk_update(sets=[
        SHAMapItem(tag, blob)
        for tag, blob in account_roots(pop["name"], drops, (0, n))])
    root_index = indexes.account_root_index(master)
    sle = led.read_entry(root_index)
    sle[sfBalance] = STAmount.from_drops(sle[sfBalance].drops() - n * drops)
    led.write_entry(root_index, sle)
    led.close(CLOSE_TIME, led.close_resolution)
    led.accepted = True

    db = make_database(type=cfg.node_db_type, path=cfg.node_db_path,
                       durability="async", async_writes=False)
    try:
        genesis.save(db)
        led.save(db)
    finally:
        db.close()
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
        "last_ledger": {"seq": led.seq, "hash": led.hash().hex()},
        "store_bytes": size,
        "build_s": round(time.perf_counter() - t0, 1),
    }
    with open(os.path.join(out_dir, "meta.json"), "w") as fh:
        json.dump(meta, fh)
    _say(f"{n} accounts, {size} bytes, {meta['build_s']}s")


if __name__ == "__main__":
    build(sys.argv[1])
