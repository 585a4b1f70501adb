"""The prepared store of the exchange deployment: the market of
``yardstick/exchange.py`` made THROUGH THE NODE'S OWN ENGINE. A plain
node (``nodedrive.plain_reference_ini``'s ``cpu``/``hashlib`` arms, and
``PLAIN_PATH``: no speculation, no delta replay, the full seal) is
booted fresh and sent the set-up transactions (the master's funding payments,
every TrustSet, the gateways' issuing payments, the makers' standing
OfferCreates), a close every ``funding_per_close``; each must come back
``tesSUCCESS`` from its close. Owner directories, book directories,
owner counts and reserves are then the engine's own arithmetic, and the
store is what the node's close pipeline wrote (segstore, txdb, the CLF
commit with its row mirror), so ``start_up=load`` resumes it as it
resumes any stored ledger and the book index finds the mirror's offer
keys. Built once in a checkout, kept under
``benchmarks/.cache/prepared/`` and copied for every run by
``prepared.copy_for_run``; the same for every ``--seed``.

Run as a script (the builder child, pinned to ``JAX_PLATFORMS=cpu`` so
it can never take the chip): ``prepared_exchange.py <out_dir>``, where
``<out_dir>/config.json`` holds the configuration and its INI template.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

BUILDER_VERSION = 2
# the plain path: every transaction applied once, serially, at its close
PLAIN_PATH = "\n[tree]\nincremental=0\n\n[close]\ndelta_replay=0\n"
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)


def _say(msg: str) -> None:
    print(f"benchmark/prepared_exchange: {msg}", file=sys.stderr, flush=True)


def key_of(config: dict, ini_template: str) -> str:
    spec = json.dumps(
        ["exchange", BUILDER_VERSION, config["population"], ini_template],
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
            f"benchmark: building the exchange's store failed "
            f"(rc={proc.returncode})"
        )
    os.rename(partial, final)
    _say(f"{os.path.basename(final)} built in "
         f"{time.perf_counter() - t0:.1f}s")
    return final


# --------------------------------------------------------------------------
# the builder child


def build(out_dir: str) -> None:
    from yardstick import exchange, nodedrive

    with open(os.path.join(out_dir, "config.json")) as fh:
        spec = json.load(fh)
    config, template = spec["config"], spec["ini"]
    pop = config["population"]
    market = exchange.Market(pop)
    per_close = int(pop["funding_per_close"])
    fee = int(pop["fee_drops"])

    workdir = os.path.join(out_dir, "db")
    os.makedirs(workdir)
    if "[tree]" in template or "[close]" in template:
        raise SystemExit("the builder adds [tree] and [close] itself")
    ini = nodedrive.plain_reference_ini(nodedrive.ini_text(
        template, workdir=workdir, start_up="fresh")) + PLAIN_PATH
    t0 = time.perf_counter()
    node = nodedrive.boot(ini, serve=False)
    try:
        ok, detail = nodedrive.host_libraries_ok()
        if not ok:
            raise SystemExit(f"host libraries not built: {detail}")
        genesis_coins = node.ledger_master.closed_ledger().tot_coins
        pump = nodedrive.Pump(node, window=96)
        sent = {}
        pending: list = []

        def close() -> None:
            _closed, results, _ms = pump.close()
            bad = [(t.hex()[:16], int(results.get(t, -1))) for t in pending
                   if int(results.get(t, -1)) != nodedrive.TES_SUCCESS]
            if bad:
                raise SystemExit(
                    f"benchmark: {len(bad)} set-up transactions did not "
                    f"succeed: {bad[:8]}")
            pending.clear()

        phase = None
        for name, tx in exchange.setup_stream(market, fee):
            if name != phase:
                # a phase reads what the one before it wrote: close first
                if pending:
                    close()
                if phase is not None:
                    _say(f"{phase}: {sent[phase]} transactions "
                         f"({time.perf_counter() - t0:.0f}s)")
                phase = name
            pump.submit(tx)
            pending.append(tx.txid())
            sent[name] = sent.get(name, 0) + 1
            if len(pending) >= per_close:
                close()
        if pending:
            close()
        _say(f"{phase}: {sent[phase]} transactions "
             f"({time.perf_counter() - t0:.0f}s)")
        node.close_pipeline.flush(timeout=600)
        last = node.ledger_master.closed_ledger()
        meta = {
            "population": pop,
            "closes_done": pump.closes_done,
            "last_ledger": {"seq": pump.ledgers[-1][0],
                            "hash": pump.ledgers[-1][1].hex()},
            "setup_transactions": sent,
            "genesis_coins": genesis_coins,
            "coins": last.tot_coins,
            "fees_burned": sum(sent.values()) * fee,
        }
    finally:
        node.stop()
    meta["store_bytes"] = sum(
        os.path.getsize(os.path.join(d, f))
        for d, _dirs, files in os.walk(workdir) for f in files)
    meta["build_s"] = round(time.perf_counter() - t0, 1)
    with open(os.path.join(out_dir, "meta.json"), "w") as fh:
        json.dump(meta, fh)
    _say(f"{sum(sent.values())} transactions, {meta['store_bytes']} bytes, "
         f"{meta['build_s']}s")


if __name__ == "__main__":
    build(sys.argv[1])
