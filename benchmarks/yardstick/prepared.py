"""Prepared state and history: built once in a checkout by the plain
reference (a ``cpu``/``cpu`` node: serial apply, full seal, ``hashlib``),
kept under ``benchmarks/.cache/prepared/`` and copied for every run.

The population is funded through the transactor (payments from the
master account), because that is the one way to make state that does
not depend on the program's internals. A history, where the
configuration has one, is the flood generator's traffic closed into
spans of ledgers on top of that state.

Run as a script (the builder child, pinned to ``JAX_PLATFORMS=cpu`` so
it can never take the chip): ``prepared.py <out_dir>``, where
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

BUILDER_VERSION = 3
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)


def _say(msg: str) -> None:
    print(f"benchmark/prepared: {msg}", file=sys.stderr, flush=True)


def key_of(config: dict, ini_template: str) -> str:
    spec = json.dumps(
        [BUILDER_VERSION, config["population"], config.get("history"),
         ini_template],
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


def copy_for_run(prepared_dir: str, work_root: str) -> tuple[str, dict]:
    """-> (a fresh working copy of the prepared store, its meta)."""
    import tempfile

    os.makedirs(work_root, exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="run-", dir=work_root)
    shutil.copytree(os.path.join(prepared_dir, "db"),
                    os.path.join(workdir, "db"))
    with open(os.path.join(prepared_dir, "meta.json")) as fh:
        meta = json.load(fh)
    return workdir, meta


# --------------------------------------------------------------------------
# the builder child


def build(out_dir: str) -> None:
    from yardstick import nodedrive, workload

    with open(os.path.join(out_dir, "config.json")) as fh:
        spec = json.load(fh)
    config, template = spec["config"], spec["ini"]
    pop = config["population"]
    hist = config.get("history")

    from stellard_tpu.protocol.sttx import SerializedTransaction

    workdir = os.path.join(out_dir, "db")
    os.makedirs(workdir)
    ini = nodedrive.plain_reference_ini(
        nodedrive.ini_text(template, workdir=workdir, start_up="fresh")
    )
    t0 = time.perf_counter()
    node = nodedrive.boot(ini, serve=False)
    try:
        ok, detail = nodedrive.host_libraries_ok()
        if not ok:
            raise SystemExit(f"host libraries not built: {detail}")
        pump = nodedrive.Pump(node, window=96)
        n = int(pop["accounts"])
        nodedrive.fund(pump, pop, nodedrive.funding_stream(pop))
        _say(f"funded {n} accounts ({time.perf_counter() - t0:.0f}s)")

        meta = {
            "population": pop,
            "funded_through_ledger": pump.ledgers[-1][0],
            "spans": [],
        }
        if hist:
            entries = workload.payment_stream(
                seed=int(hist["seed"]), pop=pop, params=hist,
                count=(int(hist["spans"]) * int(hist["ledgers_per_span"])
                       * int(hist["txs_per_ledger"])))
            in_ledger = 0
            span: list = []
            for blob, _planted, _s, _d, _txid in entries:
                pump.submit(SerializedTransaction.from_bytes(blob))
                in_ledger += 1
                if in_ledger == int(hist["txs_per_ledger"]):
                    closed, results, _ms = pump.close()
                    in_ledger = 0
                    if len(results) != int(hist["txs_per_ledger"]) or any(
                        int(t) != nodedrive.TES_SUCCESS
                        for t in results.values()
                    ):
                        raise SystemExit(
                            f"history ledger {closed.seq} is not "
                            f"{hist['txs_per_ledger']} successes"
                        )
                    span.append({"seq": closed.seq,
                                 "hash": closed.hash().hex()})
                    if len(span) == int(hist["ledgers_per_span"]):
                        meta["spans"].append(span)
                        span = []
                        _say(f"history span {len(meta['spans'])}/"
                             f"{hist['spans']} "
                             f"({time.perf_counter() - t0:.0f}s)")
        node.close_pipeline.flush(timeout=600)
        meta["closes_done"] = pump.closes_done
        meta["last_ledger"] = {"seq": pump.ledgers[-1][0],
                               "hash": pump.ledgers[-1][1].hex()}
        meta["build_s"] = round(time.perf_counter() - t0, 1)
    finally:
        node.stop()
    with open(os.path.join(out_dir, "meta.json"), "w") as fh:
        json.dump(meta, fh)


if __name__ == "__main__":
    build(sys.argv[1])
