"""Driving a node in-process: the sliding submission window (copied from
``bench.py:_drive_node``), closes on a pinned schedule, RPC read-backs
(copied from ``chip_smoke.py``) and the re-close of stored ledgers on
the plain path. Only the program's public entry points are imported:
``Config.from_ini``, ``Node``, ``replay_ledger``, ``make_database``."""

from __future__ import annotations

import json
import os
import random
import re
import threading
import time
import urllib.request

from . import workload

TES_SUCCESS = 0


def ini_text(template: str, *, workdir: str, start_up: str) -> str:
    return template.replace("{workdir}", workdir).replace(
        "{start_up}", start_up
    )


def plain_reference_ini(text: str) -> str:
    """The same deployment on the plain arms: both crypto sections become
    ``type=cpu`` (serial apply, full seal, ``hashlib``)."""
    out = text
    for section in ("signature_backend", "hash_backend"):
        out = re.sub(
            rf"(\[{section}\]\n)(?:[^\[\n][^\n]*\n)*",
            r"\1type=cpu\n",
            out,
        )
    return out


def boot(text: str, *, serve: bool):
    """-> a set-up ``Node`` from INI text (the --conf parse path). Its
    device prewarm runs in the background: ``wait_warm`` joins it."""
    from stellard_tpu.node.config import Config
    from stellard_tpu.node.node import Node

    node = Node(Config.from_ini(text)).setup()
    if serve:
        node.serve()
    return node


def wait_warm(node) -> None:
    if node.verify_prewarm is not None:
        node.verify_prewarm.join()


def counters(verify_plane, hasher, node=None) -> tuple[dict, dict]:
    """-> (flat counters, XLA programs by name) as the program counts
    them now; a window's numbers are the difference of two snapshots.
    With a ``node``, its intake's counts too: submissions shed, bad
    signatures refused, and how often the load manager raised the local
    fee by a quarter (``LoadFeeTrack.raise_count``)."""
    from stellard_tpu.utils.xlacache import COMPILES

    vj = verify_plane.get_json()
    out = {f"verify.{k}": vj.get(k, 0) for k in (
        "batches", "verified", "device_batches", "cpu_batches",
        "device_sigs", "cpu_sigs", "cpu_eligible_batches")}
    hj = getattr(hasher, "get_json", None)
    hj = hj() if hj is not None else {}
    out["hash.device_nodes"] = hj.get("device_nodes", 0)
    out["hash.host_nodes"] = hj.get(
        "host_nodes", getattr(hasher, "host_nodes", 0))
    if node is not None:
        out["ops.shed"] = node.ops.stats.get("shed", 0)
        out["ops.bad_sig"] = node.ops.stats.get("bad_sig", 0)
        out["load.fee_raises"] = node.fee_track.raise_count
    xs = COMPILES.snapshot()
    return out, xs["programs"]


def delta(after: tuple, before: tuple) -> dict:
    """Counters of a window. XLA programs become ``xla.compiled`` (built
    by the compiler, cache hits excluded), ``xla.loaded`` (cache hits)
    and ``xla.compile_s`` (the seconds of the programs that were built;
    where one name both hit and missed, its seconds are shared by count).
    ``xla.programs`` lists the names that were built, with counts."""
    (ca, pa), (cb, pb) = after, before
    out = {k: ca[k] - cb.get(k, 0) for k in ca}
    compiled = loaded = 0
    seconds = 0.0
    names = {}
    for name, a in pa.items():
        b = pb.get(name, {"requests": 0, "cache_hits": 0, "seconds": 0.0})
        req = a["requests"] - b["requests"]
        hit = a["cache_hits"] - b["cache_hits"]
        if req <= 0:
            continue
        built = req - hit
        compiled += built
        loaded += hit
        if built:
            seconds += (a["seconds"] - b["seconds"]) * built / req
            names[name] = built
    out["xla.compiled"] = compiled
    out["xla.loaded"] = loaded
    out["xla.compile_s"] = seconds
    out["xla.programs"] = names
    return out


def host_libraries_ok() -> tuple[bool, dict]:
    """Both host libraries loaded and built clean; otherwise every number
    of the run would be the pure-Python arm."""
    from stellard_tpu import native

    detail = {
        "libstellard_native": native.load_native() is not None,
        "_stser": native.load_stser() is not None,
        "build_errors": dict(native.build_errors),
    }
    ok = (detail["libstellard_native"] and detail["_stser"]
          and not detail["build_errors"])
    return ok, detail


class Pump:
    """Closed loop of ``window`` clients on the asynchronous intake: at
    most ``window`` submissions unacknowledged (below TX_BACKLOG_SHED,
    so the shed gate never drops one and the run is deterministic), a
    close on the pinned schedule whenever the caller asks."""

    def __init__(self, node, window: int, closes_done: int = 0):
        self.node = node
        self.window = window
        self.closes_done = closes_done
        self._slots = threading.Semaphore(window)
        self.outcomes: dict[bytes, tuple[int, bool]] = {}
        self.close_ms: list[float] = []
        self.ledgers: list[tuple[int, bytes, int]] = []  # seq, hash, txs
        node.ops.network_time = lambda: (
            workload.PIN_CLOSE_TIME + self.closes_done * workload.CLOSE_STEP_S
        )

    def _cb(self, tx, ter, applied) -> None:
        self.outcomes[tx.txid()] = (int(ter), bool(applied))
        self._slots.release()

    def submit(self, tx) -> None:
        self._slots.acquire()
        self.node.ops.submit_transaction(tx, self._cb)

    def drain(self) -> None:
        for _ in range(self.window):
            self._slots.acquire()
        for _ in range(self.window):
            self._slots.release()

    def close(self):
        """Drain, then one close: -> (ledger, results, milliseconds)."""
        self.drain()
        t0 = time.perf_counter()
        closed, results = self.node.ops.accept_ledger()
        ms = (time.perf_counter() - t0) * 1000.0
        self.closes_done += 1
        self.close_ms.append(ms)
        self.ledgers.append((closed.seq, closed.hash(), len(results)))
        return closed, results, ms


def funding_stream(pop: dict) -> list[bytes]:
    """The signed payments by which the master account funds the whole
    population, in index order."""
    from stellard_tpu.node.node import MASTER_PASSPHRASE
    from stellard_tpu.protocol.formats import TxType
    from stellard_tpu.protocol.keys import KeyPair
    from stellard_tpu.protocol.sfields import sfAmount, sfDestination
    from stellard_tpu.protocol.stamount import STAmount
    from stellard_tpu.protocol.sttx import SerializedTransaction

    master = KeyPair.from_passphrase(MASTER_PASSPHRASE)
    amount = STAmount.from_drops(int(pop["funded_drops"]))
    out = []
    ids = workload.population_ids(pop["name"], int(pop["accounts"]))
    for i, dest in enumerate(ids):
        tx = SerializedTransaction.build(
            TxType.ttPAYMENT, master.account_id, 1 + i, int(pop["fee_drops"]),
            {sfAmount: amount, sfDestination: dest},
        )
        tx.sign(master)
        out.append(tx.serialize())
    return out


def fund(pump: "Pump", pop: dict, blobs: list[bytes]) -> None:
    """Submit the funding payments through the transactor, a close every
    ``funding_per_close``; raises unless every one succeeded."""
    from stellard_tpu.protocol.sttx import SerializedTransaction

    per_close = int(pop["funding_per_close"])
    txids = []
    for i, blob in enumerate(blobs):
        tx = SerializedTransaction.from_bytes(blob)
        txids.append(tx.txid())
        pump.submit(tx)
        if (i + 1) % per_close == 0 or i + 1 == len(blobs):
            pump.close()
    bad = [pump.outcomes[t] for t in txids
           if pump.outcomes[t] != (TES_SUCCESS, True)]
    if bad:
        raise SystemExit(f"benchmark: {len(bad)} funding payments failed: "
                         f"{sorted(set(bad))[:8]}")


def start_funded_node(ctx, window: int, sign_traffic):
    """A fresh node of the cell's configuration with its population
    funded: -> (node, pump, ini, whatever ``sign_traffic()`` returned).
    Funding and signing run while the device prewarm loads its program
    (31-33 s from a warm cache; the node serves from the host arm
    meanwhile, as a deployment does), so set-up is the longer of the
    two and not their sum."""
    pop = ctx.config["population"]
    workdir = os.path.join(ctx.work_root, "db")
    os.makedirs(workdir)
    ini = ini_text(ctx.ini_template, workdir=workdir, start_up="fresh")
    t0 = time.perf_counter()
    node = boot(ini, serve=True)
    marks = [("boot", time.perf_counter() - t0)]
    try:
        pump = Pump(node, window)
        fund(pump, pop, funding_stream(pop))
        node.close_pipeline.flush(timeout=300)
        marks.append(("funded", time.perf_counter() - t0))
        traffic = sign_traffic()
        marks.append(("signed", time.perf_counter() - t0))
        wait_warm(node)
        marks.append(("warm", time.perf_counter() - t0))
        ctx.say("set-up, seconds from node boot: " + ", ".join(
            f"{k} {v:.1f}" for k, v in marks))
    except BaseException:
        node.stop()
        raise
    return node, pump, ini, traffic


def check_device_path(ctx, node, entries: list, cap, problems: list) -> None:
    """Behind the window, in every run: the first ``device_check_sigs``
    signed transactions of the run's own traffic, planted ones included,
    go to the node's verify plane as ONE batch (``verify_many``, the
    entry catch-up uses), and the verdicts must be exactly the planted
    pattern. The plane's cost router decides where the batch runs, as it
    does for the traffic; at a width of the deployed program it picks
    the chip. Under the default router a node's steady traffic (batches
    of tens of signatures) never reaches the chip, so without this check
    nothing in a node cell would show that the device plane the
    configuration names is alive and answers right on this traffic. A
    traced run holds its capture open over the check, so the capture has
    device operations; the per-layer metrics are cut to the window."""
    from stellard_tpu.crypto.backend import VerifyRequest
    from stellard_tpu.protocol.sttx import SerializedTransaction

    picked = entries[:int(ctx.traffic["device_check_sigs"])]
    requests = []
    for blob, *_rest in picked:
        tx = SerializedTransaction.from_bytes(blob)
        requests.append(VerifyRequest(
            tx.signing_pub_key, tx.signing_hash(), tx.signature))
    plane = node.verify_plane
    on_chip = plane.device_sigs
    t0 = time.perf_counter()
    with cap.annotate("check_device_path"):
        verdicts = plane.verify_many(requests)
    ms = (time.perf_counter() - t0) * 1000.0
    on_chip = plane.device_sigs - on_chip
    wrong = sum(1 for entry, good in zip(picked, verdicts)
                if bool(good) == bool(entry[1]))
    if wrong:
        problems.append(f"device-path check: {wrong} of {len(picked)} "
                        f"verdicts differ from the planted pattern")
    if not on_chip and not ctx.rehearsal:
        problems.append(f"device-path check: the verify plane kept a batch "
                        f"of {len(picked)} signatures off the chip")
    state = plane.get_json()
    for flag in ("device_wedged", "device_failed", "prewarm_error"):
        if state.get(flag):
            problems.append(f"verify plane: {flag}={state[flag]!r}")
    ctx.say(f"device-path check: one batch of {len(picked)} signatures "
            f"({sum(1 for e in picked if e[1])} planted), {on_chip} "
            f"verified on the chip, {ms:.0f} ms, {wrong} wrong verdicts")


def rpc(port: int, method: str, params: dict, timeout: float = 60.0) -> dict:
    body = json.dumps({"method": method, "params": [params]}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/", data=body,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.load(resp)["result"]


def check_accounts(port: int, model, population: str, sample: list[int],
                   problems: list) -> None:
    """``account_info`` of each sampled account against the benchmark's
    own arithmetic of balances and sequences."""
    from stellard_tpu.protocol.keys import encode_account_id

    keys = workload.population_keys(population, sample)
    for i in sample:
        acct = encode_account_id(keys[i].account_id)
        res = rpc(port, "account_info", {"account": acct})
        data = res.get("account_data") or {}
        got = (data.get("Balance"), data.get("Sequence"))
        want = (str(model.balance(i)), model.sequence(i))
        if got != want:
            problems.append(
                f"account_info[{i}] answered {got}, the model says {want}"
            )


def check_transactions(port: int, txids: list[bytes], ledger_seqs: set,
                       problems: list) -> None:
    """Each sampled acknowledged transaction is found by ``tx`` in a
    closed ledger with ``tesSUCCESS``."""
    for txid in txids:
        h = txid.hex().upper()
        res = rpc(port, "tx", {"transaction": h})
        meta = res.get("meta") or {}
        ok = (
            res.get("hash") == h
            and res.get("ledger_index") in ledger_seqs
            and meta.get("TransactionResult") in (0, "tesSUCCESS")
        )
        if not ok:
            problems.append(
                f"tx {h[:16]}: ledger_index={res.get('ledger_index')} "
                f"result={meta.get('TransactionResult')} "
                f"error={res.get('error')}"
            )


def reclose_from_disk(ini: str, ledger_hashes: list[bytes],
                      problems: list) -> None:
    """After ``node.stop()``: re-close stored ledgers from the store on
    disk with ``replay_ledger`` on the plain ``cpu``/``hashlib`` path
    (no device hasher, no batched verifier) and compare the hashes."""
    from stellard_tpu.node.config import Config
    from stellard_tpu.node.ledgertools import replay_ledger
    from stellard_tpu.nodestore.core import make_database

    cfg = Config.from_ini(ini)
    db = make_database(type=cfg.node_db_type, path=cfg.node_db_path)
    try:
        for h in ledger_hashes:
            stats = replay_ledger(db, h)
            if not (stats["ok"] and stats["state_hash_ok"]
                    and stats["tx_hash_ok"]):
                problems.append(
                    f"ledger {stats['ledger_seq']} re-closed from disk to "
                    f"{stats['replayed_hash'][:16]}, stored "
                    f"{stats['expected_hash'][:16]}"
                )
    finally:
        db.close()


def read_back(ctx, node, model, good_txids: list, ledgers: list,
              problems: list) -> list[bytes]:
    """The read-backs over the RPC door, outside the window: a seeded
    sample of accounts (half of them touched by the run) against the
    model, a seeded sample of acknowledged transactions found by ``tx``
    in one of ``ledgers``. -> the hashes of the seeded sample of those
    ledgers to re-close from disk once the node has stopped."""
    tr, pop = ctx.traffic, ctx.config["population"]
    port = node.http_server.port
    n = int(tr["account_sample"])
    sample = seeded_sample(ctx.seed + 1, model.touched(), n // 2)
    sample += seeded_sample(ctx.seed + 2, list(range(int(pop["accounts"]))),
                            n - len(sample))
    check_accounts(port, model, pop["name"], sample, problems)
    check_transactions(
        port, seeded_sample(ctx.seed + 3, good_txids, int(tr["tx_sample"])),
        {seq for seq, _h, _n in ledgers}, problems)
    full = [l for l in ledgers if l[2] > 0]
    return [h for _seq, h, _n in seeded_sample(
        ctx.seed + 4, full, int(tr["reclose_ledgers"]))]


def seeded_sample(seed: int, items: list, k: int) -> list:
    rng = random.Random(seed)
    return rng.sample(items, min(k, len(items)))
