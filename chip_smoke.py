#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the node still starts on the chip.

Runs the node's normal path with the device on, at the full width of the
deployed verify program (``verify_max_batch`` = 16,384 lanes, the one
shape every batch pads to on a TPU), and checks what comes out against a
plain CPU reference of the same seeded workload:

1. **device node** (holds the chip): a standalone ``Node`` built from INI
   text through ``Config.from_ini`` with ``[signature_backend]`` and
   ``[hash_backend]`` ``type=tpu mesh=0 routing=device``, a segstore under
   a temp dir and an ephemeral RPC port. Floods signed payments plus 64
   planted bad signatures through ``node.ops.submit_transaction`` (the
   asynchronous intake the overlay uses — the RPC ``submit`` door is a
   one-signature synchronous call and never forms a device batch), closes
   a ledger every ``--close-every`` transactions, then asks the HTTP door
   for ``server_info``, ``account_info``, ``ledger``, ``tx`` and
   ``get_counts``.
2. **catch-up**: ``python -m stellard_tpu --conf <same cfg> --ledger N
   --replay`` as a second process on the same chip — the chip passes from
   one process to the next and the compile cache hits across processes.
3. **plain reference**: the same workload through a ``cpu``/``cpu`` node,
   pinned to ``JAX_PLATFORMS=cpu`` so it cannot take the chip.

The parent process imports neither JAX nor ``stellard_tpu``: a chip
belongs to one process at a time, so the three children run one after
another, each under a wall-clock limit. Without an accelerator the first
child exits non-zero, naming the platform it found, and no result is
printed. Once the device is known, stdout carries two lines: a JSON
summary of the run (sizes, kernels, set-up seconds, compile counts,
``"claim": null``), then — the LAST line, with exactly these keys — the
verdict ``{"ok": true|false, "device": {"platform", "kind", "count"}}``
with the device as JAX reported it to the child that held it. Rates are
not printed: this is a smoke, the benchmark owns every number.

    python chip_smoke.py [--seed 0] [--txs 32768] [--close-every 2048]
                         [--impl xla|pallas] [--mesh 0]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

REQUIRED_PLATFORM = "tpu"
# one full width of the deployed verify program: --txs goes no lower
MIN_TXS = 16384
PLANTED = 64
# server_info, account_info x3, ledger, tx, get_counts
RPC_CHECKS = 7
# Unacknowledged submissions per wave. NetworkOPs sheds a submission once
# more than TX_BACKLOG_SHED (100) verified transactions wait for the apply
# job; shedding depends on timing and would break the byte-identity gate,
# so the window stays below it.
WINDOW = 96
# close times are hashed into the ledger: two runs on the wall clock never
# match, so both nodes close on this pinned schedule
PIN_CLOSE_TIME = 900_000_000
AMOUNT_DROPS = 250_000_000  # above the 200 STR reserve: first payment creates
TOTAL_LIMIT_S = 1150.0  # the contract allows 1200
CHILD_LIMITS_S = {"device": 840.0, "replay": 240.0, "reference": 420.0}

REDUCED = {
    "accounts": (
        "txs/2 accounts (16,384 by default) where a deployment holds "
        "millions: state is built through the transactor at the host's "
        "apply rate, inside this script's time limit; cells R1/R2 bring "
        "the real state sizes"
    ),
    "history": (
        "txs/close-every ledgers (16 by default); catch-up replays one "
        "of them, not a deep span"
    ),
}


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# configuration and workload (shared by the device and reference phases)


def build_ini(workdir: str, backend: str, mesh: int = 0,
              verify_max_batch: int | None = None) -> str:
    """The deployment under test as INI text (the --conf parse path).
    ``backend`` is ``tpu`` (device node) or ``cpu`` (plain reference);
    ``verify_max_batch`` is left at the config default (16,384) except by
    the CPU test, which cannot afford that width on XLA:CPU."""
    store = os.path.join(workdir, f"{backend}-nodestore")
    sqlite = os.path.join(workdir, f"{backend}-stellard.db")
    if backend == "tpu":
        sig = f"type=tpu\nmesh={mesh}\nrouting=device\n"
        if verify_max_batch is not None:
            sig += f"max_batch={verify_max_batch}\n"
        hsh = (f"type=tpu\nmesh={mesh}\nrouting=device\n"
               "min_device_nodes=0\n")
    else:
        sig = "type=cpu\n"
        hsh = "type=cpu\n"
    return (
        "[standalone]\n1\n\n"
        f"[signature_backend]\n{sig}\n"
        f"[hash_backend]\n{hsh}\n"
        f"[node_db]\ntype=segstore\npath={store}\n\n"
        f"[database_path]\n{sqlite}\n\n"
        "[rpc_port]\n0\n\n"
        "[spec]\nworkers=1\n\n"
        # admission control stays on but non-binding: a single-account
        # flood would otherwise be shed by the adaptive caps, and
        # shedding is timing-dependent
        "[txq]\nmin_cap=1000000\nmax_cap=1000000\n"
    )


def make_workload(seed: int, txs: int) -> dict:
    """The seeded workload: ``txs`` signed payments of 250 STR from the
    master account to ``txs/2`` distinct new accounts (the first payment
    to each creates it, the second is a transfer), plus PLANTED extra
    transactions at seeded positions, each a copy of a valid one with its
    signature corrupted (R byte, low S byte, public key). The planted ones
    are EXTRA so that a refusal leaves no gap in the master account's
    sequence chain. Returns the submission stream as wire blobs."""
    from stellard_tpu.node.node import MASTER_PASSPHRASE
    from stellard_tpu.protocol.formats import TxType
    from stellard_tpu.protocol.keys import KeyPair
    from stellard_tpu.protocol.sfields import (
        sfAmount,
        sfDestination,
        sfSigningPubKey,
        sfTxnSignature,
    )
    from stellard_tpu.protocol.stamount import STAmount
    from stellard_tpu.protocol.stobject import STObject
    from stellard_tpu.protocol.sttx import SerializedTransaction

    if txs < 2 or txs % 2:
        raise ValueError("--txs must be even and >= 2")
    master = KeyPair.from_passphrase(MASTER_PASSPHRASE)
    n_dest = txs // 2
    dests = [
        hashlib.sha256(f"chip-smoke:{seed}:{i}".encode()).digest()[:20]
        for i in range(n_dest)
    ]
    amount = STAmount.from_drops(AMOUNT_DROPS)
    valid: list[bytes] = []
    txids: list[bytes] = []
    for i in range(txs):
        tx = SerializedTransaction.build(
            TxType.ttPAYMENT, master.account_id, 1 + i, 10,
            {sfAmount: amount, sfDestination: dests[i % n_dest]},
        )
        tx.sign(master)
        valid.append(tx.serialize())
        txids.append(tx.txid())

    rng = random.Random(seed)
    planted_after = sorted(rng.sample(range(txs), PLANTED))
    planted_blobs = {}
    for k, src in enumerate(planted_after):
        obj = STObject.from_bytes(valid[src])
        sig = bytearray(obj[sfTxnSignature])
        kind = k % 3
        if kind == 0:
            sig[5] ^= 0x40  # R byte: encode([S]B + [h](-A)) != R
        elif kind == 1:
            sig[32] ^= 0x01  # low S byte: S stays canonical, wrong point
        else:
            pub = bytearray(obj[sfSigningPubKey])
            pub[3] ^= 0x80  # public key: bad decompress, or a wrong A
            obj[sfSigningPubKey] = bytes(pub)
        obj[sfTxnSignature] = bytes(sig)
        planted_blobs[src] = SerializedTransaction(obj).serialize()

    # stream entries: (blob, planted?) — a planted copy follows its source
    stream: list[tuple[bytes, bool]] = []
    for i, blob in enumerate(valid):
        stream.append((blob, False))
        if i in planted_blobs:
            stream.append((planted_blobs[i], True))
    digest = hashlib.sha256()
    for blob, _planted in stream:
        digest.update(len(blob).to_bytes(4, "big") + blob)
    return {
        "stream": stream,
        "dests": dests,
        "txids": txids,
        "digest": digest.hexdigest(),
    }


# --------------------------------------------------------------------------
# phases 1 and 3: a node, flooded, closed, asked over RPC


def _rpc(port: int, method: str, params: dict) -> dict:
    import urllib.request

    body = json.dumps({"method": method, "params": [params]}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/", data=body,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.load(resp)["result"]


def _rpc_checks(port: int, *, seed: int, txs: int, close_every: int,
                work: dict, ledgers: list, backend: str,
                verify_json: dict) -> list[dict]:
    """Ask the HTTP door and compare every answer with what this process
    computed: -> [{"call", "ok", "detail"}]."""
    from stellard_tpu.protocol.keys import encode_account_id

    out: list[dict] = []

    def check(call: str, ok: bool, detail) -> None:
        out.append({"call": call, "ok": bool(ok), "detail": detail})

    last_seq, last_hash = ledgers[-1]
    rng = random.Random(seed + 1)
    n_dest = len(work["dests"])

    info = _rpc(port, "server_info", {}).get("info", {})
    closed = info.get("closed_ledger") or info.get("validated_ledger") or {}
    check("server_info",
          closed.get("seq") == last_seq
          and str(closed.get("hash", "")).lower() == last_hash
          and info.get("signature_backend") == backend,
          {"closed": closed,
           "signature_backend": info.get("signature_backend")})

    for d in rng.sample(range(n_dest), 3):
        # tx i pays dests[i % n_dest]: every account is paid txs/n_dest times
        paid = len(range(d, txs, n_dest))
        want = str(paid * AMOUNT_DROPS)
        acct = encode_account_id(work["dests"][d])
        res = _rpc(port, "account_info", {"account": acct})
        got = (res.get("account_data") or {}).get("Balance")
        check(f"account_info[{d}]", got == want,
              {"account": acct, "balance": got, "want": want})

    res = _rpc(port, "ledger", {"ledger_index": last_seq})
    got = str((res.get("ledger") or {}).get("ledger_hash", "")).lower()
    check("ledger", got == last_hash, {"hash": got, "want": last_hash})

    i = rng.randrange(txs)
    txid = work["txids"][i].hex().upper()
    want_seq = ledgers[i // close_every][0]
    res = _rpc(port, "tx", {"transaction": txid})
    meta = res.get("meta") or {}
    check("tx",
          res.get("hash") == txid and res.get("ledger_index") == want_seq
          and meta.get("TransactionResult") in (0, "tesSUCCESS"),
          {"hash": res.get("hash"), "ledger_index": res.get("ledger_index"),
           "want_seq": want_seq,
           "result": meta.get("TransactionResult"),
           "error": res.get("error")})

    res = _rpc(port, "get_counts", {})
    crypto = res.get("crypto") or {}
    cv = crypto.get("verify") or {}
    check("get_counts",
          cv.get("backend") == backend
          and cv.get("device_sigs") == verify_json.get("device_sigs")
          and (crypto.get("hash") or {}).get("backend") == backend,
          {"verify_backend": cv.get("backend"),
           "device_sigs": cv.get("device_sigs"),
           "hash_backend": (crypto.get("hash") or {}).get("backend"),
           "error": res.get("error")})
    return out


def run_node_phase(workdir: str, backend: str, *, seed: int, txs: int,
                   close_every: int, mesh: int = 0,
                   require_platform: str = REQUIRED_PLATFORM,
                   verify_max_batch: int | None = None) -> dict:
    """Phase 1 (``backend='tpu'``) and phase 3 (``backend='cpu'``): boot
    the node from INI text, flood the seeded workload through the
    asynchronous intake, close every ``close_every`` transactions on the
    pinned schedule, ask the HTTP door, stop. -> the result dict the
    parent gates on. ``require_platform`` and ``verify_max_batch`` are
    function arguments only the CPU test supplies; no flag or environment
    variable lowers them."""
    import threading

    t_start = time.perf_counter()
    out: dict = {"phase": "device" if backend == "tpu" else "reference",
                 "backend": backend, "seed": seed, "txs": txs,
                 "close_every": close_every, "mesh": mesh,
                 "jax_platforms_env": os.environ.get("JAX_PLATFORMS", "")}
    if backend == "tpu":
        # before anything else: the platform JAX gives this process
        from stellard_tpu.crypto.backend import ensure_jax

        jax = ensure_jax()
        devices = jax.devices()
        out["platform"] = devices[0].platform
        out["device_kind"] = devices[0].device_kind
        out["devices_visible"] = len(devices)
        if devices[0].platform != require_platform:
            raise SystemExit(
                f"chip_smoke: JAX found platform {devices[0].platform!r} "
                f"({len(devices)} device(s), JAX_PLATFORMS="
                f"{os.environ.get('JAX_PLATFORMS', '')!r}), not "
                f"{require_platform!r}: no accelerator, no result"
            )

    from stellard_tpu import native
    from stellard_tpu.node.config import Config
    from stellard_tpu.node.node import Node
    from stellard_tpu.protocol.sttx import SerializedTransaction
    from stellard_tpu.protocol.ter import TER

    ini = build_ini(workdir, backend, mesh=mesh,
                    verify_max_batch=verify_max_batch)
    conf_path = os.path.join(workdir, f"{backend}.cfg")
    with open(conf_path, "w") as fh:
        fh.write(ini)
    out["conf"] = conf_path

    t0 = time.perf_counter()
    node = Node(Config.from_ini(ini)).setup().serve()
    try:
        out["setup_s"] = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()
        if node.verify_prewarm is not None:
            node.verify_prewarm.join()
        out["prewarm_s"] = round(time.perf_counter() - t0, 3)

        # built from what git commits: both host libraries come from
        # `make` on first use (native/*.so is never checked in)
        out["native"] = {
            "libstellard_native": native.load_native() is not None,
            "_stser": native.load_stser() is not None,
            "build_errors": dict(native.build_errors),
        }

        t0 = time.perf_counter()
        work = make_workload(seed, txs)
        out["workload_digest"] = work["digest"]
        out["workload_s"] = round(time.perf_counter() - t0, 3)

        closes_done = [0]
        node.ops.network_time = (
            lambda: PIN_CLOSE_TIME + closes_done[0] * 30
        )
        done = threading.Semaphore(0)
        outcomes: dict[int, tuple[int, bool]] = {}  # stream pos -> result
        pos_of: dict[bytes, int] = {}

        def cb(tx, ter, applied):
            outcomes[pos_of[tx.txid()]] = (int(ter), bool(applied))
            done.release()

        results_digest = hashlib.sha256()
        ledgers: list[tuple[int, str]] = []
        compiles_first = None
        meter = None
        if backend == "tpu":
            from stellard_tpu.utils.xlacache import COMPILES as meter

        def close() -> None:
            nonlocal compiles_first
            closed, results = node.ops.accept_ledger()
            closes_done[0] += 1
            ledgers.append((closed.seq, closed.hash().hex()))
            for txid in sorted(results):
                results_digest.update(
                    txid + bytes([int(results[txid]) & 0xFF])
                )
            if compiles_first is None and meter is not None:
                compiles_first = meter.snapshot()

        t0 = time.perf_counter()
        wave: list = []
        valid_in_ledger = 0

        def flush_wave() -> None:
            for tx in wave:
                node.ops.submit_transaction(tx, cb)
            for _ in wave:
                done.acquire()
            wave.clear()

        for pos, (blob, planted) in enumerate(work["stream"]):
            # parsed from wire bytes, as the overlay's intake does
            tx = SerializedTransaction.from_bytes(blob)
            pos_of[tx.txid()] = pos
            wave.append(tx)
            valid_in_ledger += 0 if planted else 1
            if len(wave) >= WINDOW:
                flush_wave()
            if valid_in_ledger >= close_every:
                flush_wave()
                close()
                valid_in_ledger = 0
        flush_wave()
        if valid_in_ledger:
            close()
        node.close_pipeline.flush(timeout=300)
        out["flood_close_s"] = round(time.perf_counter() - t0, 3)

        # exactly the planted transactions were refused, and nothing else
        refused = 0
        unexpected = []
        for pos, (_blob, planted) in enumerate(work["stream"]):
            ter, applied = outcomes[pos]
            was_refused = ter == int(TER.temINVALID) and not applied
            refused += was_refused
            if was_refused != planted or (not planted and not applied):
                unexpected.append([pos, planted, ter, applied])
            results_digest.update(
                pos.to_bytes(4, "big") + (ter & 0xFFFF).to_bytes(2, "big")
                + bytes([applied])
            )
        out["refused"] = refused
        out["unexpected"] = unexpected[:20]
        out["n_unexpected"] = len(unexpected)
        out["bad_sig"] = node.ops.stats.get("bad_sig", 0)
        out["shed"] = node.ops.stats.get("shed", 0)
        out["ledgers"] = ledgers
        out["results_digest"] = results_digest.hexdigest()
        # the catch-up target: the fullest ledger, the newest on a tie
        counts = [min(close_every, txs - i * close_every)
                  for i in range(len(ledgers))]
        out["replay_ledger"] = max(
            zip(counts, (seq for seq, _h in ledgers))
        )[1]
        out["replay_tx_count"] = max(counts)

        out["verify"] = node.verify_plane.get_json()
        hj = getattr(node.hasher, "get_json", None)
        out["hash"] = hj() if hj is not None else {
            "backend": node.hasher.name}
        t0 = time.perf_counter()
        out["rpc"] = _rpc_checks(
            node.http_server.port, seed=seed, txs=txs,
            close_every=close_every, work=work, ledgers=ledgers,
            backend=backend, verify_json=out["verify"],
        )
        out["rpc_s"] = round(time.perf_counter() - t0, 3)
        if meter is not None:
            out["compiles"] = {
                "through_first_close": compiles_first,
                "total": meter.snapshot(),
            }
    finally:
        node.stop()
    out["phase_s"] = round(time.perf_counter() - t_start, 3)
    return out


# --------------------------------------------------------------------------
# phase 2: catch-up as a second process on the same chip


def _run_limited(cmd: list[str], env: dict, limit_s: float,
                 cwd: str = REPO) -> tuple[int, str]:
    """Run ``cmd`` in its own process group under a wall-clock limit;
    stdout is captured, stderr passes through. On overrun the whole group
    is killed. -> (returncode, stdout); returncode 124 on timeout."""
    proc = subprocess.Popen(
        cmd, cwd=cwd, env=env, stdout=subprocess.PIPE, text=True,
        start_new_session=True,
    )
    try:
        stdout, _ = proc.communicate(timeout=limit_s)
        return proc.returncode, stdout
    except subprocess.TimeoutExpired:
        say(f"{cmd[1:4]} exceeded its {limit_s:.0f}s limit — killing it")
        return 124, ""
    finally:
        # stop every process this script started, children's children too
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        proc.wait()


def run_replay_phase(conf_path: str, ledger_seq: int, env: dict,
                     limit_s: float) -> dict:
    """``python -m stellard_tpu --conf <cfg> --ledger N --replay``: every
    signature of the ledger in one batch, both trees re-hashed."""
    t0 = time.perf_counter()
    rc, stdout = _run_limited(
        [sys.executable, "-m", "stellard_tpu", "--conf", conf_path,
         "--ledger", str(ledger_seq), "--replay"],
        env, limit_s,
    )
    out: dict = {"phase": "replay", "rc": rc,
                 "process_s": round(time.perf_counter() - t0, 3)}
    start = stdout.find("{")
    if start >= 0:
        try:
            out["stats"] = json.loads(stdout[start:])
        except ValueError:
            out["stdout_tail"] = stdout[-2000:]
    return out


# --------------------------------------------------------------------------
# the gates


def check(device: dict, replay: dict, reference: dict, *,
          require_platform: str = REQUIRED_PLATFORM, impl: str = "xla",
          mesh: int = 0) -> list[str]:
    """-> every gate that failed (empty when the smoke passes)."""
    bad: list[str] = []

    def gate(ok: bool, msg: str) -> None:
        if not ok:
            bad.append(msg)

    width = max(1, mesh)
    vj, hj = device.get("verify", {}), device.get("hash", {})
    vmesh, hmesh = vj.get("mesh") or {}, hj.get("mesh") or {}

    # byte identity against the plain reference
    gate(device.get("workload_digest") == reference.get("workload_digest"),
         "the two nodes were fed different workloads")
    gate(device.get("ledgers") == reference.get("ledgers")
         and bool(device.get("ledgers")),
         f"ledger hashes differ from the CPU reference: "
         f"{device.get('ledgers')} vs {reference.get('ledgers')}")
    gate(device.get("results_digest") == reference.get("results_digest"),
         "per-transaction result digest differs from the CPU reference")
    for name, res in (("device", device), ("reference", reference)):
        gate(res.get("refused") == PLANTED and res.get("n_unexpected") == 0
             and res.get("bad_sig") == PLANTED and res.get("shed") == 0,
             f"{name}: refusals are not exactly the {PLANTED} planted "
             f"(refused={res.get('refused')} bad_sig={res.get('bad_sig')} "
             f"shed={res.get('shed')} unexpected={res.get('unexpected')})")
        for r in res.get("rpc", []):
            gate(r["ok"], f"{name}: RPC {r['call']} answered wrong: "
                          f"{r['detail']}")
        gate(len(res.get("rpc", [])) == RPC_CHECKS,
             f"{name}: RPC checks missing")

    # the device really ran, on the platform required, at the width asked
    gate(device.get("platform") == require_platform,
         f"device child ran on {device.get('platform')!r}")
    gate(vmesh.get("platform") == require_platform
         and hmesh.get("platform") == require_platform,
         f"planes report platforms {vmesh.get('platform')!r} / "
         f"{hmesh.get('platform')!r}, not {require_platform!r}")
    want_kernel = ("xla-sharded" if impl == "xla" else "pallas-shardmap")
    gate(vmesh.get("kernel") == f"{want_kernel}@{width}",
         f"verify kernel is {vmesh.get('kernel')!r}")
    gate(hmesh.get("tree_kernel") == f"tree-sha512-sharded@{width}",
         f"tree kernel is {hmesh.get('tree_kernel')!r}")
    gate(vmesh.get("mesh_width") == width
         and hmesh.get("tree_width") == width,
         f"mesh width clamped: verify {vmesh.get('mesh_width')} tree "
         f"{hmesh.get('tree_width')}, requested {width}")
    gate(not vj.get("device_wedged") and not hj.get("wedged"),
         f"a plane wedged (verify={vj.get('device_wedged')} "
         f"hash={hj.get('wedged')})")
    gate(not vj.get("device_failed"),
         f"the verify device arm raised: {vj.get('device_error')}")
    gate(vj.get("prewarm_error") is None,
         f"the verify prewarm failed: {vj.get('prewarm_error')}")
    gate(vj.get("cpu_eligible_batches") == 0,
         f"{vj.get('cpu_eligible_batches')} batch(es) of "
         f">= min_device_batch signatures ran on the CPU arm")
    gate((vj.get("device_sigs") or 0) > 0,
         "the flood formed no device batch (device_sigs == 0)")
    gate((hj.get("device_nodes") or 0) > 0, "device_nodes == 0")
    calls = hmesh.get("tree_pipeline_calls") or 0
    reads = (hmesh.get("tree_transfers") or {}).get("readbacks")
    gate(calls > 0 and calls == reads,
         f"tree_pipeline_calls={calls} readbacks={reads}: expected one "
         f"readback per fused tree, more than zero")

    # built from what git commits
    nat = device.get("native") or {}
    gate(nat.get("libstellard_native") and nat.get("_stser")
         and vj.get("host_impl") == "native",
         f"native host libraries did not build (host_impl="
         f"{vj.get('host_impl')!r}); make said:\n"
         + "\n".join(f"--- {k}\n{v}" for k, v in
                     (nat.get("build_errors") or {}).items()))

    # catch-up on the same chip, from the cache
    stats = replay.get("stats") or {}
    rv = stats.get("verify") or {}
    gate(replay.get("rc") == 0 and stats.get("ok") is True,
         f"--replay exited {replay.get('rc')} ok={stats.get('ok')} "
         f"{replay.get('stdout_tail', '')}")
    gate(stats.get("tx_count") == device.get("replay_tx_count")
         and stats.get("device_sigs") == stats.get("tx_count"),
         f"--replay verified {stats.get('device_sigs')} of "
         f"{stats.get('tx_count')} signatures on the device (ledger "
         f"holds {device.get('replay_tx_count')})")
    gate((rv.get("mesh") or {}).get("platform") == require_platform
         and not rv.get("device_wedged") and not rv.get("device_failed")
         and not (stats.get("hash") or {}).get("wedged"),
         f"--replay planes: platform "
         f"{(rv.get('mesh') or {}).get('platform')!r} wedged="
         f"{rv.get('device_wedged')} failed={rv.get('device_failed')}")
    programs = (stats.get("xla") or {}).get("programs") or {}
    verify_programs = {n: p for n, p in programs.items()
                       if "verify_kernel" in n}
    gate(bool(verify_programs) and all(
        p["requests"] == p["cache_hits"] for p in verify_programs.values()),
         f"--replay compiled the verify program instead of loading it "
         f"from the compile cache: {verify_programs}")
    return bad


# --------------------------------------------------------------------------
# entry points: the parent (no JAX) and its children


def result_lines(device: dict, replay: dict, reference: dict,
                 failures: list[str], *, impl: str, mesh: int) -> list[str]:
    """What the parent writes to stdout once the device is known. A
    passing run gets a JSON summary line first (set-up information; it
    ends with ``"claim": null``). The LAST line is always the verdict,
    with exactly the keys ``ok`` and ``device``, the device as JAX
    reported it to the child that held the chip."""
    lines: list[str] = []
    if not failures:
        vj = device["verify"]
        hmesh = device["hash"]["mesh"]
        stats = replay.get("stats") or {}
        xla = stats.get("xla") or {}
        first = device["compiles"]["through_first_close"] or {}
        total = device["compiles"]["total"]
        lines.append(json.dumps({
            "summary": "chip_smoke",
            "impl": impl,
            "mesh": mesh,
            "sizes": {"txs": device["txs"], "planted": PLANTED,
                      "accounts": device["txs"] // 2,
                      "ledgers": len(device["ledgers"]),
                      "verify_lanes": (vj["mesh"] or {}).get("max_batch")},
            "reduced": REDUCED,
            "kernels": {"verify": vj["mesh"]["kernel"],
                        "tree": hmesh["tree_kernel"],
                        "flat": hmesh["kernel"]},
            "device_work": {
                "verify_batches": vj["device_batches"],
                "verify_sigs": vj["device_sigs"],
                "cpu_small_batches": vj["cpu_batches"],
                "hash_device_nodes": device["hash"]["device_nodes"],
                "fused_trees": hmesh["tree_pipeline_calls"]},
            "setup_seconds": {
                "device_node": {k: device[k] for k in (
                    "setup_s", "prewarm_s", "workload_s", "flood_close_s",
                    "rpc_s", "phase_s")},
                "replay": {"process_s": replay["process_s"],
                           "first_verdict_s": stats.get("verify_s")},
                "reference": {k: reference[k] for k in (
                    "workload_s", "flood_close_s", "phase_s")},
            },
            "compiles": {
                "through_first_close": {k: first.get(k) for k in (
                    "requests", "compiled", "seconds")},
                "total": {k: total[k] for k in (
                    "requests", "compiled", "seconds", "programs")},
                "replay": {k: xla.get(k) for k in (
                    "requests", "cache_hits", "compiled", "seconds",
                    "programs")},
            },
            "rates": "not measured (a smoke; the benchmark owns every "
                     "number)",
            "claim": None,
        }))
    lines.append(json.dumps({
        "ok": not failures,
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["device_kind"]),
                   "count": int(device["devices_visible"])},
    }))
    return lines


def _child_main(args) -> int:
    res = run_node_phase(
        args.workdir, "tpu" if args.phase == "device" else "cpu",
        seed=args.seed, txs=args.txs, close_every=args.close_every,
        mesh=args.mesh,
    )
    print(json.dumps(res), flush=True)
    return 0


def _spawn_phase(phase: str, args, workdir: str, env: dict,
                 limit_s: float) -> dict | None:
    rc, stdout = _run_limited(
        [sys.executable, os.path.abspath(__file__), "--phase", phase,
         "--workdir", workdir, "--seed", str(args.seed),
         "--txs", str(args.txs), "--close-every", str(args.close_every),
         "--mesh", str(args.mesh)],
        env, limit_s,
    )
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    if rc != 0 or not lines:
        say(f"{phase} child exited {rc}")
        return None
    try:
        return json.loads(lines[-1])
    except ValueError:
        say(f"{phase} child printed no result: {lines[-1][:300]}")
        return None


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--txs", type=int, default=32768,
                    help=f"valid payments (even, >= {MIN_TXS})")
    ap.add_argument("--close-every", type=int, default=2048)
    ap.add_argument("--impl", choices=("xla", "pallas"), default="xla",
                    help="verify kernel implementation of the device "
                         "children (STELLARD_VERIFY_IMPL)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="mesh= of both device planes (0 = one chip)")
    ap.add_argument("--phase", choices=("device", "reference"),
                    help=argparse.SUPPRESS)  # child mode
    ap.add_argument("--workdir", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.txs < MIN_TXS or args.txs % 2:
        ap.error(f"--txs must be even and at least {MIN_TXS} "
                 "(one full width of the verify program)")
    if args.close_every < 1 or args.mesh < 0:
        ap.error("--close-every must be positive and --mesh non-negative")
    if args.phase:
        return _child_main(args)

    deadline = time.monotonic() + TOTAL_LIMIT_S

    def limit(phase: str) -> float:
        return max(10.0, min(CHILD_LIMITS_S[phase],
                             deadline - time.monotonic()))

    # device children leave JAX's platform choice to the installation
    # (and check what they got); the kernel implementation is pinned in
    # their environment before any kernel module can be imported
    dev_env = dict(os.environ, STELLARD_VERIFY_IMPL=args.impl)
    # the reference is pinned to the CPU on purpose: it cannot take the chip
    ref_env = dict(os.environ, JAX_PLATFORMS="cpu")

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        say(f"phase 1/3: device node ({args.txs} payments + {PLANTED} "
            f"planted, close every {args.close_every}, impl={args.impl}, "
            f"mesh={args.mesh})")
        device = _spawn_phase("device", args, workdir, dev_env,
                              limit("device"))
        if device is None:
            say("FAILED: the device node did not come up")
            return 1
        say(f"  platform={device['platform']} kind={device['device_kind']} "
            f"devices={device['devices_visible']} | set-up seconds: node "
            f"{device['setup_s']} prewarm {device['prewarm_s']} workload "
            f"{device['workload_s']} flood+close {device['flood_close_s']} "
            f"rpc {device['rpc_s']}")
        first = device["compiles"]["through_first_close"] or {}
        total = device["compiles"]["total"]
        say(f"  XLA programs: {first.get('requests')} requested "
            f"({first.get('compiled')} compiled, {first.get('seconds')}s) "
            f"through the first close; {total['requests']} requested "
            f"({total['compiled']} compiled, {total['seconds']}s) in all")

        say(f"phase 2/3: catch-up, --replay of ledger "
            f"{device['replay_ledger']} as a second process on the chip")
        replay = run_replay_phase(device["conf"], device["replay_ledger"],
                                  dev_env, limit("replay"))
        stats = replay.get("stats") or {}
        xla = stats.get("xla") or {}
        say(f"  rc={replay['rc']} ok={stats.get('ok')} device_sigs="
            f"{stats.get('device_sigs')}/{stats.get('tx_count')} | seconds "
            f"to first verdict {stats.get('verify_s')} (child 1's prewarm: "
            f"{device['prewarm_s']}), process {replay['process_s']} | XLA "
            f"programs: {xla.get('requests')} requested, "
            f"{xla.get('cache_hits')} from the cache, "
            f"{xla.get('compiled')} compiled")

        say("phase 3/3: plain reference (cpu/cpu node, JAX_PLATFORMS=cpu)")
        reference = _spawn_phase("reference", args, workdir, ref_env,
                                 limit("reference"))
        if reference is None:
            say("FAILED: the CPU reference did not run")
            print(result_lines(device, replay, {}, ["no reference"],
                               impl=args.impl, mesh=args.mesh)[-1],
                  flush=True)
            return 1
        say(f"  flood+close seconds {reference['flood_close_s']}")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    failures = check(device, replay, reference, impl=args.impl,
                     mesh=args.mesh)
    if failures:
        # everything the children reported, for whoever has to find out why
        for res in (device, replay, reference):
            say(f"{res['phase']} result: {json.dumps(res)}")
        for f in failures:
            say(f"GATE FAILED: {f}")
        say(f"FAILED: {len(failures)} gate(s)")
    for line in result_lines(device, replay, reference, failures,
                             impl=args.impl, mesh=args.mesh):
        print(line, flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
