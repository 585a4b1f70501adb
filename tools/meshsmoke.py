#!/usr/bin/env python3
"""Multi-chip smoke gate (tools/tier1.sh).

Boots a standalone node with the SIGNATURE plane mesh-enabled on the
virtual 8-device CPU mesh ([signature_backend] type=tpu mesh=auto
routing=device), floods 200 payments through the full async pipeline
closing every 50, then replays the IDENTICAL deterministic workload on
a cpu-backend node. Gates:

- ledger-hash byte identity at every close between the two runs (a
  sharded verifier that flipped one verdict would fork the chain here,
  not in a consensus round);
- device_sigs > 0 and an effective mesh width of 8 on the mesh run
  (anti-vacuity: routing honesty means the gate fails when the "mesh"
  run silently verified on the host);
- the fused whole-tree hash pipeline ran ([hash_backend] type=tpu
  routing=device) and read back from the device exactly ONCE per tree
  (transfer honesty: a per-level round-trip is a residency regression);
- zero rejected transactions in either run.

Exit 0 on all gates; 1 otherwise.
"""

from __future__ import annotations

import os
import re
import sys

# the virtual mesh must exist BEFORE jax initializes (same contract as
# tests/conftest.py); runnable as `python tools/meshsmoke.py`
opt = "--xla_force_host_platform_device_count=8"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" in flags:
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", opt, flags)
else:
    flags = (flags + " " + opt).strip()
os.environ["XLA_FLAGS"] = flags
os.environ["JAX_PLATFORMS"] = "cpu"
# bounded compile budget: ONE padded shape (pad-to-max at 256) for the
# whole flood, measured XLA formulation — never pallas-interpret
os.environ["STELLARD_PAD_POLICY"] = "max"
os.environ["STELLARD_VERIFY_IMPL"] = "xla"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def drive(cfg, n_txs: int = 200):
    """Deterministic flood: same keys/seqs/amounts per run; returns
    ([(seq, ledger_hash)...], {verify, hash} plane jsons, rejected
    count)."""
    import threading

    from stellard_tpu.node.node import Node
    from stellard_tpu.protocol.formats import TxType
    from stellard_tpu.protocol.keys import KeyPair
    from stellard_tpu.protocol.sfields import sfAmount, sfDestination
    from stellard_tpu.protocol.stamount import STAmount
    from stellard_tpu.protocol.sttx import SerializedTransaction

    node = Node(cfg).setup()
    try:
        if node.verify_prewarm is not None:
            node.verify_prewarm.join(timeout=600)
        master = KeyPair.from_passphrase("masterpassphrase")
        dests = [
            KeyPair.from_passphrase(f"mesh-smoke-{i}").account_id
            for i in range(8)
        ]
        done = threading.Semaphore(0)
        rejected = []

        def cb(tx, ter, applied):
            if not applied:
                rejected.append(ter)
            done.release()

        closes = []
        for chunk in range(0, n_txs, 50):
            txs = []
            for i in range(chunk, min(chunk + 50, n_txs)):
                tx = SerializedTransaction.build(
                    TxType.ttPAYMENT, master.account_id, 1 + i, 10,
                    {sfAmount: STAmount.from_drops(250_000_000),
                     sfDestination: dests[i % len(dests)]},
                )
                tx.sign(master)
                txs.append(tx)
            for tx in txs:
                node.ops.submit_transaction(tx, cb)
            for _ in txs:
                done.acquire()
            closed, _results = node.ops.accept_ledger()
            closes.append((closed.seq, closed.hash()))
        hj = getattr(node.hasher, "get_json", None)
        planes = {
            "verify": node.verify_plane.get_json(),
            "hash": hj() if hj is not None else None,
        }
        return closes, planes, len(rejected)
    finally:
        node.stop()


def run_smoke() -> int:
    from stellard_tpu.node.config import Config
    from stellard_tpu.utils.xlacache import enable_compilation_cache

    enable_compilation_cache()

    mesh_closes, planes, mesh_rejected = drive(Config(
        signature_backend="tpu",
        verify_mesh="auto",
        verify_routing="device",
        verify_min_device_batch=1,
        verify_max_batch=256,
        # hash plane on the same virtual mesh, device-forced: the
        # fused whole-tree pipeline must carry the close's tree work
        # so the transfer gate below is non-vacuous
        hash_backend="tpu",
        hash_mesh="auto",
        hash_routing="device",
        hash_min_device_nodes=0,
    ))
    vp = planes["verify"]
    cpu_closes, _planes_cpu, cpu_rejected = drive(Config(
        signature_backend="cpu",
    ))

    bad = 0
    if mesh_rejected or cpu_rejected:
        print(f"mesh smoke: rejected txs (mesh={mesh_rejected} "
              f"cpu={cpu_rejected})", file=sys.stderr)
        bad += 1
    if len(mesh_closes) != len(cpu_closes):
        print(f"mesh smoke: close count mismatch {len(mesh_closes)} vs "
              f"{len(cpu_closes)}", file=sys.stderr)
        bad += 1
    for (ms, mh), (cs, ch) in zip(mesh_closes, cpu_closes):
        if ms != cs or mh != ch:
            print(f"mesh smoke: ledger DIVERGED at seq {ms}/{cs}: "
                  f"{mh.hex()[:16]} vs {ch.hex()[:16]}", file=sys.stderr)
            bad += 1
    # anti-vacuity: the mesh leg must have verified on the sharded
    # device plane, at the full virtual width, without a wedge fallback
    mesh_info = vp.get("mesh") or {}
    if not vp.get("device_sigs"):
        print(f"mesh smoke: device_sigs=0 — the mesh run verified on "
              f"the host (routing={vp.get('routing')}, "
              f"wedged={vp.get('device_wedged')})", file=sys.stderr)
        bad += 1
    if mesh_info.get("mesh_width") != 8:
        print(f"mesh smoke: effective width {mesh_info.get('mesh_width')}"
              f" != 8 (kernel={mesh_info.get('kernel')})", file=sys.stderr)
        bad += 1
    # fused-close transfer honesty (ISSUE 16): the whole-tree pipeline
    # ran, and it read back from the device exactly ONCE per tree — a
    # readback count above tree_pipeline_calls means some level quietly
    # round-tripped to the host mid-chain (residency regression)
    hp = planes.get("hash") or {}
    hmesh = hp.get("mesh") or {}
    tree_calls = hmesh.get("tree_pipeline_calls") or 0
    tree_tr = hmesh.get("tree_transfers") or {}
    if not tree_calls:
        print(f"mesh smoke: tree_pipeline_calls=0 — the fused hash "
              f"pipeline never ran (wedged={hp.get('wedged')}, "
              f"tree_kernel={hmesh.get('tree_kernel')})", file=sys.stderr)
        bad += 1
    elif tree_tr.get("readbacks") != tree_calls:
        print(f"mesh smoke: {tree_tr.get('readbacks')} device readbacks "
              f"over {tree_calls} fused trees — expected exactly one "
              f"per tree", file=sys.stderr)
        bad += 1
    if bad:
        return 1
    print(
        f"mesh smoke OK: {len(mesh_closes)} closes byte-identical "
        f"mesh-vs-cpu, device_sigs={vp['device_sigs']} over "
        f"width={mesh_info.get('mesh_width')} "
        f"({mesh_info.get('kernel')}, routing={vp.get('routing')}); "
        f"fused trees={tree_calls} readbacks={tree_tr.get('readbacks')} "
        f"({hmesh.get('tree_kernel')})"
    )
    return 0


if __name__ == "__main__":
    sys.exit(run_smoke())
