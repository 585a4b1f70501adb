"""4-validator private net as SEPARATE PROCESSES over real sockets —
the deployment BASELINE config #4 describes (one host per validator),
driven end-to-end through the CLI + RPC planes (reference: the Vagrant
one-box testnet, doc/stellard-example.cfg private-net template).

Each validator is `python -m stellard_tpu --conf <ini> --start`: the full
application container (NodeStore, CLF mirror, JobQueue, VerifyPlane,
TcpOverlay + ValidatorNode consensus, HTTP RPC). The test asserts the
net closes ledgers in agreement and that a payment submitted over RPC to
one validator commits network-wide.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

import pytest

from stellard_tpu.protocol.keys import KeyPair

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

# shared net-lab helpers (tools/netlab.py) — one config template /
# launcher / RPC helper for this suite AND tools/chaos_soak.py
from netlab import SPEED, free_ports, rpc, wait_until  # noqa: E402


@pytest.fixture(scope="module")
def net(tmp_path_factory):
    n = 4
    tmp = tmp_path_factory.mktemp("mpnet")
    ports = free_ports(3 * n)
    peer_ports, rpc_ports, ws_ports = ports[:n], ports[n : 2 * n], ports[2 * n :]
    keys = [KeyPair.from_passphrase(f"mp-val-{i}") for i in range(n)]

    procs = []
    for i in range(n):
        others_keys = "\n".join(
            keys[j].human_node_public for j in range(n) if j != i
        )
        others_addrs = "\n".join(
            f"127.0.0.1 {peer_ports[j]}" for j in range(n) if j != i
        )
        cfg = f"""
[standalone]
0

[node_db]
type=memory

[signature_backend]
type=cpu

[validation_seed]
{keys[i].human_seed}

[validators]
{others_keys}

[validation_quorum]
3

[peer_port]
{peer_ports[i]}

[peer_ssl]
require

[ips]
{others_addrs}

[clock_speed]
{SPEED}

[rpc_port]
{rpc_ports[i]}

[websocket_port]
{ws_ports[i]}
"""
        path = tmp / f"validator-{i}.cfg"
        path.write_text(cfg)

    procs.extend([None] * n)

    def respawn(i: int) -> subprocess.Popen:
        """(Re)launch validator i from its config. On relaunch the memory
        node_db means a FRESH genesis that must catch up over the wire."""
        env = dict(os.environ)
        # a chip belongs to one process: a multi-process net cannot share it
        env["JAX_PLATFORMS"] = "cpu"
        p = subprocess.Popen(
            [sys.executable, "-m", "stellard_tpu", "--conf",
             str(tmp / f"validator-{i}.cfg"), "--start"],
            cwd=REPO,
            env=env,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        )
        procs[i] = p
        return p

    for i in range(n):
        respawn(i)

    try:
        yield {"rpc_ports": rpc_ports, "ws_ports": ws_ports, "procs": procs,
               "respawn": respawn}
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()


@pytest.mark.slow
class TestMultiProcessNet:
    def test_ledgers_close_and_agree(self, net):
        rpc_ports = net["rpc_ports"]

        # all four servers come up and connect to each other
        assert wait_until(
            lambda: all(
                rpc(p, "server_info")["info"]["peers"] == 3 for p in rpc_ports
            ),
            # four fresh interpreters share 1-2 cores on this box; cold
            # startup alone can eat ~35s under ambient load (measured),
            # so the mesh wait must not be the startup race's victim
            timeout=90,
        ), "validators never fully meshed"

        # the net closes ledgers: every validator advances past seq 3
        def advanced():
            seqs = [
                rpc(p, "server_info")["info"]["validated_ledger"]["seq"]
                for p in rpc_ports
            ]
            return all(s >= 3 for s in seqs)

        assert wait_until(advanced, timeout=60), "net never closed 3 ledgers"

        # agreement: at a common validated sequence the hashes match
        infos = [rpc(p, "server_info")["info"] for p in rpc_ports]
        common = min(i["validated_ledger"]["seq"] for i in infos)
        hashes = {
            rpc(p, "ledger", {"ledger_index": common})["ledger"]["hash"]
            for p in rpc_ports
        }
        assert len(hashes) == 1, f"fork at seq {common}: {hashes}"

    def test_rpc_payment_commits_network_wide(self, net):
        rpc_ports = net["rpc_ports"]
        alice = KeyPair.from_passphrase("mp-alice")
        amount = 5_000 * 1_000_000

        res = rpc(
            rpc_ports[0],
            "submit",
            {
                "secret": "masterpassphrase",
                "tx_json": {
                    "TransactionType": "Payment",
                    "Account": KeyPair.from_passphrase(
                        "masterpassphrase"
                    ).human_account_id,
                    "Destination": alice.human_account_id,
                    "Amount": str(amount),
                },
            },
            timeout=15.0,
        )
        assert res["engine_result"] in ("tesSUCCESS", "terQUEUED"), res

        # the payment lands in a validated ledger on EVERY validator
        def landed():
            for p in rpc_ports:
                info = rpc(p, "account_info", {"account": alice.human_account_id,
                                               "ledger_index": "validated"})
                if int(info["account_data"]["Balance"]) != amount:
                    return False
            return True

        assert wait_until(landed, timeout=60), "payment never committed net-wide"

    def test_ws_ledger_stream_on_networked_validator(self, net):
        """The WS ledger stream must publish CONSENSUS closes, not just
        standalone ledger_accept ones (the publish path rides the
        overlay's accepted-ledger hook)."""
        from test_rpc_server import WsClient

        ws = WsClient(net["ws_ports"][1])
        try:
            resp = ws.call("subscribe", streams=["ledger"])
            assert resp.get("status") == "success", resp
            # consensus closes arrive as ledgerClosed events
            ws.sock.settimeout(30)
            evt = ws.recv()
            assert evt["type"] == "ledgerClosed", evt
            assert evt["ledger_index"] >= 1
        finally:
            ws.close()

    def test_validator_crash_catchup_rejoin(self, net):
        """Failure recovery across PROCESSES (SURVEY §5 failure
        detection/recovery): kill one validator; the remaining three
        (own validation counts toward quorum, reference accept
        :1023-1045) keep closing; the restarted validator boots from a
        FRESH genesis (memory node_db) and must catch up to the live
        net over the wire (InboundLedger/GetLedger + LCL switch) and
        re-converge on the same hashes."""
        rpc_ports = net["rpc_ports"]
        procs = net["procs"]

        victim = 3
        survivors = [p for i, p in enumerate(rpc_ports) if i != victim]

        # order-independent: wait for a fully-meshed, closing net first
        assert wait_until(
            lambda: all(
                rpc(p, "server_info")["info"]["peers"] == 3
                and rpc(p, "server_info")["info"]["validated_ledger"]["seq"]
                >= 2
                for p in rpc_ports
            ),
            timeout=60,
        ), "net not healthy before the crash"

        procs[victim].terminate()
        procs[victim].wait(timeout=10)

        # the degraded net keeps closing ledgers
        base = max(
            rpc(p, "server_info")["info"]["validated_ledger"]["seq"]
            for p in survivors
        )
        assert wait_until(
            lambda: all(
                rpc(p, "server_info")["info"]["validated_ledger"]["seq"]
                >= base + 2
                for p in survivors
            ),
            timeout=90,
        ), "net stalled after losing one of four validators"

        # restart: fresh genesis, must catch up to the net's ledger
        net["respawn"](victim)
        vport = rpc_ports[victim]

        def caught_up():
            target = max(
                rpc(p, "server_info")["info"]["validated_ledger"]["seq"]
                for p in survivors
            )
            mine = rpc(vport, "server_info")["info"]["validated_ledger"]["seq"]
            return mine >= target - 1 and mine > base

        assert wait_until(caught_up, timeout=120), (
            "restarted validator never caught up to the live net"
        )

        # convergence: pick a sequence the REJOINED validator holds (its
        # fresh-genesis history only starts at the LCL-switch point) and
        # wait until every node serves the same hash for it
        def converged():
            seq = rpc(vport, "server_info")["info"]["validated_ledger"]["seq"]
            if seq <= base:
                return False
            hashes = set()
            for p in rpc_ports:
                led = rpc(p, "ledger", {"ledger_index": seq}).get("ledger")
                if led is None:  # a lagging node hasn't got this seq yet
                    return False
                hashes.add(led["hash"])
            return len(hashes) == 1

        assert wait_until(converged, timeout=60), (
            "validators never converged on one post-rejoin ledger hash"
        )

    def test_load_restart_convergence(self, net):
        """CI-sized version of the build-time net soak that exposed the
        round-4 fork-repair fixes: continuous submissions while one
        validator restarts from fresh genesis; afterwards every
        validator's QUORUM-VALIDATED chain must advance and agree."""
        import threading

        rpc_ports = net["rpc_ports"]
        procs = net["procs"]

        assert wait_until(
            lambda: all(
                rpc(p, "server_info")["info"]["peers"] == 3 for p in rpc_ports
            ),
            timeout=60,
        ), "net not meshed before load"

        master = KeyPair.from_passphrase("masterpassphrase")
        stop = threading.Event()
        submitted = [0]

        def load():
            i = 0
            while not stop.is_set():
                try:
                    rpc(
                        rpc_ports[i % 4],
                        "submit",
                        {
                            "secret": "masterpassphrase",
                            "tx_json": {
                                "TransactionType": "Payment",
                                "Account": master.human_account_id,
                                "Destination": KeyPair.from_passphrase(
                                    f"lr-{i % 3}"
                                ).human_account_id,
                                "Amount": str(1_500_000_000),
                            },
                        },
                        timeout=15,
                    )
                    submitted[0] += 1
                except Exception:
                    pass
                i += 1
                stop.wait(1.5)

        t = threading.Thread(target=load, daemon=True)
        t.start()
        try:
            time.sleep(12)
            victim = 1
            procs[victim].terminate()
            procs[victim].wait(timeout=10)
            time.sleep(4)
            net["respawn"](victim)
            time.sleep(20)
        finally:
            stop.set()
            t.join(timeout=10)
        assert submitted[0] > 0

        def validated_seqs():
            return [
                rpc(p, "server_info")["info"]["validated_ledger"]["seq"]
                for p in rpc_ports
            ]

        target = max(validated_seqs()) + 2
        assert wait_until(
            lambda: min(validated_seqs()) >= target, timeout=120
        ), f"validated chains never converged: {validated_seqs()}"
        common = min(validated_seqs())
        hashes = {
            rpc(p, "ledger", {"ledger_index": common})["ledger"]["hash"]
            for p in rpc_ports
        }
        assert len(hashes) == 1, f"fork at {common}: {hashes}"
