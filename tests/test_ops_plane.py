"""Ops-plane round-out: TaggedCache/KeyCache, NodeStore --import
migration, the sustain supervisor, and validator file/site sources.
"""

from __future__ import annotations

import http.server
import threading

import pytest

from stellard_tpu.node.config import Config
from stellard_tpu.node.sitefiles import (
    fetch_site_validators,
    load_validators_file,
    parse_validators_text,
)
from stellard_tpu.nodestore.core import NodeObjectType, make_database
from stellard_tpu.protocol.keys import KeyPair
from stellard_tpu.utils.taggedcache import KeyCache, TaggedCache


class TestTaggedCache:
    def test_lru_and_expiry(self):
        now = [0.0]
        c = TaggedCache("t", target_size=3, expiration_s=10.0,
                        clock=lambda: now[0])
        for i in range(4):
            c.put(i, f"v{i}")
        assert len(c) == 3 and c.get(0) is None  # oldest evicted
        assert c.get(3) == "v3"
        now[0] = 11.0
        assert c.get(3) is None  # expired
        assert c.get_json()["hits"] == 1

    def test_fetch_loads_once(self):
        c = TaggedCache("t", target_size=8)
        calls = []

        def loader():
            calls.append(1)
            return "x"

        assert c.fetch("k", loader) == "x"
        assert c.fetch("k", loader) == "x"
        assert len(calls) == 1

    def test_sweep_and_keycache(self):
        now = [0.0]
        kc = KeyCache("full_below", expiration_s=5.0, clock=lambda: now[0])
        kc.insert(b"\x01")
        assert b"\x01" in kc
        now[0] = 6.0
        assert kc.sweep() == 1
        assert b"\x01" not in kc


class TestNodeStoreImport:
    def test_migrates_all_objects(self, tmp_path):
        from stellard_tpu.__main__ import _import_nodestore

        src = make_database(type="sqlite", path=str(tmp_path / "src.db"),
                            async_writes=False)
        for i in range(40):
            src.store(NodeObjectType.ACCOUNT_NODE, i.to_bytes(32, "big"),
                      b"obj-%d" % i)
        src.close()
        cfg = Config(node_db_type="sqlite",
                     node_db_path=str(tmp_path / "dst.db"))
        assert _import_nodestore(f"sqlite:{tmp_path/'src.db'}", cfg) == 0
        dst = make_database(type="sqlite", path=str(tmp_path / "dst.db"),
                            async_writes=False)
        assert sum(1 for _ in dst.backend.iterate()) == 40
        assert dst.fetch((11).to_bytes(32, "big")).data == b"obj-11"
        dst.close()


class TestSustain:
    def test_restarts_until_clean_exit(self, monkeypatch):
        import stellard_tpu.__main__ as m

        codes = iter([1, 1, 0])
        calls = []

        def fake_call(cmd):
            calls.append(cmd)
            return next(codes)

        monkeypatch.setattr("subprocess.call", fake_call)
        monkeypatch.setattr("time.sleep", lambda s: None)
        rc = m._sustain(["--sustain", "-a", "--rpc_port", "5005"])
        assert rc == 0
        assert len(calls) == 3
        # the child never re-enters sustain mode
        assert all("--sustain" not in c for c in calls)
        assert all("-a" in c for c in calls)


class TestValidatorSources:
    def test_parse_plain_and_sectioned(self):
        v1 = KeyPair.from_passphrase("vs-1").human_node_public
        v2 = KeyPair.from_passphrase("vs-2").human_node_public
        plain = f"# comment\n{v1} first validator\n{v2}\n"
        assert parse_validators_text(plain) == [
            (v1, "first validator"), (v2, "")
        ]
        sectioned = (
            "[domain]\nexample.com\n\n[validators]\n"
            f"{v1} alpha\n[other]\nignored\n"
        )
        assert parse_validators_text(sectioned) == [(v1, "alpha")]

    def test_node_loads_file_and_site_sources(self, tmp_path):
        from stellard_tpu.node.node import Node

        v_file = KeyPair.from_passphrase("vs-file").human_node_public
        v_site = KeyPair.from_passphrase("vs-site").human_node_public
        vf = tmp_path / "validators.txt"
        vf.write_text(f"{v_file} from-file\n")

        site_text = f"[validators]\n{v_site} from-site\n".encode()

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                self.send_response(200)
                self.end_headers()
                self.wfile.write(site_text)

            def log_message(self, *a):
                pass

        httpd = http.server.HTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            cfg = Config(
                standalone=True, signature_backend="cpu",
                validators_file=str(vf),
                validators_site=(
                    f"http://127.0.0.1:{httpd.server_address[1]}/stellar.txt"
                ),
            )
            node = Node(cfg).setup()
            try:
                import time

                from stellard_tpu.protocol.keys import decode_node_public

                assert decode_node_public(v_file) in node.unl
                # the site source fetches on a background thread (startup
                # must not block on a remote site): wait for it
                deadline = time.monotonic() + 10
                while (
                    decode_node_public(v_site) not in node.unl
                    and time.monotonic() < deadline
                ):
                    time.sleep(0.05)
                assert decode_node_public(v_site) in node.unl
                entries = {e["pubkey_validator"]: e["comment"]
                           for e in node.unl.get_json()}
                assert entries[v_file] == "from-file"
                assert entries[v_site] == "from-site"
            finally:
                node.stop()
        finally:
            httpd.shutdown()

    def test_unreachable_site_does_not_kill_node(self):
        from stellard_tpu.node.node import Node

        cfg = Config(
            standalone=True, signature_backend="cpu",
            validators_site="http://127.0.0.1:9/stellar.txt",
        )
        node = Node(cfg).setup()
        node.stop()


class TestLocalCredentials:
    def test_node_identity_persists_across_restarts(self, tmp_path):
        from stellard_tpu.node.node import Node

        cfg = Config(standalone=True, signature_backend="cpu",
                     database_path=str(tmp_path / "tx.db"))
        n1 = Node(cfg).setup()
        pub1 = n1.node_keys.public
        n1.stop()
        n2 = Node(cfg).setup()
        try:
            assert n2.node_keys.public == pub1  # wallet.db role
        finally:
            n2.stop()

    def test_ephemeral_without_database_path(self):
        from stellard_tpu.node.node import Node

        cfg = Config(standalone=True, signature_backend="cpu")
        n = Node(cfg).setup()
        try:
            assert n.node_keys is not None
        finally:
            n.stop()


class TestIntakeOrdering:
    """Ordered intake drain (networkops._enqueue_intake): same-account
    bursts must apply in submission order (no spurious terPRE_SEQ
    holds), and a poisoned entry must neither drop the rest of its
    batch nor wedge the drain flag."""

    def _node(self):
        from stellard_tpu.node.config import Config
        from stellard_tpu.node.node import Node

        return Node(Config(signature_backend="cpu")).setup()

    def test_burst_applies_in_order_no_holds(self):
        import threading

        from stellard_tpu.protocol.formats import TxType
        from stellard_tpu.protocol.keys import KeyPair
        from stellard_tpu.protocol.sfields import sfAmount, sfDestination
        from stellard_tpu.protocol.stamount import STAmount
        from stellard_tpu.protocol.sttx import SerializedTransaction

        node = self._node()
        try:
            master = KeyPair.from_passphrase("masterpassphrase")
            dest = KeyPair.from_passphrase("intake-dest")
            txs = []
            for i in range(200):
                tx = SerializedTransaction.build(
                    TxType.ttPAYMENT, master.account_id, 1 + i, 10,
                    {sfAmount: STAmount.from_drops(250_000_000),
                     sfDestination: dest.account_id},
                )
                tx.sign(master)
                txs.append(tx)
            done = threading.Semaphore(0)
            results = []

            def cb(tx, ter, applied):
                results.append((ter, applied))
                done.release()

            for tx in txs:
                node.ops.submit_transaction(tx, cb)
            for _ in txs:
                assert done.acquire(timeout=30)
            assert node.ops.stats.get("held", 0) == 0, "burst was held"
            assert all(applied for _, applied in results)
            node.ops.accept_ledger()
            assert node.ledger_master.closed_ledger().seq == 2
        finally:
            node.stop()

    def test_poisoned_callback_does_not_wedge_intake(self):
        import threading

        from stellard_tpu.protocol.formats import TxType
        from stellard_tpu.protocol.keys import KeyPair
        from stellard_tpu.protocol.sfields import sfAmount, sfDestination
        from stellard_tpu.protocol.stamount import STAmount
        from stellard_tpu.protocol.sttx import SerializedTransaction

        node = self._node()
        try:
            master = KeyPair.from_passphrase("masterpassphrase")
            dest = KeyPair.from_passphrase("intake-dest-2")

            def payment(seq):
                tx = SerializedTransaction.build(
                    TxType.ttPAYMENT, master.account_id, seq, 10,
                    {sfAmount: STAmount.from_drops(250_000_000),
                     sfDestination: dest.account_id},
                )
                tx.sign(master)
                return tx

            done = threading.Semaphore(0)

            def bomb(tx, ter, applied):
                done.release()
                raise RuntimeError("poisoned callback")

            def ok_cb(tx, ter, applied):
                done.release()

            node.ops.submit_transaction(payment(1), bomb)
            node.ops.submit_transaction(payment(2), ok_cb)
            for _ in range(2):
                assert done.acquire(timeout=30)
            # intake must still be alive for NEW submissions
            node.ops.submit_transaction(payment(3), ok_cb)
            assert done.acquire(timeout=30)
            assert not node.ops._intake_scheduled or node.ops._intake
        finally:
            node.stop()
