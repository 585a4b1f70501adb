"""Load plane: fee escalation, backlog shed, deadlock watchdog.

Reference behaviors (SURVEY §2.2 LoadFeeTrack/LoadMonitor, §2.1
LoadManager; VERDICT r2 'no overload behavior is testable'):
- sustained job-queue overload raises the local load fee geometrically;
  recovery decays it back to normal (LoadFeeTrackImp.cpp),
- the scaled open-ledger fee actually rejects under-paying transactions
  with telINSUF_FEE_P (Transactor::payFee + Ledger::scaleFeeLoad),
- network-tx intake sheds outright past a 100-job backlog
  (PeerImp.cpp:64-66),
- the deadlock canary fires when the heartbeat stops (LoadManager.cpp
  81-204).
"""

from __future__ import annotations

import time

import pytest

from stellard_tpu.node.config import Config
from stellard_tpu.node.jobqueue import JobQueue, JobType
from stellard_tpu.node.loadmgr import (
    LoadFeeTrack,
    LoadManager,
    NORMAL_FEE,
    TX_BACKLOG_SHED,
)
from stellard_tpu.node.node import Node
from stellard_tpu.protocol.formats import TxType
from stellard_tpu.protocol.keys import KeyPair
from stellard_tpu.protocol.sfields import sfAmount, sfDestination
from stellard_tpu.protocol.stamount import STAmount
from stellard_tpu.protocol.sttx import SerializedTransaction
from stellard_tpu.protocol.ter import TER

XRP = 1_000_000


class TestLoadFeeTrack:
    def test_raise_lower_dynamics(self):
        ft = LoadFeeTrack()
        assert ft.load_factor == NORMAL_FEE and not ft.is_loaded
        for _ in range(4):
            ft.raise_local_fee()
        raised = ft.load_factor
        assert raised > NORMAL_FEE
        # the single fee-scaling implementation is Ledger.scale_fee_load,
        # driven by the factor stamped from this track
        from stellard_tpu.state.ledger import Ledger

        led = Ledger(seq=1)
        led.load_factor = raised
        assert led.scale_fee_load(10) == 10 * raised // NORMAL_FEE
        assert led.scale_fee_load(10, admin=True) == 10  # admin never scaled
        while ft.is_loaded:
            ft.lower_local_fee()
        assert ft.load_factor == NORMAL_FEE
        led.load_factor = ft.load_factor
        assert led.scale_fee_load(10) == 10

    def test_remote_fee_merges(self):
        ft = LoadFeeTrack()
        ft.set_remote_fee(512)
        assert ft.load_factor == 512  # max(local, remote)

    def test_remote_report_freshness_ordering(self):
        """A relayed copy of a report we already hold (same or older
        report_time) must neither refresh its TTL nor overwrite a fresher
        direct report — only strictly newer reports land (reference:
        TMCluster carries the ORIGINAL reportTime so receivers keep only
        the newest)."""
        ft = LoadFeeTrack()
        src = b"\x02" * 33
        ft.set_remote_fee(512, source=src, report_time=100)
        # stale relay: older report_time, different fee -> dropped
        ft.set_remote_fee(999, source=src, report_time=99)
        ft.set_remote_fee(999, source=src, report_time=100)  # same: dropped
        assert ft.load_factor == 512
        reports = ft.remote_reports()
        assert reports == [(src, 512, 100)]
        # strictly newer report wins (even lowering the fee)
        ft.set_remote_fee(300, source=src, report_time=101)
        assert ft.remote_reports() == [(src, 300, 101)]

    def test_remote_report_ttl_not_refreshed_by_relay(self):
        """Replaying the same report right before expiry must not extend
        its life — a crashed member's high-load report ages out even while
        other members keep relaying it."""
        ft = LoadFeeTrack()
        ft.REMOTE_TTL = 0.1
        src = b"\x03" * 33
        ft.set_remote_fee(800, source=src, report_time=50)
        time.sleep(0.06)
        ft.set_remote_fee(800, source=src, report_time=50)  # relay echo
        time.sleep(0.06)  # past the ORIGINAL expiry
        assert ft.load_factor == NORMAL_FEE
        assert ft.remote_reports() == []


class TestLoadFeeTrackConcurrency:
    """The track is hammered from several threads at once in production:
    the LoadManager watchdog (raise/lower), peer threads (set_remote_fee)
    and the TxQ close path (set_queue_fee), while RPC workers read
    load_factor. These tests pin the invariants that must hold under
    that interleaving."""

    def test_concurrent_raise_lower_remote_bounded(self):
        import threading

        ft = LoadFeeTrack()
        from stellard_tpu.node.loadmgr import MAX_FEE

        stop = threading.Event()
        violations = []

        def reader():
            while not stop.is_set():
                f = ft.load_factor
                if not (NORMAL_FEE <= f <= MAX_FEE):
                    violations.append(f)
                j = ft.get_json()
                if j["load_factor"] < max(j["local_fee"], j["remote_fee"],
                                          j["queue_fee"]):
                    violations.append(j)

        def raiser():
            for _ in range(400):
                ft.raise_local_fee()

        def lowerer():
            for _ in range(400):
                ft.lower_local_fee()

        def remote(i):
            src = bytes([i]) * 33
            for t in range(200):
                ft.set_remote_fee(NORMAL_FEE * (1 + t % 7), source=src,
                                  report_time=t)

        threads = (
            [threading.Thread(target=raiser) for _ in range(3)]
            + [threading.Thread(target=lowerer) for _ in range(3)]
            + [threading.Thread(target=remote, args=(i,)) for i in range(3)]
            + [threading.Thread(target=reader) for _ in range(2)]
        )
        for t in threads[:-2]:
            t.start()
        for t in threads[-2:]:
            t.start()
        for t in threads[:-2]:
            t.join()
        stop.set()
        for t in threads[-2:]:
            t.join()
        assert not violations
        # after the storm: lowering fully decays back to normal
        for _ in range(200):
            ft.lower_local_fee()
        ft._remote.clear()
        ft.set_queue_fee(0)
        assert ft.load_factor == NORMAL_FEE

    def test_load_factor_monotone_under_pure_raise_flood(self):
        """During a sustained overload (only raises arriving, remote
        reports static) sampled load_factor must never move DOWN — a
        dip would let a flood burst through under the stale lower fee."""
        import threading

        ft = LoadFeeTrack()
        ft.set_remote_fee(512, source=b"\x09" * 33, report_time=1)
        samples = []
        stop = threading.Event()

        def sampler():
            while not stop.is_set():
                samples.append(ft.load_factor)

        s = threading.Thread(target=sampler)
        s.start()
        for _ in range(300):
            ft.raise_local_fee()
        stop.set()
        s.join()
        assert samples == sorted(samples)

    def test_stale_remote_expiry_under_concurrent_readers(self):
        """Remote-report expiry is evaluated inside load_factor reads;
        concurrent readers must agree the report died after its TTL and
        the fee floor returns to the local component."""
        import threading

        ft = LoadFeeTrack()
        ft.REMOTE_TTL = 0.05
        ft.set_remote_fee(4096, source=b"\x0a" * 33, report_time=7)
        assert ft.load_factor == 4096
        time.sleep(0.08)
        results = []
        threads = [
            threading.Thread(target=lambda: results.append(ft.load_factor))
            for _ in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == [NORMAL_FEE] * 8
        assert ft.remote_reports() == []


class TestLoadManager:
    def test_overload_raises_then_recovers(self):
        jq = JobQueue(threads=2)
        ft = LoadFeeTrack()
        lm = LoadManager(jq, ft)
        # saturate with slow jtTRANSACTION jobs until the EWMA (which
        # includes queue wait) exceeds the 250ms target
        for _ in range(60):
            jq.add_job(JobType.jtTRANSACTION, "slow", lambda: time.sleep(0.02))
        deadline = time.monotonic() + 10
        while not jq.is_overloaded() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert jq.is_overloaded()
        lm.tick()
        assert ft.is_loaded
        jq.drain(10)
        # queue idle: ticks decay the fee back to normal
        for _ in range(50):
            lm.tick()
        assert not ft.is_loaded
        jq.stop()

    def test_deadlock_canary_fires_once(self):
        now = [0.0]
        fired = []
        lm = LoadManager(
            None,
            LoadFeeTrack(),
            clock=lambda: now[0],
            deadlock_timeout=500.0,
            on_deadlock=lambda: fired.append(1),
        )
        lm.jq = _IdleJq()
        lm.arm()
        now[0] = 499.0
        lm.tick()
        assert not fired
        lm.reset_deadlock_detector()
        now[0] = 998.0
        lm.tick()
        assert not fired  # heartbeat kept it alive
        now[0] = 1600.0
        lm.tick()
        lm.tick()
        assert fired == [1]  # fires exactly once


class _IdleJq:
    def is_overloaded(self):
        return False


class TestEndToEndLoad:
    @pytest.fixture()
    def node(self):
        n = Node(Config(standalone=True, signature_backend="cpu")).setup()
        yield n
        n.stop()

    def test_scaled_fee_rejects_underpayer(self, node):
        """With load escalation active, a tx paying the normal fee gets
        telINSUF_FEE_P; paying the scaled fee passes."""
        alice = KeyPair.from_passphrase("alice")
        master = node.master_keys
        for _ in range(8):
            node.fee_track.raise_local_fee()
        factor = node.fee_track.load_factor
        assert factor > NORMAL_FEE
        scaled = 10 * factor // NORMAL_FEE

        def pay(seq, fee):
            tx = SerializedTransaction.build(
                TxType.ttPAYMENT, master.account_id, seq, fee,
                {sfAmount: STAmount.from_drops(100 * XRP),
                 sfDestination: alice.account_id},
            )
            tx.sign(master)
            return node.ops.process_transaction(tx)

        ter, applied = pay(1, 10)
        assert ter == TER.telINSUF_FEE_P and not applied
        ter, applied = pay(1, scaled)
        assert ter == TER.tesSUCCESS and applied
        # load drops back to normal: base fee applies again
        while node.fee_track.is_loaded:
            node.fee_track.lower_local_fee()
        ter, applied = pay(2, 10)
        assert ter == TER.tesSUCCESS and applied

    def test_backlog_shed(self, node):
        """submit_transaction drops network txs past the 100-job backlog."""
        # wedge the queue with blockers so jtTRANSACTION jobs pile up
        import threading

        gate = threading.Event()
        for _ in range(len(node.job_queue._threads)):
            node.job_queue.add_job(
                JobType.jtTRANSACTION, "blocker", lambda: gate.wait(30)
            )
        alice = KeyPair.from_passphrase("alice")
        master = node.master_keys

        def submit(i):
            tx = SerializedTransaction.build(
                TxType.ttPAYMENT, master.account_id, i + 1, 10,
                {sfAmount: STAmount.from_drops(XRP),
                 sfDestination: alice.account_id},
            )
            tx.sign(master)
            node.ops.submit_transaction(tx)

        # wave 1: fill the backlog (verification is async, so wait for
        # the verified txs to land). Intake batching keeps the QUEUED
        # job count at ~1 — the backlog accumulates in ops._intake, and
        # the shed gate counts job_count + len(_intake); assert on the
        # gate's own quantity.
        def backlog():
            return (node.job_queue.get_job_count(JobType.jtTRANSACTION)
                    + len(node.ops._intake))

        for i in range(TX_BACKLOG_SHED + 20):
            submit(i)
        deadline = time.monotonic() + 15
        while backlog() <= TX_BACKLOG_SHED and time.monotonic() < deadline:
            time.sleep(0.02)
        assert backlog() > TX_BACKLOG_SHED
        # wave 2: intake now sheds at the door
        for i in range(TX_BACKLOG_SHED + 20, TX_BACKLOG_SHED + 40):
            submit(i)
        assert node.ops.stats.get("shed", 0) > 0
        gate.set()
        node.job_queue.drain(15)
