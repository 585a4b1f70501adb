"""Device-wedge watchdog: a hung accelerator call must degrade the node
to its CPU backends, never freeze it.

An accelerator call can hang indefinitely with the GIL released (a sick
driver, a chip another process holds). These tests plant a
verifier/hasher that blocks forever and assert the planes detect the
wedge, answer every request via the CPU side, and route around the dead
device from then on. Reference stance: a stalled subsystem is a
loudly-reported fault (LoadManager deadlock detector,
src/ripple_core/functional/LoadManager.cpp:180-214), not a silent freeze.
"""

import threading
import time

import numpy as np
import pytest

from stellard_tpu.crypto.backend import (
    BatchHasher,
    BatchVerifier,
    CpuHasher,
    VerifyRequest,
    WatchdogHasher,
)
from stellard_tpu.node.verifyplane import VerifyPlane
from stellard_tpu.protocol.keys import KeyPair
from stellard_tpu.state.shamap import SHAMap, SHAMapItem, TNType, compute_hashes
from stellard_tpu.utils import devicewatch
from stellard_tpu.utils.devicewatch import (
    DeviceHealth,
    DeviceWedged,
    call_with_deadline,
)


@pytest.fixture(autouse=True)
def _fresh_health():
    """The process-wide verdict is sticky by design; tests need it fresh."""
    devicewatch.HEALTH.reset()
    yield
    devicewatch.HEALTH.reset()


class _Wedge(BatchVerifier):
    """verify_batch blocks until released (never, by default)."""

    name = "tpu"

    def __init__(self):
        self.release = threading.Event()
        self.calls = 0

    def verify_batch(self, batch):
        self.calls += 1
        self.release.wait()
        return np.ones(len(batch), bool)


class _WedgeHasher(BatchHasher):
    name = "tpu"

    def __init__(self):
        self.release = threading.Event()

    def prefix_hash_batch(self, prefixes, payloads):
        self.release.wait()
        return CpuHasher().prefix_hash_batch(prefixes, payloads)

    def hash_tree(self, root, cancelled=None, cancel_lock=None) -> int:
        self.release.wait()
        lock = cancel_lock if cancel_lock is not None else threading.Lock()
        with lock:
            if cancelled is not None and cancelled.is_set():
                return 0
            return compute_hashes(root)


def _reqs(n: int) -> list[VerifyRequest]:
    kp = KeyPair.from_seed(b"\x11" * 32)
    out = []
    for i in range(n):
        msg = bytes([i % 256]) * 32
        out.append(VerifyRequest(kp.public, msg, kp.sign(msg)))
    return out


class TestCallWithDeadline:
    def test_fast_call_returns(self):
        h = DeviceHealth()
        assert call_with_deadline(lambda: 42, 5.0, health=h) == 42
        assert not h.dead

    def test_timeout_marks_dead_and_raises(self):
        h = DeviceHealth()
        with pytest.raises(DeviceWedged):
            call_with_deadline(
                lambda: threading.Event().wait(), 0.1, health=h
            )
        assert h.dead
        # later calls refuse instantly (no new sacrificial thread wait)
        t0 = time.perf_counter()
        with pytest.raises(DeviceWedged):
            call_with_deadline(lambda: 1, 5.0, health=h)
        assert time.perf_counter() - t0 < 0.5

    def test_exceptions_propagate(self):
        h = DeviceHealth()
        with pytest.raises(ValueError):
            call_with_deadline(
                lambda: (_ for _ in ()).throw(ValueError("x")), 5.0, health=h
            )
        assert not h.dead


class TestVerifyPlaneWedge:
    def _plane(self, wedge):
        plane = VerifyPlane(
            backend="cpu",  # construct cheap, then plant the wedge
            window_ms=1.0,
            min_device_batch=4,
            device_first_timeout=0.3,
            device_warm_timeout=0.3,
        )
        plane.verifier = wedge
        plane._device_capable = True
        return plane

    def test_wedged_device_falls_back_and_verifies(self):
        wedge = _Wedge()
        plane = self._plane(wedge)
        reqs = _reqs(16)
        t0 = time.perf_counter()
        out = plane.verify_many(reqs)
        assert out.all()  # every signature still verified (CPU side)
        assert time.perf_counter() - t0 < 10
        assert plane.device_wedged
        assert wedge.calls == 1
        stats = plane.get_json()
        assert stats["device_wedged"] is True
        assert stats["cpu_sigs"] == 16 and stats["device_sigs"] == 0

    def test_after_wedge_device_never_retried(self):
        wedge = _Wedge()
        plane = self._plane(wedge)
        plane.verify_many(_reqs(8))
        assert wedge.calls == 1
        for _ in range(3):
            out = plane.verify_many(_reqs(8))
            assert out.all()
        assert wedge.calls == 1  # no re-exploration of a dead device

    def test_node_closes_ledgers_through_a_wedged_device(self):
        """Node-level wiring: a validator whose device wedges mid-run
        must keep accepting transactions and closing ledgers on the CPU
        side — the subsystem degrades, the chain does not stall."""
        from stellard_tpu.node.config import Config
        from stellard_tpu.node.node import Node
        from stellard_tpu.protocol.formats import TxType
        from stellard_tpu.protocol.sfields import sfAmount, sfDestination
        from stellard_tpu.protocol.stamount import STAmount
        from stellard_tpu.protocol.sttx import SerializedTransaction

        node = Node(Config()).setup()
        try:
            # plant a wedge in the live plane (as if the device hung);
            # min_device_batch=1 so even single-signature batches explore
            # the device (normal routing would shield them from it)
            node.verify_plane.verifier = _Wedge()
            node.verify_plane._device_capable = True
            node.verify_plane._t_first = 0.3
            node.verify_plane._t_warm = 0.3
            node.verify_plane.min_device_batch = 1
            node.verify_plane.model.min_device_batch = 1
            master = KeyPair.from_passphrase("masterpassphrase")
            dest = KeyPair.from_seed(b"\x33" * 32)
            done = threading.Semaphore(0)
            results = []

            def cb(tx, ter, applied):
                results.append((ter, applied))
                done.release()

            for seq in (1, 2):
                tx = SerializedTransaction.build(
                    TxType.ttPAYMENT, master.account_id, seq, 10,
                    {sfAmount: STAmount.from_drops(300_000_000),
                     sfDestination: dest.account_id},
                )
                tx.sign(master)
                # async intake: signature rides the verify plane, which
                # explores the (wedged) device on the first batch
                node.ops.submit_transaction(tx, cb)
                assert done.acquire(timeout=30)
                node.ops.accept_ledger()
            assert node.ledger_master.closed_ledger().seq >= 3
            assert all(applied for _, applied in results), results
            assert node.verify_plane.device_wedged
            assert node.verify_plane.get_json()["cpu_sigs"] >= 2
        finally:
            node.stop()

    def test_healthy_device_unaffected(self):
        class _Ok(BatchVerifier):
            name = "tpu"

            def verify_batch(self, batch):
                from stellard_tpu.crypto.backend import CpuVerifier

                return CpuVerifier(threads=1).verify_batch(batch)

        plane = self._plane(_Ok())
        out = plane.verify_many(_reqs(8))
        assert out.all()
        assert not plane.device_wedged
        assert plane.get_json()["device_sigs"] == 8


class TestWatchdogHasher:
    def _map(self, n=12) -> SHAMap:
        m = SHAMap(TNType.ACCOUNT_STATE)
        for i in range(n):
            m.set_item(SHAMapItem(bytes([i]) * 32, b"payload-%d" % i))
        return m

    def test_wedged_batch_hash_falls_back(self):
        wd = WatchdogHasher(
            _WedgeHasher(), CpuHasher(), first_timeout=0.2, warm_timeout=0.2
        )
        out = wd.prefix_hash_batch([0x12345678], [b"abc"])
        assert out == CpuHasher().prefix_hash_batch([0x12345678], [b"abc"])
        assert wd.device_wedged

    def test_wedged_tree_hash_matches_host(self):
        expect = self._map()
        expect_hash = expect.get_hash()

        wd = WatchdogHasher(
            _WedgeHasher(), CpuHasher(), first_timeout=0.2, warm_timeout=0.2
        )
        m = self._map()
        m.hash_batch = wd
        assert m.get_hash() == expect_hash  # fallback path, same root hash
        assert wd.device_wedged

    def test_abandoned_call_cannot_stamp_the_tree(self):
        """The zombie thread finishing late must not write node hashes."""
        inner = _WedgeHasher()
        wd = WatchdogHasher(
            inner, CpuHasher(), first_timeout=0.2, warm_timeout=0.2
        )
        m = self._map()
        before = m.get_hash()  # plain host hashing for the expectation
        m2 = self._map()
        m2.hash_batch = wd
        assert m2.get_hash() == before
        inner.release.set()  # zombie wakes up — sees cancelled, returns 0
        time.sleep(0.2)
        assert m2.get_hash() == before

    def test_healthy_inner_passthrough(self):
        wd = WatchdogHasher(CpuHasher(), CpuHasher(), first_timeout=5.0)
        out = wd.prefix_hash_batch([0x11111111], [b"x"])
        assert out == CpuHasher().prefix_hash_batch([0x11111111], [b"x"])
        assert not wd.device_wedged

    def test_inner_without_hash_tree_still_used_when_healthy(self):
        """A healthy inner lacking hash_tree (e.g. the native cpp hasher)
        must hash trees THROUGH the watchdog's batch path, not silently
        via the fallback (review finding r4)."""

        class _Counting(BatchHasher):
            name = "cpp"
            calls = 0

            def prefix_hash_batch(self, prefixes, payloads):
                self.calls += 1
                return CpuHasher().prefix_hash_batch(prefixes, payloads)

        inner, fb = _Counting(), _Counting()
        wd = WatchdogHasher(inner, fb, first_timeout=5.0, warm_timeout=5.0)
        expect = self._map().get_hash()
        m = self._map()
        m.hash_batch = wd
        assert m.get_hash() == expect
        assert inner.calls > 0  # the watched inner did the level batches
        assert fb.calls == 0  # the fallback was never touched


class TestHashCostRouting:
    """WatchdogHasher's measured-cost routing (the VerifyPlane stance
    applied to hashing): the device must EARN traffic — a measured-slow
    device floors at the host path with bounded re-exploration, and an
    unmeasured device is explored (first, compile-laden sample
    discarded)."""

    class _Fake:
        name = "fake"

        def __init__(self, delay_s):
            self.delay = delay_s
            self.calls = 0
            self.device_nodes = 0
            self.host_nodes = 0

        def prefix_hash_batch(self, prefixes, payloads):
            import hashlib
            import time as _t

            self.calls += 1
            _t.sleep(self.delay)
            return [
                hashlib.sha512(p.to_bytes(4, "big") + d).digest()[:32]
                for p, d in zip(prefixes, payloads)
            ]

    def _mk(self, dev_delay, host_delay, routing="cost"):
        from stellard_tpu.crypto.backend import WatchdogHasher

        dev = self._Fake(dev_delay)
        host = self._Fake(host_delay)
        w = WatchdogHasher(dev, host, first_timeout=30, warm_timeout=30,
                           routing=routing)
        return w, dev, host

    def test_slow_device_floors_at_host(self):
        w, dev, host = self._mk(dev_delay=0.02, host_delay=0.0)
        batch = ([0x1234] * 8, [b"x" * 40] * 8)
        for _ in range(12):
            w.prefix_hash_batch(*batch)
        # exploration: first (discarded) + second (recorded) device
        # samples, one host measurement, then the host wins every call
        assert dev.calls <= 3
        assert host.calls >= 8

    def test_fast_device_keeps_traffic(self):
        w, dev, host = self._mk(dev_delay=0.0, host_delay=0.02)
        batch = ([0x1234] * 8, [b"x" * 40] * 8)
        for _ in range(12):
            w.prefix_hash_batch(*batch)
        # one host measurement for the comparison; device keeps the rest
        assert host.calls == 1
        assert dev.calls >= 10

    def test_device_mode_restores_unconditional_routing(self):
        w, dev, host = self._mk(dev_delay=0.02, host_delay=0.0,
                                routing="device")
        batch = ([0x1234] * 4, [b"x" * 40] * 4)
        for _ in range(6):
            w.prefix_hash_batch(*batch)
        assert host.calls == 0 and dev.calls == 6

    def test_results_identical_across_routes(self):
        w, dev, host = self._mk(dev_delay=0.01, host_delay=0.0)
        batch = ([0x1234] * 4, [b"a" * 33, b"b" * 100, b"", b"c" * 7])
        outs = {tuple(w.prefix_hash_batch(*batch)) for _ in range(8)}
        assert len(outs) == 1  # device and host routes agree bytes-for-bytes
