"""Latency-aware VerifyPlane dispatch (VERDICT r2 #1b).

The plane must learn, from real measurements, when the device batch
kernel beats the threaded CPU path, and route each batch accordingly —
trickled submissions must not pay the device kernel latency.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from stellard_tpu.crypto.backend import (
    BatchVerifier,
    VerifyRequest,
    register_verifier,
)
from stellard_tpu.node.verifyplane import VerifyPlane, _LatencyModel
from stellard_tpu.protocol.keys import KeyPair


class FakeDeviceVerifier(BatchVerifier):
    """Deterministic 'device': fixed 50ms kernel latency per call."""

    name = "fake-device"
    kernel_ms = 50.0

    def __init__(self, **_):
        self.calls = []

    def verify_batch(self, batch):
        self.calls.append(len(batch))
        time.sleep(self.kernel_ms / 1000.0)
        return np.ones(len(batch), bool)


register_verifier("fake-device", FakeDeviceVerifier)


def reqs(n: int) -> list[VerifyRequest]:
    k = KeyPair.from_passphrase("vp-policy")
    m = b"\x42" * 32
    s = k.sign(m)
    return [VerifyRequest(k.public, m, s) for _ in range(n)]


class TestModel:
    def test_routing_learns_crossover(self):
        m = _LatencyModel(min_device_batch=64)
        # measured: CPU 0.1 ms/sig; device flat 50ms per call
        m.observe_cpu(100, 10.0)
        for _ in range(2):  # first device sample per bucket is warmup
            m.observe_device(256, 50.0)
            m.observe_device(4096, 55.0)
        assert not m.use_device(32)  # below floor
        assert not m.use_device(200)  # 20ms CPU < ~50ms device
        assert m.use_device(1000)  # 100ms CPU > ~50ms device
        assert m.use_device(4096)  # 410ms CPU > 55ms device

    def test_one_stalled_host_batch_is_a_stall_not_a_price(self):
        """A small host batch caught behind a close reads many times
        the running price: it moves the price by a quarter, and a
        batch of 96 stays on the host (PERF.md section 7, PR 34)."""
        m = _LatencyModel(min_device_batch=64)
        for _ in range(2):
            m.observe_device(96, 125.0)
        m.observe_cpu(96, 7.7)  # 0.08 ms a signature
        m.observe_cpu(8, 80.0)  # the same batch size, stalled: 10 ms
        assert m.cpu_persig_ms == pytest.approx(7.7 / 96 * 1.25)
        assert m.decide(96)[:2] == ("cpu", "priced")
        # a host that really slowed down is believed a quarter more with
        # every batch
        for _ in range(24):
            m.observe_cpu(96, 96 * 2.0)
        assert m.cpu_persig_ms == pytest.approx(2.0, rel=0.05)
        assert m.decide(96)[:2] == ("device", "priced")

    def test_a_run_of_device_batches_lets_the_host_arm_report(self):
        """A closed loop whose every batch rides the chip forms no host
        batch: behind four in a row priced to a device arm that is not
        far ahead, one goes to the host, which reprices it."""
        m = _LatencyModel(min_device_batch=64)
        for _ in range(2):
            m.observe_device(96, 125.0)
        m.observe_cpu(96, 96 * 2.45)  # a first sample that was a stall
        sides = [m.decide(96)[:2] for _ in range(5)]
        assert sides == [("device", "priced")] * 4 + [("cpu", "explore")]
        # polling the same question does not advance the run
        assert all(m.decide(96, count=False)[0] == "device"
                   for _ in range(9))
        m.observe_cpu(96, 7.7)
        assert m.decide(96)[0] == "device"  # 1.86 ms a signature still
        # a device arm that is far ahead is never probed
        far = _LatencyModel(min_device_batch=64)
        for _ in range(2):
            far.observe_device(16384, 97.0)
        far.observe_cpu(16384, 16384 * 0.08)
        assert all(far.decide(16384)[:2] == ("device", "priced")
                   for _ in range(32))

    def test_unmeasured_device_explored_then_driven_by_data(self):
        m = _LatencyModel(min_device_batch=64)
        m.observe_cpu(100, 1.0)  # very fast CPU: 0.01 ms/sig
        assert m.use_device(128)  # no device data yet: explore
        m.observe_device(128, 5000.0)  # first sample = compile: discarded
        assert m.use_device(128)  # still exploring (warm, unmeasured)
        m.observe_device(128, 50.0)  # steady-state sample
        assert not m.use_device(128)  # 1.3ms CPU beats 50ms kernel

    def test_bucket_estimates_generalize(self):
        m = _LatencyModel(min_device_batch=64)
        for _ in range(2):  # past the warmup discard
            m.observe_device(4096, 50.0)
        # unmeasured bucket borrows the nearest measurement
        assert m.expected_device_ms(256) == 50.0
        assert m.expected_device_ms(16384) == 50.0


class TestPlaneRouting:
    def test_small_batches_stay_on_cpu(self):
        plane = VerifyPlane(backend="fake-device", min_device_batch=64,
                            window_ms=1.0)
        fake: FakeDeviceVerifier = plane.verifier  # type: ignore[assignment]
        try:
            # trickle: 10 batches of 4 — all must go CPU (below floor)
            for _ in range(10):
                assert plane.verify_many(reqs(4)).all()
            assert fake.calls == []
            assert plane.cpu_batches == 10
        finally:
            plane.stop()

    def test_large_batches_move_to_device_when_it_wins(self):
        plane = VerifyPlane(backend="fake-device", min_device_batch=64,
                            window_ms=1.0)
        fake: FakeDeviceVerifier = plane.verifier  # type: ignore[assignment]
        # teach the model a slow CPU (0.5 ms/sig) without sleeping
        plane.model.observe_cpu(100, 50.0)
        # pre-warm the buckets (the first device sample per bucket is
        # treated as compile time and discarded)
        for b in (256, 64, 512):
            plane.model.observe_device(b, 0.0)
        try:
            assert plane.verify_many(reqs(256)).all()
            assert fake.calls == [256]  # 128ms CPU estimate > explore
            # model now knows device ≈ 50ms; a 64-batch (32ms CPU) goes CPU
            assert plane.verify_many(reqs(64)).all()
            assert fake.calls == [256]
            # but a 512-batch (256ms CPU) goes device
            assert plane.verify_many(reqs(512)).all()
            assert fake.calls == [256, 512]
        finally:
            plane.stop()

    def test_device_losing_everywhere_goes_all_cpu(self):
        """The r2 regression shape: device slower at every size -> after
        the exploration batch, everything routes CPU."""
        plane = VerifyPlane(backend="fake-device", min_device_batch=64,
                            window_ms=1.0)
        fake: FakeDeviceVerifier = plane.verifier  # type: ignore[assignment]
        plane.model.observe_cpu(1000, 10.0)  # fast CPU: 0.01 ms/sig
        try:
            for _ in range(6):
                plane.verify_many(reqs(256))
            # exploration hits the device at most twice (the first sample
            # is discarded as compile warmup); never again after
            assert len(fake.calls) <= 2
            assert plane.cpu_batches >= 4
        finally:
            plane.stop()

    def test_histograms_and_model_exported(self):
        plane = VerifyPlane(backend="cpu")
        try:
            plane.verify_many(reqs(8))
            j = plane.get_json()
            assert sum(j["latency_histogram_ms"]["cpu"]) == 1
            assert j["model"]["cpu_persig_ms"] is not None
        finally:
            plane.stop()

    def test_async_submit_path_unchanged(self):
        plane = VerifyPlane(backend="cpu", window_ms=1.0)
        try:
            futs = [plane.submit(r) for r in reqs(32)]
            assert all(f.result(timeout=10) for f in futs)
        finally:
            plane.stop()


class TestPrewarm:
    def test_prewarm_gates_device_until_done_then_model_is_warm(self):
        import threading

        plane = VerifyPlane(backend="fake-device", min_device_batch=64,
                            window_ms=1.0)
        fake: FakeDeviceVerifier = plane.verifier  # type: ignore[assignment]
        # hold the fake device until the live batch has been routed, so
        # the "prewarm still pending" window is deterministic
        gate = threading.Event()
        orig = fake.verify_batch

        def gated(batch):
            gate.wait(10)
            return orig(batch)

        fake.verify_batch = gated  # type: ignore[method-assign]
        try:
            t = plane.start_prewarm(sizes=(256,), rounds=2)
            # while the prewarm runs, a device-sized batch routes CPU
            assert plane.verify_many(reqs(256)).all()
            gate.set()
            t.join(timeout=30)
            assert not t.is_alive()
            assert plane._prewarm_pending is False
            # the prewarm compiled (discarded) + measured the bucket
            assert plane.model.expected_device_ms(256) is not None
            # prewarm traffic never pollutes the public counters
            assert plane.device_sigs == 0
            assert plane.verified == 256  # the one live batch above
            # prewarm calls went to the fake device directly
            assert fake.calls and all(c == 256 for c in fake.calls)
        finally:
            plane.stop()

    def test_prewarm_on_cpu_backend_is_a_noop(self):
        plane = VerifyPlane(backend="cpu")
        try:
            t = plane.start_prewarm(sizes=(64,))
            t.join(timeout=10)
            assert plane._prewarm_pending is False
        finally:
            plane.stop()


class TestBoundedReexplore:
    def test_hopeless_batches_never_reexplored(self):
        m = _LatencyModel(min_device_batch=64)
        m.observe_cpu(100, 1.0)  # 0.01 ms/sig
        for _ in range(2):
            m.observe_device(256, 500.0)  # device hopeless at this size
        # 256 sigs = 2.56ms CPU vs 500ms device: outside the 4x band,
        # so even REEXPLORE_EVERY calls never send it back to the device
        for _ in range(m.REEXPLORE_EVERY * 2 + 5):
            assert not m.use_device(256)

    def test_close_losses_are_reexplored(self):
        m = _LatencyModel(min_device_batch=64)
        m.observe_cpu(100, 30.0)  # 0.3 ms/sig
        for _ in range(2):
            m.observe_device(256, 100.0)  # 77ms CPU vs 100ms device
        hits = sum(
            m.use_device(256) for _ in range(m.REEXPLORE_EVERY + 5)
        )
        assert hits == 1  # exactly one periodic re-exploration

    def test_window_poll_does_not_advance_reexplore(self):
        m = _LatencyModel(min_device_batch=64)
        m.observe_cpu(100, 30.0)
        for _ in range(2):
            m.observe_device(256, 100.0)
        for _ in range(m.REEXPLORE_EVERY * 3):
            assert not m.use_device(256, count=False)
        assert m._since_device == 0


class TestPadPolicy:
    def test_max_policy_pads_every_chunk_to_one_shape(self, monkeypatch):
        monkeypatch.setenv("STELLARD_PAD_POLICY", "max")
        from stellard_tpu.crypto.backend import TpuVerifier

        v = TpuVerifier(min_batch=256, max_batch=16384)
        assert v._pad_size(5, 256, 16384) == 16384
        assert v._pad_size(5000, 256, 16384) == 16384

    def test_pow2_policy_keeps_proportional_buckets(self, monkeypatch):
        monkeypatch.setenv("STELLARD_PAD_POLICY", "pow2")
        from stellard_tpu.crypto.backend import TpuVerifier

        v = TpuVerifier(min_batch=256, max_batch=16384)
        assert v._pad_size(5, 256, 16384) == 256
        assert v._pad_size(5000, 256, 16384) == 8192

    def test_bad_policy_rejected(self, monkeypatch):
        monkeypatch.setenv("STELLARD_PAD_POLICY", "bogus")
        from stellard_tpu.crypto.backend import TpuVerifier

        with pytest.raises(ValueError):
            TpuVerifier()
