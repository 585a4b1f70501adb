"""Device bring-up seams (ISSUE 21): the chip smoke's phases on the CPU,
the compile-cache placement rule, and a device arm that raises.

On the chip the program is proven by `python chip_smoke.py` (README,
"Running"); here its phase functions run at a tiny size with the required
platform passed as a function argument that only this test supplies.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_fast_without_a_chip():
    """`python chip_smoke.py` under JAX_PLATFORMS=cpu exits non-zero
    within seconds, names the platform, and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr and "JAX_PLATFORMS='cpu'" in r.stderr
    assert r.stdout.strip() == ""


def test_chip_smoke_phases_on_cpu(tmp_path, monkeypatch):
    """The three phases and every gate at --txs 256: device node (the
    `tpu` backends on the CPU platform), --replay as a second process,
    plain reference. One padded shape (pad-to-max at 256) bounds the
    XLA:CPU compile cost, as tools/meshsmoke.py does."""
    import chip_smoke

    monkeypatch.setenv("STELLARD_PAD_POLICY", "max")
    monkeypatch.setenv("STELLARD_VERIFY_IMPL", "xla")
    kw = dict(seed=3, txs=256, close_every=64)
    device = chip_smoke.run_node_phase(
        str(tmp_path), "tpu", require_platform="cpu",
        verify_max_batch=256, **kw,
    )
    assert device["platform"] == "cpu"
    assert len(device["ledgers"]) == 4
    assert device["refused"] == chip_smoke.PLANTED
    assert device["verify"]["device_sigs"] > 0

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    replay = chip_smoke.run_replay_phase(
        device["conf"], device["replay_ledger"], env, limit_s=600,
    )
    assert replay["rc"] == 0, replay
    assert replay["stats"]["device_sigs"] == replay["stats"]["tx_count"] == 64

    reference = chip_smoke.run_node_phase(str(tmp_path), "cpu", **kw)
    failures = chip_smoke.check(
        device, replay, reference, require_platform="cpu",
    )
    assert failures == []

    # stdout: a summary line, then the verdict with exactly ok + device
    import json

    lines = chip_smoke.result_lines(
        device, replay, reference, failures, impl="xla", mesh=0)
    assert json.loads(lines[0])["claim"] is None
    want_device = {"platform": "cpu", "kind": device["device_kind"],
                   "count": device["devices_visible"]}
    assert json.loads(lines[-1]) == {"ok": True, "device": want_device}
    assert isinstance(want_device["kind"], str)
    assert type(want_device["count"]) is int
    lines = chip_smoke.result_lines(
        device, replay, reference, ["a gate"], impl="xla", mesh=0)
    assert [json.loads(ln) for ln in lines] == [
        {"ok": False, "device": want_device}]

    # the gates bite: a plane that fell back, a forked ledger
    broken = dict(device, verify=dict(device["verify"],
                                      cpu_eligible_batches=1))
    assert any("CPU arm" in f for f in chip_smoke.check(
        broken, replay, reference, require_platform="cpu"))
    forked = dict(reference, ledgers=reference["ledgers"][:-1] + [
        (reference["ledgers"][-1][0], "00" * 32)])
    assert any("ledger hashes differ" in f for f in chip_smoke.check(
        device, replay, forked, require_platform="cpu"))
    # and the platform is a gate of its own
    assert any("platform" in f for f in chip_smoke.check(
        device, replay, reference))


def test_compilation_cache_placement(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no directory in
    code. Not set: the fixed in-checkout path."""
    import jax

    from stellard_tpu.utils.xlacache import (
        enable_compilation_cache,
        host_cpu_fingerprint,
    )

    before = jax.config.jax_compilation_cache_dir
    try:
        outside = str(tmp_path / "placed-from-outside")
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        assert enable_compilation_cache() == outside
        # left alone (a real process has JAX read the variable itself)
        assert jax.config.jax_compilation_cache_dir == "sentinel"

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache", host_cpu_fingerprint())
        assert enable_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compilation_cache_env_is_honoured_by_a_fresh_process(tmp_path):
    """End to end: with the variable set, a device entry point leaves its
    entries there and creates nothing under <checkout>/.jax_cache."""
    outside = tmp_path / "cache"
    code = (
        "import jax, jax.numpy as jnp\n"
        "from stellard_tpu.crypto.backend import ensure_jax\n"
        "from stellard_tpu.utils import xlacache\n"
        "xlacache.MIN_COMPILE_TIME_SECS = 0.0\n"
        "ensure_jax()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()\n"
        "print(xlacache.COMPILES.snapshot()['requests'])\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(outside), PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=env, cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr
    lines = r.stdout.split()
    assert lines[0] == str(outside)
    assert int(lines[1]) >= 1  # the compile meter saw the program
    assert any(outside.iterdir())  # entries landed where the env said


class _RaisingDevice:
    """A device arm the compiler refused: raises instead of answering."""

    name = "tpu"
    min_batch = 8
    max_batch = 64

    def __init__(self):
        self.calls = 0

    def verify_batch(self, batch):
        self.calls += 1
        raise RuntimeError("Mosaic failed to compile TPU kernel")


def test_device_arm_that_raises_is_not_a_verdict():
    """A verifier whose device arm raises yields correct verdicts from
    the CPU arm, sets the failed flag (sticky), and records no SF_BAD for
    the good signatures it never judged."""
    import threading

    from stellard_tpu.crypto.backend import VerifyRequest
    from stellard_tpu.node.config import Config
    from stellard_tpu.node.hashrouter import SF_BAD, SF_SIGGOOD
    from stellard_tpu.node.node import MASTER_PASSPHRASE, Node
    from stellard_tpu.node.verifyplane import VerifyPlane
    from stellard_tpu.protocol.formats import TxType
    from stellard_tpu.protocol.keys import KeyPair
    from stellard_tpu.protocol.sfields import sfAmount, sfDestination
    from stellard_tpu.protocol.stamount import STAmount
    from stellard_tpu.protocol.sttx import SerializedTransaction
    from stellard_tpu.protocol.ter import TER

    # the plane alone: verdicts come from the CPU arm, flag is sticky
    key = KeyPair.from_passphrase("bringup")
    msgs = [bytes([i]) * 32 for i in range(16)]
    reqs = [VerifyRequest(key.public, m, key.sign(m)) for m in msgs]
    reqs[5] = VerifyRequest(key.public, msgs[5], bytes(64))
    plane = VerifyPlane(backend="cpu", min_device_batch=8)
    dev = _RaisingDevice()
    try:
        plane.verifier = dev
        plane._device_capable = True
        plane._route_by_cost = False  # routing=device
        got = plane.verify_many(reqs)
        want = np.ones(16, bool)
        want[5] = False
        assert np.array_equal(got, want)
        j = plane.get_json()
        assert j["device_failed"] is True
        assert "Mosaic failed" in j["device_error"]
        assert j["device_wedged"] is False
        assert j["cpu_eligible_batches"] == 1 and j["device_sigs"] == 0
        plane.verify_many(reqs)  # sticky: the device is not asked again
        assert dev.calls == 1
    finally:
        plane.stop()

    # through the node's asynchronous intake: good signatures apply
    node = Node(Config(verify_min_device_batch=1)).setup()
    try:
        vp = node.verify_plane
        vp.verifier = _RaisingDevice()
        vp._device_capable = True
        vp._route_by_cost = False
        master = KeyPair.from_passphrase(MASTER_PASSPHRASE)
        dest = KeyPair.from_passphrase("bringup-dest").account_id
        txs = []
        for i in range(6):
            tx = SerializedTransaction.build(
                TxType.ttPAYMENT, master.account_id, 1 + i, 10,
                {sfAmount: STAmount.from_drops(250_000_000),
                 sfDestination: dest},
            )
            tx.sign(master)
            txs.append(tx)
        done = threading.Semaphore(0)
        seen = {}

        def cb(tx, ter, applied):
            seen[tx.txid()] = (ter, applied)
            done.release()

        for tx in txs:
            node.ops.submit_transaction(tx, cb)
        for _ in txs:
            assert done.acquire(timeout=60)
        assert all(applied and ter == TER.tesSUCCESS
                   for ter, applied in seen.values()), seen
        assert node.ops.stats["bad_sig"] == 0
        for tx in txs:
            flags = node.hash_router.get_flags(tx.txid())
            assert flags & SF_SIGGOOD and not flags & SF_BAD
        assert vp.get_json()["device_failed"] is True
    finally:
        node.stop()


def test_prewarm_failure_is_surfaced():
    """A prewarm the compiler refuses must not leave a node that merely
    looks cold: the error rides get_json."""
    from stellard_tpu.node.verifyplane import VerifyPlane

    plane = VerifyPlane(backend="cpu", min_device_batch=8, max_batch=64)
    try:
        plane.verifier = _RaisingDevice()
        plane._device_capable = True
        plane.start_prewarm(sizes=[64]).join(timeout=60)
        j = plane.get_json()
        assert "Mosaic failed" in j["prewarm_error"]
    finally:
        plane.stop()


def test_hasher_describe_names_the_platform():
    from stellard_tpu.crypto.backend import make_hasher

    h = make_hasher("tpu", mesh="0")
    assert h.describe()["platform"] == "unresolved"
    h.prefix_hash_batch([0x4D4C4E00] * 8, [bytes([i]) * 40 for i in range(8)])
    assert h.describe()["platform"] == "cpu"  # tests/conftest.py pins it
