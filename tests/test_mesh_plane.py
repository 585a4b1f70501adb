"""Sharded multi-chip crypto + hash plane (ISSUE 15).

Mesh width as a config axis: [signature_backend]/[hash_backend] mesh=
round-trips through config parsing with validation, backend options
reach the factories (and unknown keys fail loudly — the dead-config
seam), width 1 and width N execute the same routed plane, and the
three-way host/1-chip/N-chip cost routing picks arms by measured cost.
Byte identity is pinned sharded-vs-single-device-vs-host on ragged
batches, bad signatures in every shard position, and masked-SHA packed
buffers — all on the virtual 8-device CPU mesh, no TPU required.
"""

from __future__ import annotations

import ast
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from stellard_tpu.crypto.backend import (
    BatchHasher,
    BatchVerifier,
    CpuHasher,
    TpuVerifier,
    VerifyRequest,
    WatchdogHasher,
    _HashCostModel,
    make_hasher,
    make_verifier,
    make_watched_hasher,
    mesh_wants_width,
    parse_mesh,
    register_verifier,
    resolve_mesh_width,
)
from stellard_tpu.node.config import Config
from stellard_tpu.node.verifyplane import VerifyPlane, _LatencyModel
from stellard_tpu.ops import ed25519_ref as ref
from stellard_tpu.protocol.keys import KeyPair

EIGHT_DEVICES = len(jax.devices()) >= 8
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_CONFIGS = os.path.join(REPO, "benchmarks", "configs")


def make_reqs(n: int, corrupt: set = frozenset(), seed: int = 9):
    rng = np.random.default_rng(seed)
    keys = [KeyPair.from_seed(rng.bytes(32)) for _ in range(8)]
    reqs, want = [], []
    for i in range(n):
        k = keys[i % 8]
        m = rng.bytes(32)
        s = bytearray(k.sign(m))
        if i in corrupt:
            s[int(rng.integers(0, 64))] ^= 1 << int(rng.integers(0, 8))
        reqs.append(VerifyRequest(k.public, m, bytes(s)))
        want.append(ref.verify(k.public, m, bytes(s)))
    return reqs, np.array(want)


class TestMeshAxisParsing:
    def test_parse_mesh_canonical_forms(self):
        assert parse_mesh(None) == "0"
        assert parse_mesh("") == "0"
        assert parse_mesh("off") == "0"
        assert parse_mesh(0) == "0"
        assert parse_mesh("4") == "4"
        assert parse_mesh(" AUTO ") == "auto"

    def test_parse_mesh_rejects_garbage(self):
        with pytest.raises(ValueError):
            parse_mesh("many")
        with pytest.raises(ValueError):
            parse_mesh("-2")

    def test_resolve_width_clamps_and_floors(self):
        assert resolve_mesh_width("0", 8) == 1
        assert resolve_mesh_width("auto", 8) == 8
        assert resolve_mesh_width("4", 8) == 4
        assert resolve_mesh_width("16", 8) == 8  # clamped, loudly
        assert resolve_mesh_width("auto", 1) == 1
        assert resolve_mesh_width("6", 8, pow2=True) == 4
        assert resolve_mesh_width("auto", 6, pow2=True) == 4

    def test_mesh_wants_width(self):
        assert mesh_wants_width("auto")
        assert mesh_wants_width("2")
        assert not mesh_wants_width("0")
        assert not mesh_wants_width("1")
        assert not mesh_wants_width(None)


class TestConfigRoundTrip:
    def test_mesh_round_trips_both_sections(self):
        cfg = Config.from_ini(
            "[signature_backend]\ntype=tpu\nmesh=4\nrouting=device\n"
            "[hash_backend]\ntype=tpu\nmesh=auto\nmin_device_nodes=32\n"
        )
        assert cfg.verify_mesh == "4"
        assert cfg.verify_routing == "device"
        assert cfg.hash_mesh == "auto"
        assert cfg.hash_min_device_nodes == 32

    def test_mesh_zero_and_defaults(self):
        cfg = Config.from_ini("[signature_backend]\ntype=tpu\nmesh=0\n")
        assert cfg.verify_mesh == "0"
        # defaults: auto (today's all-visible-devices behavior)
        cfg = Config.from_ini("[signature_backend]\ntype=tpu\n")
        assert cfg.verify_mesh == "auto"
        assert cfg.hash_mesh == "auto"
        assert cfg.verify_routing == "cost" and cfg.hash_routing == "cost"

    def test_mesh_on_host_backend_is_loud(self):
        with pytest.raises(ValueError, match="meaningless"):
            Config.from_ini("[signature_backend]\ntype=cpu\nmesh=4\n")
        with pytest.raises(ValueError, match="meaningless"):
            Config.from_ini("[hash_backend]\ntype=cpu\nmesh=auto\n")
        # mesh=0 with a host backend is fine (explicitly off)
        cfg = Config.from_ini("[signature_backend]\ntype=cpu\nmesh=0\n")
        assert cfg.verify_mesh == "0"

    def test_bad_mesh_and_routing_rejected(self):
        with pytest.raises(ValueError):
            Config.from_ini("[signature_backend]\ntype=tpu\nmesh=lots\n")
        with pytest.raises(ValueError, match="routing"):
            Config.from_ini("[hash_backend]\ntype=tpu\nrouting=maybe\n")

    def test_unknown_keys_fail_loudly(self):
        # the dead-config seam: use_mesh= parsed clean and did nothing
        with pytest.raises(ValueError, match="use_mesh"):
            Config.from_ini("[signature_backend]\ntype=tpu\nuse_mesh=1\n")
        with pytest.raises(ValueError, match="unknown key"):
            Config.from_ini("[hash_backend]\ntype=cpu\nfloor=64\n")

    @pytest.mark.parametrize("case", [
        "none", "off", "empty", "path",
        "standalone-fsync.ini", "catchup-replay.ini", "state-1m.ini",
    ])
    def test_retired_kernel_tuning_section(self, case, tmp_path):
        """[kernel_tuning] is retired. The benchmark's INIs still carry
        it with ``none``, so that (``off``, or nothing) parses and does
        nothing; a path is refused by name — an operator who points at
        a sweep file must not believe it applied."""
        base = "[signature_backend]\ntype=tpu\nmesh=0\n"
        if case.endswith(".ini"):
            with open(os.path.join(BENCH_CONFIGS, case)) as f:
                text = f.read()
            assert "[kernel_tuning]\nnone\n" in text
            text = text.replace("{workdir}", str(tmp_path)).replace(
                "{start_up}", "fresh")
            base = text.replace("[kernel_tuning]\nnone\n", "")
        elif case == "path":
            with pytest.raises(ValueError, match=r"\[kernel_tuning\].*"
                               "no longer selectable from a file"):
                Config.from_ini(base + "[kernel_tuning]\nKERNEL_TUNING.json\n")
            return
        else:
            value = "" if case == "empty" else case
            text = base + f"[kernel_tuning]\n{value}\n"
        env = dict(os.environ)
        assert Config.from_ini(text) == Config.from_ini(base)
        assert dict(os.environ) == env

    def test_backend_mismatched_keys_fail_loudly(self):
        """Keys only one backend type honors must not parse clean and
        be silently dropped downstream (the dead-config class again)."""
        with pytest.raises(ValueError, match="only apply to type=tpu"):
            Config.from_ini("[hash_backend]\ntype=cpu\nrouting=device\n")
        with pytest.raises(ValueError, match="only apply to type=tpu"):
            Config.from_ini("[hash_backend]\ntype=cpu\nmin_device_nodes=5\n")
        with pytest.raises(ValueError, match="only apply to type=tpu"):
            Config.from_ini(
                "[signature_backend]\ntype=cpu\ndevice_first_timeout_s=2\n"
            )
        with pytest.raises(ValueError, match="only apply to host"):
            Config.from_ini("[signature_backend]\ntype=tpu\nthreads=16\n")

    def test_timeouts_threads_and_floors_plumbed(self):
        cfg = Config.from_ini(
            "[signature_backend]\ntype=tpu\ndevice_first_timeout_s=123\n"
            "device_warm_timeout_s=4.5\n"
            "[hash_backend]\ntype=tpu\ndevice_first_timeout_s=99\n"
        )
        assert cfg.verify_device_first_timeout_s == 123.0
        assert cfg.verify_device_warm_timeout_s == 4.5
        assert cfg.hash_device_first_timeout_s == 99.0
        cfg = Config.from_ini("[signature_backend]\ntype=cpu\nthreads=7\n")
        assert cfg.verify_threads == 7
        assert cfg.verify_backend_opts() == {"threads": 7}

    def test_verify_backend_opts_for_tpu(self):
        cfg = Config.from_ini(
            "[signature_backend]\ntype=tpu\nmesh=2\nmax_batch=512\n"
        )
        assert cfg.verify_backend_opts() == {"mesh": "2", "max_batch": 512}


class TestFactoryOptionValidation:
    def test_unknown_verifier_option_fails_loudly(self):
        with pytest.raises(ValueError, match="bogus"):
            make_verifier("cpu", bogus=1)
        with pytest.raises(ValueError, match="threads"):
            make_verifier("tpu", threads=4)

    def test_unknown_hasher_option_fails_loudly(self):
        with pytest.raises(ValueError, match="mesh"):
            make_hasher("cpu", mesh="4")

    def test_accepted_options_pass(self):
        v = make_verifier("tpu", mesh="2", min_batch=8, max_batch=64)
        assert isinstance(v, TpuVerifier)
        assert v.mesh == "2"
        h = make_hasher("tpu", mesh="0")
        assert h.mesh == "0"

    def test_bad_mesh_fails_at_build_not_first_batch(self):
        with pytest.raises(ValueError):
            make_verifier("tpu", mesh="wide")
        with pytest.raises(ValueError):
            make_hasher("tpu", mesh="-1")


@pytest.mark.skipif(not EIGHT_DEVICES, reason="needs the 8-device mesh")
class TestVerifierWidthIdentity:
    """Width is config, not code path: every width of the same sharded
    program returns byte-identical verdicts on ragged batches with bad
    signatures planted in every shard position of the widest mesh."""

    def test_every_width_matches_reference(self):
        # 61 sigs pad to 64: shard size 8 at width 8 — one corrupt
        # signature lands in every shard (position 58 covers the shard
        # that also holds the padding rows)
        corrupt = {0, 9, 17, 26, 33, 42, 49, 58}
        reqs, want = make_reqs(61, corrupt)
        for width in (1, 2, 4, 8):
            v = TpuVerifier(min_batch=8, max_batch=64, mesh=str(width))
            got = v.verify_batch(reqs)
            assert np.array_equal(got, want), f"width {width} diverged"
            assert v.n_devices == width
            assert v.kernel_selected == f"xla-sharded@{width}"
            assert not got[list(corrupt)].any()

    # NOTE: the three tests below deliberately use 40+-sig batches so
    # they pad to the SAME 64-row shapes the widths test compiles —
    # every fresh (pad-shape, width) combo is a multi-second XLA:CPU
    # compile on a cold cache, and identity is already pinned per shape

    def test_width_request_clamps_to_visible(self):
        v = TpuVerifier(min_batch=64, max_batch=64, mesh="16")
        reqs, want = make_reqs(40, {3})
        assert np.array_equal(v.verify_batch(reqs), want)
        assert v.n_devices == len(jax.devices())

    def test_mesh_zero_is_width_one_same_path(self):
        v = TpuVerifier(min_batch=64, max_batch=64, mesh="0")
        reqs, want = make_reqs(40, {0, 9})
        assert np.array_equal(v.verify_batch(reqs), want)
        assert v.n_devices == 1
        assert v.kernel_selected == "xla-sharded@1"

    def test_describe_reports_provenance(self):
        v = TpuVerifier(min_batch=64, max_batch=64, mesh="2")
        v.verify_batch(make_reqs(40)[0])
        d = v.describe()
        assert d["mesh_requested"] == "2"
        assert d["mesh_width"] == 2
        assert d["devices_visible"] == len(jax.devices())
        assert d["kernel"] == "xla-sharded@2"


class TestMeshFloorBypass:
    """The pallas small-batch bypass boundary, pinned with fake kernels
    (no interpreter wall-clock): padded sizes below _mesh_floor route to
    the single-chip kernel, at/above it to the sharded kernel."""

    def _fake(self, calls, tag):
        def kern(a_words, *rest):
            calls.append((tag, int(a_words.shape[0])))
            return np.ones(int(a_words.shape[0]), bool)

        return kern

    def test_boundary(self):
        v = TpuVerifier(min_batch=8, max_batch=64, mesh="8")
        calls = []
        v._kernel = self._fake(calls, "wide")
        v._small_kernel = self._fake(calls, "small")
        v._mesh_floor = 32
        v.n_devices = 8
        reqs, _ = make_reqs(9)  # pads to 16 < 32: bypass
        v.verify_batch(reqs)
        assert calls[-1][0] == "small"
        reqs, _ = make_reqs(30)  # pads to 32 == floor: sharded
        v.verify_batch(reqs)
        assert calls[-1][0] == "wide"


@pytest.mark.skipif(not EIGHT_DEVICES, reason="needs the 8-device mesh")
class TestHashPlaneIdentity:
    def test_packed_flat_identity_every_width(self):
        """hash_packed (the pack_nodes/seal-flush contract: blob ==
        hashed bytes) through the watched three-way plane, forced
        device, ragged 37-message buffer — byte parity with hashlib
        (CpuHasher) at every width."""
        rng = np.random.default_rng(13)
        msgs = [
            b"MIN\0" + rng.bytes(int(rng.integers(1, 500)))
            for _ in range(37)
        ]
        buf = b"".join(msgs)
        offsets = [0]
        for m in msgs:
            offsets.append(offsets[-1] + len(m))
        want = CpuHasher().hash_packed(buf, offsets)
        for width in ("0", "2", "8", "auto"):
            h = make_watched_hasher(
                "tpu", mesh=width, routing="device", min_device_nodes=0
            )
            assert h.hash_packed(buf, offsets) == want, f"width {width}"
            assert h.device_nodes == 37

    def test_tree_hash_parity_vs_host(self):
        """Whole-SHAMap hashing (the seal/drainer shape) through the
        meshed watched hasher == the host-hashed root, bytes."""
        from stellard_tpu.state.shamap import SHAMap, SHAMapItem, TNType

        rng = np.random.default_rng(17)

        def build(hash_batch=None):
            m = (SHAMap(TNType.ACCOUNT_STATE, hash_batch=hash_batch)
                 if hash_batch is not None
                 else SHAMap(TNType.ACCOUNT_STATE))
            r = np.random.default_rng(17)
            for _ in range(60):
                m.set_item(SHAMapItem(r.bytes(32), r.bytes(90)))
            return m

        host_root = build().get_hash()
        meshed = make_watched_hasher(
            "tpu", mesh="8", routing="device", min_device_nodes=0
        )
        dev_map = build(hash_batch=meshed)
        assert dev_map.get_hash() == host_root
        assert meshed.device_nodes > 0


class TestThreeArmCostModel:
    def test_explores_then_routes_cheapest(self):
        m = _HashCostModel(reexplore_every=8, arms=("dev1", "devN"))
        # declared order explored first while unmeasured
        assert m.choose(100) == "dev1"
        m.observe("dev1", 100, 100.0)  # compile sample: discarded
        assert m.choose(100) == "dev1"  # still unmeasured
        m.observe("dev1", 100, 4.0)
        assert m.choose(100) == "devN"  # next unmeasured arm
        m.observe("devN", 100, 100.0)
        m.observe("devN", 100, 12.0)
        assert m.choose(100) == "host"  # host measured once
        m.observe("host", 100, 100.0)  # 1 ms/node
        # 100 nodes: host 100ms, dev1 4ms, devN 12ms -> dev1
        assert m.choose(100) == "dev1"
        # teach the big bucket the opposite ordering: wide wins
        for _ in range(2):
            m.observe("dev1", 5000, 80.0)
            m.observe("devN", 5000, 20.0)
        assert m.choose(5000) == "devN"

    def test_small_batches_stay_on_host(self):
        m = _HashCostModel(
            reexplore_every=8, min_device_nodes=64, arms=("dev1", "devN")
        )
        assert m.choose(63) == "host"
        assert m.choose(64) == "dev1"

    def test_losing_arm_reexplored_bounded(self):
        m = _HashCostModel(reexplore_every=5, arms=("dev1", "devN"))
        for arm, ms in (("dev1", 10.0), ("devN", 30.0)):
            m.observe(arm, 100, 999.0)
            m.observe(arm, 100, ms)
        m.observe("host", 100, 10000.0)  # 100 ms/node: devices win
        # devN loses to dev1 but sits within 4x: re-explored every 5
        picks = [m.choose(100) for _ in range(11)]
        assert picks.count("devN") == 2
        assert all(p in ("dev1", "devN") for p in picks)

    def test_hopeless_arm_never_reexplored(self):
        m = _HashCostModel(reexplore_every=3, arms=("dev1", "devN"))
        for arm, ms in (("dev1", 1.0), ("devN", 50.0)):
            m.observe(arm, 100, 999.0)
            m.observe(arm, 100, ms)
        m.observe("host", 100, 200.0)  # 2 ms/node -> 200ms; dev1 wins
        # devN at 50ms is within 4x of dev1's 1ms? no: 50 > 4*1 — hopeless
        assert all(m.choose(100) == "dev1" for _ in range(20))

    def test_get_json_snapshots_all_arms(self):
        m = _HashCostModel(reexplore_every=8, arms=("dev1", "devN"))
        m.observe("dev1", 10, 5.0)
        m.observe("devN", 10, 7.0)
        j = m.get_json()
        assert set(j["arms"]) == {"dev1", "devN"}
        # legacy view tracks the PRIMARY (widest) arm — the one still
        # accumulating after a 1-chip arm collapse
        assert j["buckets"] == j["arms"]["devN"]

    def test_legacy_single_arm_shims(self):
        m = _HashCostModel(reexplore_every=8)
        m.observe_device(100, 999.0)
        m.observe_device(100, 5.0)
        m.observe_host(100, 1000.0)
        assert m.use_device(100)


class TestLatencyModelArms:
    def test_route_picks_cheapest_arm(self):
        m = _LatencyModel(min_device_batch=8, device_arms=("dev1", "devN"))
        m.observe_cpu(100, 50.0)  # 0.5 ms/sig
        for arm, small, big in (("dev1", 2.0, 60.0), ("devN", 10.0, 12.0)):
            for _ in range(2):
                m.observe_device(16, small, arm=arm)
                m.observe_device(1024, big, arm=arm)
        assert m.route(16) == "dev1"   # 8ms cpu > 2ms dev1 < 10ms devN
        assert m.route(1024) == "devN"  # 512 cpu > 12 devN < 60 dev1
        assert m.route(4) == "cpu"      # below floor

    def test_legacy_use_device_still_works(self):
        m = _LatencyModel(min_device_batch=64)
        m.observe_cpu(100, 10.0)
        for _ in range(2):
            m.observe_device(256, 50.0)
        assert not m.use_device(200)
        assert m.use_device(1000)


class FakeMeshVerifier(BatchVerifier):
    """Fake device backend whose factory accepts mesh= (dual-arm plane
    tests): records calls per instance."""

    name = "fake-mesh"

    def __init__(self, mesh="auto", **_):
        self.mesh = mesh
        self.n_devices = 1 if mesh == "0" else 4
        self.calls: list[int] = []

    def verify_batch(self, batch):
        self.calls.append(len(batch))
        return np.ones(len(batch), bool)


register_verifier("fake-mesh", FakeMeshVerifier)


def garbage_reqs(n):
    return [VerifyRequest(b"\x01" * 32, b"\x02" * 32, b"\x03" * 64)] * n


class TestPlaneDualArms:
    def test_plane_builds_and_routes_both_arms(self):
        plane = VerifyPlane(
            backend="fake-mesh", backend_opts={"mesh": "4"},
            min_device_batch=8, window_ms=1.0,
        )
        try:
            wide: FakeMeshVerifier = plane.verifier
            one: FakeMeshVerifier = plane._one_chip
            assert one is not None and one.mesh == "0"
            assert plane.model.device_arms == ("dev1", "devN")
            m = plane.model
            m.observe_cpu(100, 50.0)  # 0.5 ms/sig
            for arm, small, big in (
                ("dev1", 2.0, 60.0), ("devN", 10.0, 12.0),
            ):
                for _ in range(2):
                    m.observe_device(16, small, arm=arm)
                    m.observe_device(1024, big, arm=arm)
            plane.verify_many(garbage_reqs(16))
            assert one.calls == [16] and wide.calls == []
            plane.verify_many(garbage_reqs(1024))
            assert wide.calls == [1024]
            j = plane.get_json()
            assert j["arms"]["dev1"]["sigs"] == 16
            assert j["arms"]["devN"]["sigs"] == 1024
            assert j["backend"] == "fake-mesh"
        finally:
            plane.stop()

    def test_arms_collapse_when_wide_resolves_single(self):
        plane = VerifyPlane(
            backend="fake-mesh", backend_opts={"mesh": "4"},
            min_device_batch=8, window_ms=1.0,
        )
        try:
            plane.verifier.n_devices = 1  # "mesh wider than the box"
            assert plane._device_arms() == ("devN",)
            assert plane._one_chip is None
        finally:
            plane.stop()

    def test_forced_device_routing(self):
        plane = VerifyPlane(
            backend="fake-mesh", backend_opts={"mesh": "4"},
            min_device_batch=8, window_ms=1.0, routing="device",
        )
        try:
            wide: FakeMeshVerifier = plane.verifier
            # no model training at all: device mode forces the widest
            plane.verify_many(garbage_reqs(32))
            assert wide.calls == [32]
            # below the floor still goes cpu even when forced
            plane.verify_many(garbage_reqs(4))
            assert wide.calls == [32]
            assert plane.get_json()["routing"] == "device"
        finally:
            plane.stop()

    def test_bad_routing_rejected(self):
        with pytest.raises(ValueError, match="routing"):
            VerifyPlane(backend="cpu", routing="sometimes")

    def test_no_mesh_opts_keeps_single_arm(self):
        plane = VerifyPlane(backend="fake-mesh", window_ms=1.0)
        try:
            assert plane._one_chip is None
            assert plane.model.device_arms == ("device",)
        finally:
            plane.stop()


class TestSyncSubmitRidesThePlane:
    def test_process_transaction_counts_through_verify_plane(self):
        """The RPC submit path (NetworkOPs.process_transaction) verifies
        THROUGH the routed plane: before ISSUE 15 it called
        tx.check_sign() inline, so a mesh-enabled node could serve a
        whole RPC flood with device_sigs frozen at 0 and no routing
        evidence."""
        from stellard_tpu.node.config import Config
        from stellard_tpu.node.node import Node
        from stellard_tpu.protocol.formats import TxType
        from stellard_tpu.protocol.sfields import sfAmount, sfDestination
        from stellard_tpu.protocol.stamount import STAmount
        from stellard_tpu.protocol.sttx import SerializedTransaction

        n = Node(Config(signature_backend="cpu")).setup()
        try:
            master = KeyPair.from_passphrase("masterpassphrase")
            dest = KeyPair.from_passphrase("plane-sync").account_id
            tx = SerializedTransaction.build(
                TxType.ttPAYMENT, master.account_id, 1, 10,
                {sfAmount: STAmount.from_drops(250_000_000),
                 sfDestination: dest},
            )
            tx.sign(master)
            before = n.verify_plane.verified
            ter, applied = n.ops.process_transaction(tx)
            assert applied
            assert n.verify_plane.verified == before + 1
            assert n.verify_plane.cpu_sigs >= 1
            # tampered signature: rejected THROUGH the plane, not inline
            tx2 = SerializedTransaction.build(
                TxType.ttPAYMENT, master.account_id, 2, 10,
                {sfAmount: STAmount.from_drops(250_000_000),
                 sfDestination: dest},
            )
            tx2.sign(master)
            blob = bytearray(tx2.serialize())
            blob[-5] ^= 0x40
            bad = SerializedTransaction.from_bytes(bytes(blob))
            from stellard_tpu.protocol.ter import TER

            ter2, applied2 = n.ops.process_transaction(bad)
            assert ter2 == TER.temINVALID and not applied2
            assert n.verify_plane.verified == before + 2
        finally:
            n.stop()


class FakeDevHasher(BatchHasher):
    name = "tpu"

    def __init__(self, n_devices=8):
        self.n_devices = n_devices
        self.calls = 0
        self.device_nodes = 0
        self.host_nodes = 0

    def prefix_hash_batch(self, prefixes, payloads):
        self.calls += 1
        self.device_nodes += len(prefixes)
        from stellard_tpu.utils.hashes import prefix_hash

        return [prefix_hash(p, d) for p, d in zip(prefixes, payloads)]


class TestWatchdogThreeWay:
    def _mk(self, routing="cost"):
        wide, one, host = FakeDevHasher(8), FakeDevHasher(1), CpuHasher()
        w = WatchdogHasher(wide, host, inner_one=one,
                           min_device_nodes=0, routing=routing)
        return w, wide, one, host

    def test_cost_routes_three_ways(self):
        w, wide, one, _ = self._mk()
        batch = ([0x1234] * 16, [b"x" * 40] * 16)
        m = w._flat
        for arm, small, big in (("dev1", 1.0, 50.0), ("devN", 9.0, 5.0)):
            m.observe(arm, 16, 999.0)
            m.observe(arm, 16, small)
            m.observe(arm, 2048, 999.0)
            m.observe(arm, 2048, big)
        m.observe("host", 16, 160.0)  # 10 ms/node: devices win
        w.prefix_hash_batch(*batch)
        assert one.calls == 1 and wide.calls == 0
        big_batch = ([0x1234] * 2048, [b"x" * 40] * 2048)
        w.prefix_hash_batch(*big_batch)
        assert wide.calls == 1
        j = w.get_json()
        assert j["arms"] == ["dev1", "devN"]
        assert set(j["flat_model"]["arms"]) == {"dev1", "devN"}

    def test_forced_device_uses_widest_arm(self):
        w, wide, one, _ = self._mk(routing="device")
        w.prefix_hash_batch([0x1234] * 4, [b"x" * 40] * 4)
        assert wide.calls == 1 and one.calls == 0
        assert w.get_json()["routing"] == "device"

    def test_arms_collapse_when_wide_is_single(self):
        w, wide, one, _ = self._mk()
        wide.n_devices = 1
        assert w._live_arms() == ("devN",)
        assert w.inner_one is None

    def test_counters_sum_both_arms(self):
        w, wide, one, _ = self._mk(routing="device")
        w.prefix_hash_batch([0x1234] * 4, [b"x" * 40] * 4)
        one.device_nodes += 3  # as if the 1-chip arm also ran
        assert w.device_nodes == 7
        w.device_nodes = 0
        assert w.device_nodes == 0

    def test_make_watched_hasher_arm_construction(self):
        w = make_watched_hasher("tpu", mesh="8")
        assert isinstance(w, WatchdogHasher)
        assert w.inner_one is not None  # wide request: 1-chip arm built
        w0 = make_watched_hasher("tpu", mesh="0")
        assert w0.inner_one is None
        host = make_watched_hasher("cpu")
        assert isinstance(host, CpuHasher)  # host passes through


# what the program showed of itself before the variables went; printed
# by a child so that the kernel modules are imported under them
_SELF_PORTRAIT = """
import hashlib, json
import jax, jax.numpy as jnp
from stellard_tpu.crypto.backend import CpuHasher, TpuVerifier, WatchdogHasher
from stellard_tpu.node.verifyplane import VerifyPlane
from stellard_tpu.ops.ed25519_jax import pt_add_cached, pt_double

p = jax.ShapeDtypeStruct((4, 20, 8), jnp.int32)
program = str(jax.make_jaxpr(lambda a, b: pt_add_cached(pt_double(a), b))(p, p))
plane = VerifyPlane(backend="cpu")
try:
    print(json.dumps({
        "describe": TpuVerifier().describe(),
        "verify_routing": plane.routing,
        "hash_routing": WatchdogHasher(CpuHasher(), CpuHasher()).routing,
        "program": hashlib.sha256(program.encode()).hexdigest(),
    }))
finally:
    plane.stop()
"""

REMOVED_VARIABLES = {
    "STELLARD_HOIST_SELECT": "1",
    "STELLARD_GROUP_OPS": "1",
    "STELLARD_VERIFY_UNROLL": "4",
    "STELLARD_FE_MUL": "legacy",
    "STELLARD_VERIFY_ROUTING": "device",
    "STELLARD_HASH_ROUTING": "device",
    "STELLARD_HASH_MIN_DEVICE_NODES": "7",
    "STELLARD_PATHQ_ROUTING": "host",
    "STELLARD_PATHQ_MIN_DEVICE_BATCH": "7",
    "STELLARD_SWEEP_ALLOW_CPU": "1",
    "STELLARD_PROFILE_TRACE": "1",
}


class TestRetiredEnvironment:
    def _portrait(self, extra: dict) -> dict:
        env = {k: v for k, v in os.environ.items()
               if k not in REMOVED_VARIABLES}
        env.update(extra, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        out = subprocess.run(
            [sys.executable, "-c", _SELF_PORTRAIT], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    def test_removed_variables_are_not_read(self):
        """Each of the 11 removed variables at a value that used to
        switch something: the verifier, both routers and the point
        arithmetic the kernel traces are what they are without them."""
        plain = self._portrait({})
        assert plain["verify_routing"] == plain["hash_routing"] == "cost"
        assert self._portrait(REMOVED_VARIABLES) == plain

    def test_no_module_writes_the_environment(self):
        """No module of the program writes a STELLARD_* variable (the
        retired tuning loader did, with keys from a dict, so any write
        whose key is not a literal of another prefix counts)."""
        writers = ("setdefault", "update", "pop", "popitem", "clear",
                   "putenv", "unsetenv")
        found = []
        for root, _dirs, files in os.walk(os.path.join(REPO, "stellard_tpu")):
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(root, name)
                with open(path) as f:
                    tree = ast.parse(f.read(), path)
                for node in ast.walk(tree):
                    keys = []
                    if isinstance(node, (ast.Assign, ast.AugAssign,
                                         ast.Delete)):
                        targets = (node.targets if hasattr(node, "targets")
                                   else [node.target])
                        keys = [t.slice for t in targets
                                if isinstance(t, ast.Subscript)
                                and "environ" in ast.unparse(t.value)]
                    elif (isinstance(node, ast.Call)
                          and isinstance(node.func, ast.Attribute)
                          and node.func.attr in writers
                          and ("environ" in ast.unparse(node.func.value)
                               or node.func.attr in ("putenv", "unsetenv"))):
                        keys = node.args[:1] or [None]
                    for key in keys:
                        literal = (isinstance(key, ast.Constant)
                                   and isinstance(key.value, str))
                        if not literal or key.value.startswith("STELLARD_"):
                            found.append(
                                f"{os.path.relpath(path, REPO)}:{node.lineno}")
        assert found == []
