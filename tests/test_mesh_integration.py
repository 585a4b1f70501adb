"""Multi-chip mesh integrated into the production verify plane
(VERDICT r2 #3): TpuVerifier shards over every visible device, exercised
here on the 8-device virtual CPU mesh the conftest pins.

Covers: uneven (padded) batches, invalid signatures landing in specific
shards, the psum count path, and the VerifyPlane wiring end-to-end.
"""

from __future__ import annotations


import numpy as np
import pytest

import jax

from stellard_tpu.crypto.backend import TpuVerifier, VerifyRequest
from stellard_tpu.ops import ed25519_ref as ref
from stellard_tpu.ops.ed25519_jax import prepare_batch
from stellard_tpu.parallel.mesh import make_mesh, verify_and_count
from stellard_tpu.protocol.keys import KeyPair

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device virtual CPU mesh"
)


def make_reqs(n: int, corrupt: set[int] = frozenset()):
    rng = np.random.default_rng(3)
    keys = [KeyPair.from_seed(rng.bytes(32)) for _ in range(8)]
    reqs, want = [], []
    for i in range(n):
        k = keys[i % 8]
        m = rng.bytes(32)
        s = bytearray(k.sign(m))
        if i in corrupt:
            s[rng.integers(0, 64)] ^= 1 << int(rng.integers(0, 8))
        reqs.append(VerifyRequest(k.public, m, bytes(s)))
        want.append(ref.verify(k.public, m, bytes(s)))
    return reqs, np.array(want)


class TestMeshVerifier:
    def test_verifier_auto_meshes_over_all_devices(self):
        v = TpuVerifier(min_batch=64)
        v._resolve_kernel()
        assert v.n_devices == len(jax.devices())

    def test_uneven_batch_with_bad_sigs_in_specific_shards(self):
        # 300 requests pad to 512 over 8 shards of 64; corrupt indexes
        # chosen to land in shards 0, 3 and 7
        corrupt = {1, 2, 200, 290, 299}
        reqs, want = make_reqs(300, corrupt)
        v = TpuVerifier(min_batch=64)
        got = v.verify_batch(reqs)
        assert np.array_equal(got, want)
        assert not got[list(corrupt)].any()

    def test_pallas_impl_shards_over_the_mesh(self, monkeypatch):
        """STELLARD_VERIFY_IMPL=pallas in mesh mode: each device runs
        the whole-verify-in-VMEM kernel on its batch shard (explicit
        shard_map — a pallas_call is a custom call XLA cannot
        auto-partition). Interpreter mode on the CPU mesh."""
        monkeypatch.setenv("STELLARD_VERIFY_IMPL", "pallas")
        # an 8-shard interpreter run at the block of 512 is minutes of
        # dead time. (If ed25519_pallas is already imported this is a
        # no-op — the test sizes its batch from the ACTUAL P.BLOCK.)
        monkeypatch.setenv("STELLARD_PALLAS_BLOCK", "128")
        from stellard_tpu.ops import ed25519_pallas as P

        # at least the mesh floor, or the small-batch bypass routes
        # the chunk to the single-chip kernel (by design)
        n = len(jax.devices()) * P.BLOCK
        corrupt = {0, n // 2, n - 1}
        reqs, want = make_reqs(n, corrupt)
        v = TpuVerifier(min_batch=64, max_batch=n)
        got = v.verify_batch(reqs)
        assert v.n_devices == len(jax.devices())
        assert np.array_equal(got, want)
        assert not got[list(corrupt)].any()

        # below the floor: the bypass must still verify correctly
        # (single-chip kernel on shard-sized padding)
        small_reqs, small_want = make_reqs(40, {3})
        got2 = v.verify_batch(small_reqs)
        assert np.array_equal(got2, small_want)

    def test_multi_chunk_pipeline(self):
        reqs, want = make_reqs(96, corrupt={5, 50})
        v = TpuVerifier(min_batch=8, max_batch=32)  # forces 3 chunks
        got = v.verify_batch(reqs)
        assert np.array_equal(got, want)

    def test_psum_count_with_shard_local_failures(self):
        n = 128
        corrupt = {0, 1, 64, 127}
        reqs, want = make_reqs(n, corrupt)
        inp = prepare_batch(
            [r.public for r in reqs],
            [r.signing_hash for r in reqs],
            [r.signature for r in reqs],
        )
        mesh = make_mesh()
        flags, total = verify_and_count(mesh)(
            inp["a_words"], inp["r_words"], inp["s_windows"],
            inp["h_digits"], inp["s_canonical"],
        )
        assert int(total) == int(want.sum())
        assert np.array_equal(np.asarray(flags), want)

    @pytest.mark.slow  # ~1.5 min wall clock on the CI box
    def test_verifyplane_uses_meshed_verifier(self):
        from stellard_tpu.node.verifyplane import VerifyPlane

        plane = VerifyPlane(backend="tpu", min_device_batch=8)
        try:
            reqs, want = make_reqs(64, corrupt={7})
            # force-teach the model that the device wins so routing is
            # deterministic in this test
            plane.model.observe_cpu(10, 1000.0)
            got = plane.verify_many(reqs)
            assert np.array_equal(got, want)
            assert plane.device_batches == 1
            assert isinstance(plane.verifier, TpuVerifier)
            assert plane.verifier.n_devices == len(jax.devices())
        finally:
            plane.stop()


class TestMeshedHashing:
    """The hashing twin: flat-batch SHA-512-half shards over the mesh."""

    def test_prefix_hash_batch_shards_and_matches_host(self):
        from stellard_tpu.crypto.backend import CpuHasher, TpuHasher

        rng = np.random.default_rng(5)
        prefixes = [0x54584E00] * 100
        payloads = [rng.bytes(int(rng.integers(10, 900))) for _ in range(100)]
        tpu = TpuHasher()
        got = tpu.prefix_hash_batch(prefixes, payloads)
        want = CpuHasher().prefix_hash_batch(prefixes, payloads)
        assert got == want
        assert tpu.n_devices == 8  # mesh="auto" default on the 8-dev env
        # the kernel in use really is the mesh-sharded jit (its input
        # shardings name the batch axis)
        kern = tpu._masked_kernel()
        shardings = getattr(kern, "_in_shardings", None) or getattr(
            kern, "in_shardings", None
        )
        if shardings is not None:  # jax version exposes them
            assert any(s is not None for s in shardings)

    def test_every_width_matches_host_bytes(self):
        """mesh= is a config axis: widths 1/2/4/8 of the SAME sharded
        program produce byte-identical digests on ragged batches (37
        messages — not divisible by any width)."""
        from stellard_tpu.crypto.backend import CpuHasher, TpuHasher

        rng = np.random.default_rng(11)
        prefixes = [0x4D494E00] * 37
        payloads = [rng.bytes(int(rng.integers(1, 700))) for _ in range(37)]
        want = CpuHasher().prefix_hash_batch(prefixes, payloads)
        for width in (1, 2, 4, 8):
            h = TpuHasher(mesh=str(width))
            assert h.prefix_hash_batch(prefixes, payloads) == want
            assert h.n_devices == width

    def test_non_pow2_width_rounds_down(self):
        from stellard_tpu.crypto.backend import TpuHasher

        h = TpuHasher(mesh="3")
        h.prefix_hash_batch([0x1234], [b"x"])
        assert h.n_devices == 2  # pow2 floor: the leaf batcher pads
        # rows to powers of two, only pow2 widths divide them evenly
