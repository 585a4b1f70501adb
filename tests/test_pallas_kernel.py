"""Differential test of the Pallas whole-verify-in-VMEM Ed25519 kernel
(ops/ed25519_pallas.py) against the host library, in interpreter mode on
the CPU backend.

Covers: multi-block grids, tail padding, and cryptographically planted
corruption (R byte, S low byte, public key byte, message swap) — the
same adversarial shapes the XLA kernel's suite pins, so both
implementations are held to the identical contract
(reference: crypto_sign_verify_detached semantics incl. canonical-S,
src/ripple_data/protocol/RippleAddress.cpp:190-252).
"""

import os

import numpy as np
import pytest

# small grid block keeps interpreter cost CI-sized; set before the
# module under test is imported (read once at import, jit-static)
os.environ["STELLARD_PALLAS_BLOCK"] = "128"

from stellard_tpu.ops.ed25519_jax import prepare_batch  # noqa: E402
from stellard_tpu.ops.ed25519_pallas import (  # noqa: E402
    verify_kernel_pallas,
)
from stellard_tpu.protocol.keys import KeyPair  # noqa: E402


@pytest.mark.slow  # ~2 min interpret-mode wall clock on the CI box
def test_pallas_verify_differential():
    rng = np.random.default_rng(31)
    keys = [
        KeyPair.from_seed(bytes(rng.integers(0, 256, 32, dtype=np.uint8)))
        for _ in range(4)
    ]
    n = 130  # > one 128-lane block: exercises the grid AND tail padding
    msgs = [
        bytes(rng.integers(0, 256, 32, dtype=np.uint8)) for _ in range(n)
    ]
    sigs = [keys[i % 4].sign(msgs[i]) for i in range(n)]
    pubs = [keys[i % 4].public for i in range(n)]
    expect = np.ones(n, bool)

    def corrupt(idx: int, kind: str) -> None:
        if kind == "r":
            s = bytearray(sigs[idx])
            s[5] ^= 0x40
            sigs[idx] = bytes(s)
        elif kind == "s":
            s = bytearray(sigs[idx])
            s[33] ^= 0x01
            sigs[idx] = bytes(s)
        elif kind == "a":
            p = bytearray(pubs[idx])
            p[7] ^= 0x20
            pubs[idx] = bytes(p)
        elif kind == "m":
            msgs[idx] = bytes(32)
        expect[idx] = False

    corrupt(3, "r")
    corrupt(9, "s")
    corrupt(17, "a")
    corrupt(25, "m")
    corrupt(129, "r")  # in the padded tail block

    got = np.asarray(verify_kernel_pallas(**prepare_batch(pubs, msgs, sigs)))
    assert got.shape == (n,)
    assert (got == expect).all(), np.nonzero(got != expect)


def _export_for_tpu(stack_depth: int = 0):
    """Export one Pallas block program for the TPU platform from the CPU
    host, from ``stack_depth`` extra Python frames down."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax import export

    from stellard_tpu.ops import ed25519_pallas as P

    if stack_depth:
        return _export_for_tpu(stack_depth - 1)
    with P._TRACE_LOCK:
        ktab = P._ensure_const_table()
    blk = P.BLOCK
    args = (
        jax.ShapeDtypeStruct((8, blk), jnp.uint32),
        jax.ShapeDtypeStruct((8, blk), jnp.uint32),
        jax.ShapeDtypeStruct((64, blk), jnp.int32),
        jax.ShapeDtypeStruct((64, blk), jnp.int32),
        jax.ShapeDtypeStruct((1, blk), jnp.int32),
        jax.ShapeDtypeStruct((64, 60, 16), jnp.int32),
        jax.ShapeDtypeStruct(ktab.shape, jnp.int32),
    )
    fn = functools.partial(P._call, interpret=False, nconst=ktab.shape[0])
    with P._TRACE_LOCK:
        return export.export(jax.jit(fn), platforms=["tpu"])(*args)


def test_pallas_lowers_for_tpu():
    """Cross-platform export must produce TPU MLIR: Mosaic supports a
    subset of primitives (no value dynamic_slice, no scatter, no 1-D
    iota...), and a refactor of the shared fe/pt helpers can silently
    reintroduce one. This catches it on the CPU host — chip time is
    too scarce to spend discovering lowering errors.

    The module must also be the same from any call stack: the Mosaic
    payload rides the custom call and the persistent compile cache
    hashes it, so a payload that embeds the caller's traceback makes
    every entry point compile its own copy (utils/xlacache.py)."""
    exp = _export_for_tpu()
    assert len(exp.mlir_module_serialized) > 0
    deeper = _export_for_tpu(stack_depth=3)
    assert deeper.mlir_module_serialized == exp.mlir_module_serialized


@pytest.mark.slow  # ~2.5 min interpret-mode wall clock on the CI box
def test_pallas_matches_oracle_on_edge_cases():
    """The adversarial corpus the XLA kernel is pinned by (y=0 / identity
    / invalid-encoding / non-canonical-y pubkeys, bad R, non-canonical S,
    random bit flips) must give byte-identical verdicts from the Pallas
    kernel — both implementations answer to the same Python oracle."""
    from stellard_tpu.ops import ed25519_ref as ref
    from test_crypto_plane import _make_cases  # pytest's module name

    cases = _make_cases(48)
    pubs, msgs, sigs = (list(t) for t in zip(*cases))
    got = np.asarray(verify_kernel_pallas(**prepare_batch(pubs, msgs, sigs)))
    want = np.array([ref.verify(p, m, s) for p, m, s in cases])
    assert np.array_equal(got, want), np.nonzero(got != want)
