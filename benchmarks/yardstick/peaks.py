"""Peaks of the devices the benchmark may run on, keyed by the
``device_kind`` JAX reports, and the functions that count a program's
work from its shapes. A kind that is not in the table is an error, not
a default."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e, 819 GB/s.
    # No int32 vector (VPU) peak is published; the verify program is
    # int32 lane arithmetic, so its roofline share waits for a measured
    # peak (PERF.md, Open questions).
    "TPU v5 lite": {
        "source": "Google Cloud documentation, 'TPU v5e'",
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "int32_vpu_ops_per_s": None,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in the peaks table "
            f"({sorted(PEAKS)}): add it with its source"
        ) from None


# Ed25519 batch verify, as the program's XLA kernel computes it (the
# count PERF.md used to give only in prose, "about 3.1M int32 lane-ops a
# signature"): a double-scalar multiplication [S]B + [h](-A) over 256
# bits with a fixed-base comb for B and a 4-bit window for A, in
# radix-2^13 limbs (20 limbs of int32).
_LIMBS = 20
_FE_MUL_OPS = 2 * _LIMBS * _LIMBS + 6 * _LIMBS  # products, adds, carries
_POINT_ADD_FE_MULS = 9
_POINT_DBL_FE_MULS = 8
_DECOMPRESS_FE_MULS = 265  # one field inversion/sqrt chain (~255 squarings)
_FINAL_INVERT_FE_MULS = 265


def verify_int32_lane_ops(lanes: int) -> int:
    """int32 lane-operations of one verify batch of ``lanes`` padded
    signatures: the work the chip does whether a lane is useful or pad."""
    doublings = 256 * _POINT_DBL_FE_MULS
    adds_a = 64 * _POINT_ADD_FE_MULS  # 4-bit windows of h
    adds_b = 64 * _POINT_ADD_FE_MULS  # comb columns of S
    table_a = 15 * _POINT_ADD_FE_MULS  # the per-signature window table
    fe_muls = (doublings + adds_a + adds_b + table_a
               + _DECOMPRESS_FE_MULS + _FINAL_INVERT_FE_MULS)
    return lanes * fe_muls * _FE_MUL_OPS


def verify_batch_bytes(lanes: int, wire: str = "raw") -> int:
    """Host-to-device bytes of one padded batch (public key, signature,
    32-byte hash a lane) plus the verdicts back."""
    per_lane = {"raw": 32 + 64 + 32}[wire]
    return lanes * per_lane + lanes


def tree_program_bytes(nodes: int, kind: str) -> int:
    """Bytes a hash program reads and writes for ``nodes`` tree nodes: an
    inner node is 16 child hashes of 32 bytes behind a 4-byte prefix, a
    leaf is its padded payload; each node writes a 32-byte digest."""
    read = {"inner": 4 + 16 * 32, "leaf": 256}[kind]
    return nodes * (read + 32)


def sha512_ops(nodes: int, blocks_per_node: int) -> int:
    """64-bit operations of SHA-512 over ``nodes`` messages: 80 rounds a
    128-byte block, about 40 operations a round with the schedule."""
    return nodes * blocks_per_node * 80 * 40


def roofline_share(ops: float, bytes_: float, seconds: float,
                   peak_ops_per_s, peak_bytes_per_s):
    """-> (share in %, which bound) or None while a peak is missing."""
    if not peak_ops_per_s or not peak_bytes_per_s or seconds <= 0:
        return None
    t_ops, t_bytes = ops / peak_ops_per_s, bytes_ / peak_bytes_per_s
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
