"""Field arithmetic mod p = 2^255 - 19 for batched Ed25519 on TPU.

Representation: 20 limbs x 13 bits, int32, little-endian limb order,
LIMB-MAJOR layout: shape [20, *batch]. The batch axes TRAIL, so the batch
dimension lands in the TPU minor (lane) axis — a [20, B] tensor tiles as
(sublane=20, lane=B) and fills all 128 vector lanes for B >= 128, where
the previous batch-major [B, 20] layout left 108 of 128 lanes idle (the
limb axis, size 20, was minor). Measured on-chip this layout bound was
the kernel's dominant cost, not FLOPs.

Why 13-bit limbs in int32: schoolbook products are < 2^26.3 and a 20-term
column sum stays < 2^31, so the whole multiply runs in native int32 lanes
(TPU VPU width) with no 64-bit emulation. Reduction uses
2^260 ≡ 608 (mod p) folding (608 = 19 * 2^5, since 13*20 = 260 = 255 + 5).

Invariant maintained by every op: limbs in [0, 9500] ("bounded redundant",
mul-safe since 20 * 9500^2 < 2^31). The represented value is any 260-bit
integer; it is brought into canonical [0, p) form only where bytes /
equality / parity are produced (`fe_reduce_full`).

Engineering notes (all from profiling the batched verify kernel):
- multiply/square accumulate columns as pure SSA values (no
  scatter-style `.at[].add` updates — those materialize a fresh buffer
  per limb step and defeat XLA fusion),
- squaring uses the symmetric column halving (210 lane products instead
  of 400),
- additions/subtractions do ONE carry sweep plus a 2^260-overflow fold
  (the loose 9500 invariant absorbs the slack; full normalization would
  triple their cost),
- inversion and the decompression square root run fixed addition chains
  (254S+11M / 252S+11M) instead of a generic 2-ops-per-bit square&multiply
  ladder.

This fills the role of libsodium's ref10 fe25519 used by the reference's
crypto_sign_verify_detached path
(/root/reference/src/ripple_data/protocol/RippleAddress.cpp:190-252).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax
from contextlib import contextmanager


NLIMB = 20
BITS = 13
MASK = (1 << BITS) - 1
P = (1 << 255) - 19
FOLD = 608  # 2^260 mod p = 19 * 2^5

D = (-121665 * pow(121666, P - 2, P)) % P  # Edwards d
D2 = (2 * D) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)  # sqrt(-1)
L = (1 << 252) + 27742317777372353535851937790883648493  # group order l

# Loose limb bound maintained by every op (see module docstring).
BOUND = 9500


def int_to_limbs_np(x: int, n: int = NLIMB) -> np.ndarray:
    out = np.zeros(n, dtype=np.int32)
    for i in range(n):
        out[i] = x & MASK
        x >>= BITS
    if x:
        raise ValueError("value does not fit in limbs")
    return out


def limbs_to_int(limbs) -> int:
    limbs = np.asarray(limbs)
    return sum(int(v) << (BITS * i) for i, v in enumerate(limbs))


_P_LIMBS = int_to_limbs_np(P)
# Subtraction bias: 38p, laid out limb-wise as 38 * (limbs of p) so every
# bias limb (min 38*255 = 9690) dominates any invariant limb (<= 9500):
# a + bias - b is limb-wise non-negative, and bias ≡ 0 (mod p).
_BIAS_LIMBS = (38 * _P_LIMBS).astype(np.int32)


# --- constant provisioning ------------------------------------------------
# Pallas kernels cannot capture array constants ("pass them as inputs"),
# so every [20]-limb constant routes through _const20(). Outside a kernel
# it just materializes the numpy array (jaxpr constant, status quo). For
# a Pallas trace, ed25519_pallas first traces the math in COLLECT mode to
# enumerate the distinct constants, then passes the stacked [K, 20] table
# as a kernel input and sets CONSUME mode so _const20 returns rows of it.
_CONST_MODE: str | None = None  # None | "collect" | "consume"
_CONST_INDEX: dict[bytes, int] = {}
_CONST_ROWS: list[np.ndarray] = []
_CONST_TABLE: jnp.ndarray | None = None  # [K, 20] while consuming


def _const20(limbs_np: np.ndarray) -> jnp.ndarray:
    row = np.asarray(limbs_np, np.int32)
    if _CONST_MODE is None:
        return jnp.asarray(row)
    key = row.tobytes()
    idx = _CONST_INDEX.get(key)
    if idx is None:
        if _CONST_MODE == "consume":
            raise KeyError(
                "fe25519 constant not seen during the collect trace — "
                "the Pallas const table is incomplete"
            )
        idx = len(_CONST_ROWS)
        _CONST_INDEX[key] = idx
        _CONST_ROWS.append(row)
    if _CONST_MODE == "collect":
        return jnp.asarray(row)
    return _CONST_TABLE[idx]


def _col(limbs_1d, ndim: int) -> jnp.ndarray:
    """[20] constant -> [20, 1, 1, ...] so it broadcasts against a
    limb-major [20, *batch] tensor of rank `ndim`."""
    arr = _const20(limbs_1d)
    return arr.reshape((NLIMB,) + (1,) * (ndim - 1)) if ndim > 1 else arr


@contextmanager
def const_mode(mode: str, table: jnp.ndarray | None = None):
    """Scope the constant-provisioning mode (see _const20). ``collect``
    records every distinct [20]-limb constant a trace touches;
    ``consume`` serves them from ``table`` ([K, 20], normally a Pallas
    kernel input). Traces are single-threaded per kernel build; the
    caller (ed25519_pallas) holds a lock around nested use."""
    global _CONST_MODE, _CONST_TABLE
    prev_mode, prev_table = _CONST_MODE, _CONST_TABLE
    _CONST_MODE, _CONST_TABLE = mode, table
    try:
        yield
    finally:
        _CONST_MODE, _CONST_TABLE = prev_mode, prev_table


def const_table_np() -> np.ndarray:
    """The collected constants as one [K, 20] int32 table."""
    if not _CONST_ROWS:
        raise RuntimeError("no constants collected — run a collect trace")
    return np.stack(_CONST_ROWS, axis=0)


def _align2(a: jnp.ndarray, b: jnp.ndarray):
    """Limb-major rank alignment: numpy broadcasting prepends axes, but a
    [20] constant must align with [20, *batch] by APPENDING singleton
    batch axes. Every binary fe op routes through this."""
    if a.ndim < b.ndim:
        a = a.reshape(a.shape + (1,) * (b.ndim - a.ndim))
    elif b.ndim < a.ndim:
        b = b.reshape(b.shape + (1,) * (a.ndim - b.ndim))
    return a, b


def fe_const(x: int, batch_shape=()) -> jnp.ndarray:
    limbs = _const20(int_to_limbs_np(x % P))
    out = limbs.reshape((NLIMB,) + (1,) * len(batch_shape))
    return jnp.broadcast_to(out, (NLIMB,) + tuple(batch_shape))


def _carry(c: jnp.ndarray, steps: int) -> jnp.ndarray:
    """Global carry-propagation steps (arithmetic shifts, so signed values
    borrow correctly). Value-preserving; callers size buffers so the top
    limb's carry-out is never dropped."""
    for _ in range(steps):
        hi = c >> BITS
        c = (c & MASK) + jnp.concatenate([jnp.zeros_like(hi[:1]), hi[:-1]], axis=0)
    return c


def _carry20_fold(c: jnp.ndarray) -> jnp.ndarray:
    """One carry sweep over a 20-limb value with limbs < 2^18.3, folding
    the limb-19 carry-out (weight 2^260) onto limb 0 as * FOLD.
    Output limbs <= 8191 + 40 + FOLD*3 < BOUND."""
    hi = c >> BITS
    lo = c & MASK
    shifted = jnp.concatenate([jnp.zeros_like(hi[:1]), hi[:-1]], axis=0)
    out = lo + shifted
    return jnp.concatenate([(out[0] + FOLD * hi[19])[None], out[1:]], axis=0)


def _finish_mul_t(lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
    """Shared tail of multiply/square: fold the 19 high columns
    (weights 2^260..) onto the 20 low ones via 2^260 ≡ FOLD, then carry.

    lo: [20, *batch] column sums < 2^31. hi: [19, *batch] column sums."""
    # carry hi first so FOLD*hi stays in int32; 2 spare limbs so no
    # carry-out is ever dropped
    hi = jnp.concatenate(
        [hi, jnp.zeros((2,) + hi.shape[1:], hi.dtype)], axis=0
    )
    hi = _carry(hi, 2)  # limbs <= MASK + 33
    c = lo + FOLD * hi[:20]  # < 2^31
    # hi[20] (weight 2^260 * 2^260) folds with FOLD^2; hi's own carrying
    # makes it tiny (<= 33)
    c0 = c[0] + (FOLD * FOLD) * hi[20]
    c = jnp.concatenate(
        [c0[None], c[1:], jnp.zeros((2,) + c.shape[1:], c.dtype)], axis=0
    )
    c = _carry(c, 2)  # limbs <= MASK + 33; c[20] <= MASK + 33, c[21] <= 33
    h = c[19] >> 8  # bits >= 2^255 in limb 19
    c0 = c[0] + 19 * h + FOLD * (c[20] + (c[21] << BITS))
    c = jnp.concatenate([c0[None], c[1:19], (c[19] & 0xFF)[None]], axis=0)
    return _carry(c, 2)  # limbs <= MASK + 33 < BOUND


def _rows_padsum(rows: list) -> jnp.ndarray:
    """rows[i]: [len_i, *batch] partial products whose limb 0 sits at
    column offset off_i; returns [39, *batch] column sums."""
    nb = rows[0][1].ndim - 1
    padded = [
        jnp.pad(r, ((off, 2 * NLIMB - 1 - off - r.shape[0]),) + ((0, 0),) * nb)
        for off, r in rows
    ]
    return jnp.sum(jnp.stack(padded, axis=0), axis=0)


def fe_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook 20x20 product -> 39 column sums + fold.

    The limb axis stays inside the tensors: 20 shifted row-products,
    padded to the 39-column width and summed in one reduction (~45 wide
    ops). A TPU core runs the post-fusion op sequence serially, so the
    kernel's cost tracks the op COUNT, not its FLOPs."""
    a, b = jnp.broadcast_arrays(*_align2(a, b))
    # row i = a_i * b lands at columns i..i+19
    cols = _rows_padsum([(i, a[i] * b) for i in range(NLIMB)])
    return _finish_mul_t(cols[:NLIMB], cols[NLIMB:])


def fe_square(a: jnp.ndarray) -> jnp.ndarray:
    """Symmetric schoolbook square: halved off-diagonal work."""
    # row i = a_i * (a_i, 2a_{i+1}, .., 2a_19) lands at columns
    # 2i..i+19; every i<j pair appears once, doubled. Bounds: column k
    # sums the pairs (i, k-i) with i <= k-i < 20 — at most 10 of them
    # (k = 19: (0,19)..(9,10); k = 20: (1,19)..(10,10)) — each term
    # <= 2*BOUND^2 = 1.805e8, so the worst column is 10 * 1.805e8 =
    # 1.805e9 < 2^31.
    rows = []
    for i in range(NLIMB):
        seg = a[i] * a[i:]  # [NLIMB - i, *batch]
        if seg.shape[0] > 1:
            seg = jnp.concatenate([seg[:1], seg[1:] + seg[1:]], axis=0)
        rows.append((2 * i, seg))
    cols = _rows_padsum(rows)
    return _finish_mul_t(cols[:NLIMB], cols[NLIMB:])


def fe_add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    a, b = _align2(a, b)
    c = a + b  # limbs <= 2*BOUND < 2^14.3
    return _carry20_fold(c)


def fe_sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    a, b = _align2(a, b)
    ndim = max(a.ndim, b.ndim)
    c = a + _col(_BIAS_LIMBS, ndim) - b  # limb-wise >= 0; value = a-b+38p
    return _carry20_fold(c)


def fe_neg(a: jnp.ndarray) -> jnp.ndarray:
    return fe_sub(jnp.zeros_like(a), a)


def fe_reduce_full(a: jnp.ndarray) -> jnp.ndarray:
    """Exact canonical form: value in [0, p), limbs < 2^13.

    Folding limb 19's bits >= 2^255 FIRST (2^255 ≡ 19) brings the value
    under 2p before any carry sweep, so no 2^260 carry-out ever exists
    to drop; the conditional subtract then handles the last excess."""
    h = a[19] >> 8
    c = jnp.concatenate(
        [(a[0] + 19 * h)[None], a[1:19], (a[19] & 0xFF)[None]], axis=0
    )
    c = _carry(c, NLIMB + 1)
    # limbs < 2^13 exactly, value < 2^255 + eps; subtract p once if >= p
    ge = (
        (c[19] >= 0x100)
        | (
            (c[19] == 0xFF)
            & jnp.all(c[1:19] == MASK, axis=0)
            & (c[0] >= MASK - 18)
        )
    )
    p_col = _col(_P_LIMBS, c.ndim)
    c = c - jnp.where(ge, p_col, jnp.zeros_like(p_col))
    return _carry(c, NLIMB + 1)


def limbs_lt_p(a: jnp.ndarray) -> jnp.ndarray:
    """[20, *batch] CANONICAL-per-limb value (each limb < 2^13, e.g.
    straight from limbs_from_words_le) -> [*batch] bool: value < p.

    Unrolled most-significant-first compare (no cumprod/scan — the
    helper must lower inside Pallas kernels)."""
    p_col = _col(_P_LIMBS, a.ndim)
    lt = jnp.zeros(a.shape[1:], bool)
    all_eq = jnp.ones(a.shape[1:], bool)
    for k in range(NLIMB - 1, -1, -1):
        lt = lt | (all_eq & (a[k] < p_col[k]))
        all_eq = all_eq & (a[k] == p_col[k])
    return lt


def _sqn(a: jnp.ndarray, n: int) -> jnp.ndarray:
    """a^(2^n): n chained squarings. Rolled for large n (small XLA graph,
    the loop body is one fused square); unrolled when tiny."""
    if n <= 4:
        for _ in range(n):
            a = fe_square(a)
        return a
    return lax.fori_loop(0, n, lambda i, x: fe_square(x), a)


def _chain_250(a: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Core of the curve25519 inversion/sqrt addition chains: returns
    (a^(2^250 - 1), a^11)."""
    z2 = fe_square(a)  # a^2
    z9 = fe_mul(_sqn(z2, 2), a)  # a^9
    z11 = fe_mul(z9, z2)  # a^11
    z2_5_0 = fe_mul(fe_square(z11), z9)  # a^(2^5 - 1)
    z2_10_0 = fe_mul(_sqn(z2_5_0, 5), z2_5_0)  # a^(2^10 - 1)
    z2_20_0 = fe_mul(_sqn(z2_10_0, 10), z2_10_0)
    z2_40_0 = fe_mul(_sqn(z2_20_0, 20), z2_20_0)
    z2_50_0 = fe_mul(_sqn(z2_40_0, 10), z2_10_0)
    z2_100_0 = fe_mul(_sqn(z2_50_0, 50), z2_50_0)
    z2_200_0 = fe_mul(_sqn(z2_100_0, 100), z2_100_0)
    z2_250_0 = fe_mul(_sqn(z2_200_0, 50), z2_50_0)
    return z2_250_0, z11


def fe_invert(a: jnp.ndarray) -> jnp.ndarray:
    """a^(p-2) = a^(2^255 - 21): 254 squarings + 11 multiplies."""
    z2_250_0, z11 = _chain_250(a)
    return fe_mul(_sqn(z2_250_0, 5), z11)


def fe_pow_p58(a: jnp.ndarray) -> jnp.ndarray:
    """a^((p-5)/8) = a^(2^252 - 3): 252 squarings + 11 multiplies."""
    z2_250_0, _ = _chain_250(a)
    return fe_mul(_sqn(z2_250_0, 2), a)


def fe_pow(a: jnp.ndarray, e: int) -> jnp.ndarray:
    """a^e for a static exponent. The two hot exponents route to their
    addition chains; anything else falls back to a rolled ladder."""
    if e == P - 2:
        return fe_invert(a)
    if e == (P - 5) // 8:
        return fe_pow_p58(a)
    bits = [int(b) for b in bin(e)[2:]]
    bits_arr = jnp.asarray(np.array(bits, dtype=np.int32))

    def body(i, r):
        r = fe_square(r)
        return jnp.where(bits_arr[i] == 1, fe_mul(r, a), r)

    return lax.fori_loop(1, len(bits), body, a)


def fe_is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(fe_reduce_full(a) == 0, axis=0)


def fe_eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    ra, rb = _align2(fe_reduce_full(a), fe_reduce_full(b))
    return jnp.all(ra == rb, axis=0)


def fe_is_odd(a: jnp.ndarray) -> jnp.ndarray:
    return (fe_reduce_full(a)[0] & 1) == 1


def fe_select(cond: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a where cond else b; cond is [*batch], a/b are [20, *batch] —
    trailing-axis broadcasting aligns cond with the batch axes."""
    return jnp.where(cond, *_align2(a, b))


def limbs_from_words_le(words_u32: jnp.ndarray, mask_high: bool = True) -> jnp.ndarray:
    """[8, *batch] uint32 little-endian words -> [20, *batch] int32 limbs.

    With mask_high, bit 255 (the point-compression sign bit) is dropped.
    """
    w = words_u32
    out = []
    for k in range(NLIMB):
        bit = BITS * k
        a, r = divmod(bit, 32)
        lo = w[a] >> r
        if r + BITS > 32 and a + 1 < 8:
            lo = lo | (w[a + 1] << (32 - r))
        lo = lo & MASK
        if mask_high and k == NLIMB - 1:
            lo = lo & 0xFF
        out.append(lo.astype(jnp.int32))
    return jnp.stack(out, axis=0)


def limbs_to_words_le(limbs: jnp.ndarray) -> jnp.ndarray:
    """Canonical [20, *batch] limbs -> [8, *batch] uint32 LE words."""
    l = limbs.astype(jnp.uint32)
    words = []
    for wi in range(8):
        bit0 = 32 * wi
        w = jnp.zeros(limbs.shape[1:], jnp.uint32)
        for k in range(NLIMB):
            lb = BITS * k
            if lb + BITS <= bit0 or lb >= bit0 + 32:
                continue
            sh = lb - bit0
            if sh >= 0:
                w = w | (l[k] << sh)
            else:
                w = w | (l[k] >> (-sh))
        words.append(w)
    return jnp.stack(words, axis=0)
