"""float/double -> string with Java Float.toString/Double.toString semantics.

Reference: /root/reference/src/main/cpp/src/cast_float_to_string.cu (API :35)
and ftos_converter.cuh, which port the Ryu shortest-round-trip algorithm
(d2d :480, f2d :659) plus Java's formatting rules (to_chars :796): decimal
notation for 1e-3 <= |x| < 1e7, otherwise scientific "d.dddEexp"; specials
"NaN", "Infinity", "-Infinity", "0.0", "-0.0"; golden vectors in
tests/cast_float_to_string.cpp (e.g. 123456789012.34f -> "1.2345679E11").

TPU-native design — no per-row char loop, everything is fused vector math:

1.  Ryu tables (pow5 / inverse-pow5 fixed-point factors) are generated
    host-side at import with exact Python bigints and shipped to device as
    uint64 / (N,4)-uint32-limb constants.
2.  The shortest-digit search runs as one jitted kernel over the whole
    column: the 64x128-bit fixed-point multiplies are 32-bit-limb schoolbook
    products in uint64 accumulators (TPU has no native u128), and Ryu's
    digit-removal loops are unrolled to their worst-case depth with lane
    masks (every lane stops at its own shortest length).
3.  Formatting writes sign/digits/point/exponent chars into a padded
    (n, 40) byte matrix with one batched scatter, then assembles the Arrow
    string column with the standard measure->gather pattern.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes
from ..columnar.column import Column, strings_from_padded

# ---------------------------------------------------------------------------
# Host-side table generation (exact bigint math)
# ---------------------------------------------------------------------------


def _pow5bits(e: int) -> int:
    return ((e * 1217359) >> 19) + 1


def _log10_pow2(e: int) -> int:
    return (e * 78913) >> 18


def _log10_pow5(e: int) -> int:
    return (e * 732923) >> 20


_F_INV_BITS = 59   # FLOAT_POW5_INV_BITCOUNT
_F_POW_BITS = 61   # FLOAT_POW5_BITCOUNT
_D_INV_BITS = 125  # DOUBLE_POW5_INV_BITCOUNT
_D_POW_BITS = 125  # DOUBLE_POW5_BITCOUNT


def _gen_float_tables():
    inv = []
    for q in range(32):
        k = _F_INV_BITS + _pow5bits(q) - 1
        inv.append((1 << k) // 5**q + 1)
    pow5 = []
    for i in range(49):
        b = _pow5bits(i)
        if b <= _F_POW_BITS:
            pow5.append(5**i << (_F_POW_BITS - b))
        else:
            pow5.append(5**i >> (b - _F_POW_BITS))
    return (np.array(inv, np.uint64), np.array(pow5, np.uint64))


def _gen_double_tables():
    def limbs(v: int) -> Tuple[int, int, int, int]:
        return tuple((v >> (32 * j)) & 0xFFFFFFFF for j in range(4))

    inv = []
    for q in range(293):
        k = _D_INV_BITS + _pow5bits(q) - 1
        inv.append(limbs((1 << k) // 5**q + 1))
    pow5 = []
    for i in range(327):
        b = _pow5bits(i)
        if b <= _D_POW_BITS:
            pow5.append(limbs(5**i << (_D_POW_BITS - b)))
        else:
            pow5.append(limbs(5**i >> (b - _D_POW_BITS)))
    return (np.array(inv, np.uint32), np.array(pow5, np.uint32))


_F_INV_TABLE, _F_POW5_TABLE = _gen_float_tables()
_D_INV_TABLE, _D_POW5_TABLE = _gen_double_tables()
_POW10_U64 = np.array([10**k for k in range(20)], np.uint64)
_POW5_U64 = np.array([5**k for k in range(23)], np.uint64)

_U64 = jnp.uint64
_MASK32 = jnp.uint64(0xFFFFFFFF)


def _u(x) -> jnp.ndarray:
    return jnp.asarray(x, jnp.uint64)


# ---------------------------------------------------------------------------
# Fixed-point multiplies
# ---------------------------------------------------------------------------


def _mulshift32(m, factor, j):
    """(m * factor) >> j for m < 2^27, factor < 2^64, 32 < j < 91.

    (factor_hi<<32 + factor_lo) * m >> j == (m*factor_hi + (m*factor_lo >> 32))
    >> (j - 32) exactly, because the low 32 bits carry nothing upward.
    """
    plo = m * (factor & _MASK32)
    phi = m * (factor >> _u(32))
    return (phi + (plo >> _u(32))) >> (j - _u(32))


def _mulshift128(m, flimbs, j):
    """(m * factor) >> j for m < 2^56, factor a (n,4) little-endian uint32
    limb matrix (held in uint64 lanes), 96 <= j < 192. Schoolbook product
    into 32-bit columns with uint64 accumulators, then a 64-bit window
    extract at bit j."""
    m_lo = m & _MASK32
    m_hi = m >> _u(32)
    acc = [jnp.zeros_like(m) for _ in range(8)]
    for l in range(4):
        f = flimbs[:, l]
        p = m_lo * f
        acc[l] = acc[l] + (p & _MASK32)
        acc[l + 1] = acc[l + 1] + (p >> _u(32))
        p = m_hi * f
        acc[l + 1] = acc[l + 1] + (p & _MASK32)
        acc[l + 2] = acc[l + 2] + (p >> _u(32))
    limbs = []
    carry = jnp.zeros_like(m)
    for k in range(8):
        s = acc[k] + carry
        limbs.append(s & _MASK32)
        carry = s >> _u(32)
    L = jnp.stack(limbs, axis=1)  # (n, 8) uint64 lanes holding 32-bit limbs
    s_idx = (j >> _u(5)).astype(jnp.int32)
    off = j & _u(31)
    cols = jnp.arange(4, dtype=jnp.int32)[None, :] + s_idx[:, None]
    g = jnp.take_along_axis(L, jnp.clip(cols, 0, 7), axis=1)
    w0 = g[:, 0] | (g[:, 1] << _u(32))
    w1 = g[:, 2] | (g[:, 3] << _u(32))
    hi = jnp.where(off == 0, _u(0), w1 << (_u(64) - off))
    return (w0 >> off) | hi


# ---------------------------------------------------------------------------
# Ryu shortest-digit cores
# ---------------------------------------------------------------------------


def _removal_loops(vr, vp, vm, vr_tz, vm_tz, last_removed, accept, max_iter):
    """Ryu digit removal, unrolled with lane masks. Covers both the general
    trailing-zero-tracking loop and the vm trailing-zero strip."""
    removed = jnp.zeros_like(vr, dtype=jnp.int32)
    for _ in range(max_iter):
        c1 = (vp // _u(10)) > (vm // _u(10))
        c2 = (~c1) & vm_tz & (vm % _u(10) == 0)
        active = c1 | c2
        vm_tz = jnp.where(c1, vm_tz & (vm % _u(10) == 0), vm_tz)
        vr_tz = jnp.where(active, vr_tz & (last_removed == 0), vr_tz)
        last_removed = jnp.where(active, (vr % _u(10)).astype(jnp.int32),
                                 last_removed)
        vr = jnp.where(active, vr // _u(10), vr)
        vp = jnp.where(active, vp // _u(10), vp)
        vm = jnp.where(active, vm // _u(10), vm)
        removed = removed + active.astype(jnp.int32)
    # round-even correction
    last_removed = jnp.where(
        vr_tz & (last_removed == 5) & (vr % _u(2) == 0), 4, last_removed)
    round_up = ((vr == vm) & (~accept | ~vm_tz)) | (last_removed >= 5)
    return vr + round_up.astype(jnp.uint64), removed


def _decimal_length(v):
    """Number of decimal digits of v (uint64, v < 10^19)."""
    p10 = jnp.asarray(_POW10_U64)
    return (1 + jnp.sum(v[:, None] >= p10[None, 1:], axis=1)).astype(jnp.int32)


def _ryu_f32(bits):
    """bits: (n,) uint64 holding float32 bit patterns. Returns
    (digits u64, exp10 i32, sign bool, is_nan, is_inf, is_zero)."""
    mantissa = bits & _u((1 << 23) - 1)
    exponent = ((bits >> _u(23)) & _u(0xFF)).astype(jnp.int32)
    sign = (bits >> _u(31)) != 0
    is_nan = (exponent == 0xFF) & (mantissa != 0)
    is_inf = (exponent == 0xFF) & (mantissa == 0)
    is_zero = (exponent == 0) & (mantissa == 0)

    e2 = jnp.where(exponent == 0, 1, exponent) - (127 + 23 + 2)
    m2 = jnp.where(exponent == 0, mantissa, mantissa | _u(1 << 23))
    even = (m2 & _u(1)) == 0
    accept = even
    mv = _u(4) * m2
    mm_shift = ((mantissa != 0) | (exponent <= 1)).astype(jnp.uint64)
    mp = mv + _u(2)
    mm = mv - _u(1) - mm_shift

    inv_t = jnp.asarray(_F_INV_TABLE)
    pow_t = jnp.asarray(_F_POW5_TABLE)
    p5 = jnp.asarray(_POW5_U64)
    pos = e2 >= 0

    # ---- e2 >= 0 branch ---------------------------------------------------
    e2p = jnp.maximum(e2, 0)
    qp = jnp.asarray([_log10_pow2(e) for e in range(128)], jnp.int32)[
        jnp.clip(e2p, 0, 127)]
    kp = _F_INV_BITS + jnp.asarray([_pow5bits(q) for q in range(32)],
                                   jnp.int32)[jnp.clip(qp, 0, 31)] - 1
    jp = (-e2p + qp + kp).astype(jnp.uint64)
    fp = inv_t[jnp.clip(qp, 0, 31)]
    vr_p = _mulshift32(mv, fp, jp)
    vp_p = _mulshift32(mp, fp, jp)
    vm_p = _mulshift32(mm, fp, jp)
    # lastRemovedDigit pre-computation (f2s-only: its q overshoots by one)
    lr_cond_p = (qp != 0) & ((vp_p - _u(1)) // _u(10) <= vm_p // _u(10))
    qm1 = jnp.clip(qp - 1, 0, 31)
    lp = _F_INV_BITS + jnp.asarray([_pow5bits(q) for q in range(32)],
                                   jnp.int32)[qm1] - 1
    lr_p = (_mulshift32(mv, inv_t[qm1],
                        (-e2p + qp - 1 + lp).astype(jnp.uint64)) % _u(10))
    lr_p = jnp.where(lr_cond_p, lr_p, _u(0)).astype(jnp.int32)
    q_le9 = qp <= 9
    mv5 = mv % _u(5) == 0
    p5q = p5[jnp.clip(qp, 0, 22)]
    vr_tz_p = q_le9 & mv5 & (mv % p5q == 0)
    vm_tz_p = q_le9 & ~mv5 & accept & (mm % p5q == 0)
    vp_p = vp_p - (q_le9 & ~mv5 & ~accept & (mp % p5q == 0)).astype(jnp.uint64)

    # ---- e2 < 0 branch ----------------------------------------------------
    ne2 = jnp.maximum(-e2, 1)
    qn = jnp.asarray([_log10_pow5(e) for e in range(160)], jnp.int32)[
        jnp.clip(ne2, 0, 159)]
    i_n = ne2 - qn
    kn = jnp.asarray([_pow5bits(i) for i in range(49)], jnp.int32)[
        jnp.clip(i_n, 0, 48)] - _F_POW_BITS
    jn = (qn - kn).astype(jnp.uint64)
    fn = pow_t[jnp.clip(i_n, 0, 48)]
    vr_n = _mulshift32(mv, fn, jn)
    vp_n = _mulshift32(mp, fn, jn)
    vm_n = _mulshift32(mm, fn, jn)
    lr_cond_n = (qn != 0) & ((vp_n - _u(1)) // _u(10) <= vm_n // _u(10))
    i1 = jnp.clip(i_n + 1, 0, 48)
    jn2 = qn - 1 - (jnp.asarray([_pow5bits(i) for i in range(49)],
                                jnp.int32)[i1] - _F_POW_BITS)
    lr_n = (_mulshift32(mv, pow_t[i1],
                        jnp.maximum(jn2, 33).astype(jnp.uint64)) % _u(10))
    lr_n = jnp.where(lr_cond_n, lr_n, _u(0)).astype(jnp.int32)
    q_le1 = qn <= 1
    qc = jnp.clip(qn - 1, 0, 63).astype(jnp.uint64)
    vr_tz_n = jnp.where(q_le1, True,
                        (qn < 31) & ((mv & ((_u(1) << qc) - _u(1))) == 0))
    vm_tz_n = q_le1 & accept & (mm_shift == 1)
    vp_n = vp_n - (q_le1 & ~accept).astype(jnp.uint64)

    # ---- select branch ----------------------------------------------------
    e10 = jnp.where(pos, qp, qn + e2)
    vr = jnp.where(pos, vr_p, vr_n)
    vpv = jnp.where(pos, vp_p, vp_n)
    vmv = jnp.where(pos, vm_p, vm_n)
    vr_tz = jnp.where(pos, vr_tz_p, vr_tz_n)
    vm_tz = jnp.where(pos, vm_tz_p, vm_tz_n)
    last_removed = jnp.where(pos, lr_p, lr_n)

    digits, removed = _removal_loops(vr, vpv, vmv, vr_tz, vm_tz,
                                     last_removed, accept, max_iter=11)
    olength = _decimal_length(digits)
    exp10 = e10 + removed + olength - 1
    return digits, exp10, olength, sign, is_nan, is_inf, is_zero


def _ryu_f64(bits):
    """bits: (n,) uint64 float64 bit patterns; same contract as _ryu_f32."""
    mantissa = bits & _u((1 << 52) - 1)
    exponent = ((bits >> _u(52)) & _u(0x7FF)).astype(jnp.int32)
    sign = (bits >> _u(63)) != 0
    is_nan = (exponent == 0x7FF) & (mantissa != 0)
    is_inf = (exponent == 0x7FF) & (mantissa == 0)
    is_zero = (exponent == 0) & (mantissa == 0)

    e2 = jnp.where(exponent == 0, 1, exponent) - (1023 + 52 + 2)
    m2 = jnp.where(exponent == 0, mantissa, mantissa | _u(1 << 52))
    even = (m2 & _u(1)) == 0
    accept = even
    mv = _u(4) * m2
    mm_shift = ((mantissa != 0) | (exponent <= 1)).astype(jnp.uint64)
    mp = mv + _u(2)
    mm = mv - _u(1) - mm_shift

    inv_t = jnp.asarray(_D_INV_TABLE.astype(np.uint64))   # (293, 4)
    pow_t = jnp.asarray(_D_POW5_TABLE.astype(np.uint64))  # (327, 4)
    p5 = jnp.asarray(_POW5_U64)
    pos = e2 >= 0

    pow5bits_t = jnp.asarray([_pow5bits(i) for i in range(400)], jnp.int32)

    # ---- e2 >= 0 ----------------------------------------------------------
    e2p = jnp.maximum(e2, 0)
    log10pow2_t = jnp.asarray([_log10_pow2(e) for e in range(1000)], jnp.int32)
    qp = log10pow2_t[jnp.clip(e2p, 0, 999)] - (e2p > 3)
    qp = jnp.maximum(qp, 0)
    kp = _D_INV_BITS + pow5bits_t[jnp.clip(qp, 0, 292)] - 1
    jp = (-e2p + qp + kp).astype(jnp.uint64)
    fp = inv_t[jnp.clip(qp, 0, 292)]
    vr_p = _mulshift128(mv, fp, jp)
    vp_p = _mulshift128(mp, fp, jp)
    vm_p = _mulshift128(mm, fp, jp)
    q_le21 = qp <= 21
    mv5 = mv % _u(5) == 0
    p5q = p5[jnp.clip(qp, 0, 22)]
    vr_tz_p = q_le21 & mv5 & (mv % p5q == 0)
    vm_tz_p = q_le21 & ~mv5 & accept & (mm % p5q == 0)
    vp_p = vp_p - (q_le21 & ~mv5 & ~accept & (mp % p5q == 0)).astype(jnp.uint64)

    # ---- e2 < 0 -----------------------------------------------------------
    ne2 = jnp.maximum(-e2, 1)
    log10pow5_t = jnp.asarray([_log10_pow5(e) for e in range(1100)], jnp.int32)
    qn = log10pow5_t[jnp.clip(ne2, 0, 1099)] - (ne2 > 1)
    qn = jnp.maximum(qn, 0)
    i_n = ne2 - qn
    kn = pow5bits_t[jnp.clip(i_n, 0, 326)] - _D_POW_BITS
    jn = (qn - kn).astype(jnp.uint64)
    fn = pow_t[jnp.clip(i_n, 0, 326)]
    vr_n = _mulshift128(mv, fn, jn)
    vp_n = _mulshift128(mp, fn, jn)
    vm_n = _mulshift128(mm, fn, jn)
    q_le1 = qn <= 1
    qc = jnp.clip(qn, 0, 63).astype(jnp.uint64)
    vr_tz_n = jnp.where(q_le1, True,
                        (qn < 63) & ((mv & ((_u(1) << qc) - _u(1))) == 0))
    vm_tz_n = q_le1 & accept & (mm_shift == 1)
    vp_n = vp_n - (q_le1 & ~accept).astype(jnp.uint64)

    # ---- select -----------------------------------------------------------
    e10 = jnp.where(pos, qp, qn + e2)
    vr = jnp.where(pos, vr_p, vr_n)
    vpv = jnp.where(pos, vp_p, vp_n)
    vmv = jnp.where(pos, vm_p, vm_n)
    vr_tz = jnp.where(pos, vr_tz_p, vr_tz_n)
    vm_tz = jnp.where(pos, vm_tz_p, vm_tz_n)
    last_removed = jnp.zeros_like(vr, dtype=jnp.int32)

    digits, removed = _removal_loops(vr, vpv, vmv, vr_tz, vm_tz,
                                     last_removed, accept, max_iter=20)
    olength = _decimal_length(digits)
    exp10 = e10 + removed + olength - 1
    return digits, exp10, olength, sign, is_nan, is_inf, is_zero


# ---------------------------------------------------------------------------
# Java-style formatting (to_chars)
# ---------------------------------------------------------------------------

_PAD = 40  # >= longest possible output ("-2.2250738585072014E-308" is 24)


def _format_java(digits, exp10, olength, sign, is_nan, is_inf, is_zero):
    """Scatter Java-formatted chars into an (n, _PAD) byte matrix."""
    n = digits.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    # zeros format through the normal plain path as "0.0"
    digits = jnp.where(is_zero, _u(0), digits)
    olength = jnp.where(is_zero, 1, olength)
    exp10 = jnp.where(is_zero, 0, exp10)
    special = is_nan | is_inf

    plain = (exp10 >= -3) & (exp10 <= 6) & ~special
    sci = ~plain & ~special
    s = (sign & ~is_nan).astype(jnp.int32)  # '-' offset (NaN has no sign)

    idx_list = []
    val_list = []

    def emit(pos, ch, mask):
        idx_list.append(jnp.where(mask, pos, _PAD).astype(jnp.int32))
        val_list.append(jnp.broadcast_to(jnp.asarray(ch, jnp.uint8), (n,))
                        if jnp.ndim(ch) == 0 else ch.astype(jnp.uint8))

    # sign
    emit(jnp.zeros_like(s), ord("-"), (sign & ~is_nan))

    # per-digit characters, most significant first
    p10 = jnp.asarray(_POW10_U64)
    ip = exp10 + 1                       # plain int-part width (exp10 >= 0)
    zneg = -exp10 - 1                    # plain leading zeros (exp10 < 0)
    m = jnp.maximum(olength, 2)          # sci mantissa char budget
    for k in range(17):
        have = k < olength
        p = jnp.clip(olength - 1 - k, 0, 19)
        d = ((digits // p10[p]) % _u(10)).astype(jnp.uint8) + ord("0")
        # plain, exp10 >= 0: digit k sits before/after the point
        pos_pp = s + jnp.where(k < ip, k, k + 1)
        emit(pos_pp, d, plain & (exp10 >= 0) & have)
        # plain, exp10 < 0: "0." + zeros + digits
        emit(s + 2 + zneg + k, d, plain & (exp10 < 0) & have)
        # scientific: d0 then point then rest
        pos_sci = jnp.where(k == 0, s, s + 1 + k)
        emit(pos_sci, d, sci & have)

    # plain exp10 >= 0 furniture: int-part zero padding, point, frac zero
    pge = plain & (exp10 >= 0)
    for t in range(7):
        emit(s + olength + t, ord("0"), pge & (olength + t < ip))
    emit(s + ip, ord("."), pge)
    emit(s + ip + 1, ord("0"), pge & (olength <= ip))

    # plain exp10 < 0 furniture: "0." and up to 2 zeros
    plt = plain & (exp10 < 0)
    emit(jnp.broadcast_to(s, (n,)), ord("0"), plt)
    emit(s + 1, ord("."), plt)
    for t in range(2):
        emit(s + 2 + t, ord("0"), plt & (t < zneg))

    # scientific furniture: point, pad zero, E, exponent
    emit(s + 1, ord("."), sci)
    emit(s + 2, ord("0"), sci & (olength == 1))
    emit(s + m + 1, ord("E"), sci)
    eneg = exp10 < 0
    eabs = jnp.abs(exp10)
    emit(s + m + 2, ord("-"), sci & eneg)
    es = s + m + 2 + eneg.astype(jnp.int32)
    ne_dig = 1 + (eabs >= 10).astype(jnp.int32) + (eabs >= 100).astype(jnp.int32)
    emit(es, (eabs // 100 % 10 + ord("0")).astype(jnp.uint8),
         sci & (ne_dig == 3))
    emit(es + (ne_dig == 3), (eabs // 10 % 10 + ord("0")).astype(jnp.uint8),
         sci & (ne_dig >= 2))
    emit(es + ne_dig - 1, (eabs % 10 + ord("0")).astype(jnp.uint8), sci)

    # specials
    for text, mask in (("NaN", is_nan), ("Infinity", is_inf)):
        base = jnp.where(mask & sign & ~is_nan, 1, 0)
        for t, ch in enumerate(text):
            emit(base + t, ord(ch), mask)

    idx = jnp.stack(idx_list, axis=1)           # (n, S)
    vals = jnp.stack(val_list, axis=1)          # (n, S)
    mat = jnp.zeros((n, _PAD + 1), jnp.uint8)
    mat = mat.at[rows[:, None], idx].set(vals, mode="drop")
    mat = mat[:, :_PAD]

    # lengths
    frac = jnp.where(olength > ip, olength - ip, 1)
    len_pge = s + ip + 1 + frac
    len_plt = s + 2 + zneg + olength
    len_sci = s + m + 2 + eneg.astype(jnp.int32) + ne_dig
    length = jnp.where(pge, len_pge, jnp.where(plt, len_plt, len_sci))
    length = jnp.where(is_nan, 3, length)
    length = jnp.where(is_inf, 8 + sign.astype(jnp.int32), length)
    return mat, length


def float_bits(data: jnp.ndarray) -> jnp.ndarray:
    """Bit pattern of a float array as uint64.

    The TPU X64 emulation pass does not implement bitcast-convert *from*
    64-bit floats (u32->f64 works, f64->u64 does not), so off-CPU the f64
    view is taken host-side; float32 bitcasts are native everywhere.
    """
    if data.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(data, jnp.uint32).astype(jnp.uint64)
    if jax.default_backend() == "cpu":
        return jax.lax.bitcast_convert_type(data, jnp.uint64)
    return jnp.asarray(np.asarray(data).view(np.uint64))


@jax.jit
def _float32_to_chars(bits):
    return _format_java(*_ryu_f32(bits))


@jax.jit
def _float64_to_chars(bits):
    return _format_java(*_ryu_f64(bits))


def float_to_string(column: Column) -> Column:
    """FLOAT32/FLOAT64 column -> STRING column, Java toString text
    (spark_rapids_jni::float_to_string, cast_float_to_string.cu:119)."""
    if column.dtype.kind == dtypes.Kind.FLOAT32:
        mat, length = _float32_to_chars(float_bits(column.data))
    elif column.dtype.kind == dtypes.Kind.FLOAT64:
        mat, length = _float64_to_chars(float_bits(column.data))
    else:
        raise TypeError(f"expected a float column, got {column.dtype}")
    if column.validity is not None:
        length = jnp.where(column.validity, length, 0)
    return strings_from_padded(mat, length, column.validity)
