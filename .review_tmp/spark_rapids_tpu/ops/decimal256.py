"""256-bit integer limb arithmetic for DECIMAL128 kernels, TPU-vectorized.

Equivalent of the reference's `chunked256` device struct
(decimal_utils.cu:32-119) re-designed for XLA: a 256-bit value is a (n, 8)
uint64 array of 32-bit limbs, little-endian (limb j holds bits [32j, 32j+32)).
32-bit limbs keep every intermediate product/carry within uint64, which the
TPU emulates exactly; all ops are dense vector ops over the row axis.

The divide is the reference's binary long division (decimal_utils.cu:149-168)
expressed as a 256-iteration `fori_loop` — the loop body compiles once, and
every row advances in lockstep (SIMD over rows instead of one thread per row).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NLIMBS = 8
_M32 = jnp.uint64(0xFFFFFFFF)


def _from_int_np(values) -> np.ndarray:
    out = np.zeros((len(values), NLIMBS), np.uint64)
    for i, v in enumerate(values):
        u = int(v) & ((1 << 256) - 1)
        for j in range(NLIMBS):
            out[i, j] = (u >> (32 * j)) & 0xFFFFFFFF
    return out


def from_int(values) -> jnp.ndarray:
    """Host helper: python ints -> (n, 8) limbs (two's complement)."""
    return jnp.asarray(_from_int_np(values))


def to_int(limbs) -> list:
    """Host helper: (n, 8) limbs -> python ints (signed 256-bit)."""
    arr = np.asarray(limbs, dtype=np.uint64)
    out = []
    for row in arr:
        u = 0
        for j in range(NLIMBS):
            u |= int(row[j]) << (32 * j)
        if u >= (1 << 255):
            u -= (1 << 256)
        out.append(u)
    return out


def from_i128_limbs(limbs_u32: jnp.ndarray) -> jnp.ndarray:
    """Sign-extend a decimal128 column's (n, 4) uint32 limbs to (n, 8)."""
    lo = limbs_u32.astype(jnp.uint64)
    sign = (lo[:, 3] >> jnp.uint64(31)) & jnp.uint64(1)
    ext = jnp.where(sign[:, None] == 1, _M32, jnp.uint64(0))
    return jnp.concatenate([lo, jnp.broadcast_to(ext, lo.shape)], axis=1)


def to_i128_limbs(x: jnp.ndarray) -> jnp.ndarray:
    """Truncate (n, 8) -> (n, 4) uint32 (as_128_bits, decimal_utils.cu:110)."""
    return x[:, :4].astype(jnp.uint32)


def is_negative(x: jnp.ndarray) -> jnp.ndarray:
    return (x[:, 7] >> jnp.uint64(31)) != 0


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """256-bit add, wrap-around (chunked256::add)."""
    out = []
    carry = jnp.zeros(a.shape[:1], jnp.uint64)
    for j in range(NLIMBS):
        s = a[:, j] + b[:, j] + carry
        out.append(s & _M32)
        carry = s >> jnp.uint64(32)
    return jnp.stack(out, axis=1)


def add_small(a: jnp.ndarray, v) -> jnp.ndarray:
    """Add a per-row (or scalar) small non-negative uint64 (< 2^32)."""
    v = jnp.broadcast_to(jnp.asarray(v, jnp.uint64), a.shape[:1])
    out = []
    carry = v
    for j in range(NLIMBS):
        s = a[:, j] + carry
        out.append(s & _M32)
        carry = s >> jnp.uint64(32)
    return jnp.stack(out, axis=1)


def negate(a: jnp.ndarray) -> jnp.ndarray:
    """Two's-complement negate (chunked256::negate)."""
    return add_small(a ^ _M32, 1)


def abs_(a: jnp.ndarray):
    neg = is_negative(a)
    return jnp.where(neg[:, None], negate(a), a), neg


def lt_unsigned(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Unsigned a < b, lexicographic from the top limb."""
    lt = jnp.zeros(a.shape[:1], jnp.bool_)
    decided = jnp.zeros(a.shape[:1], jnp.bool_)
    for j in range(NLIMBS - 1, -1, -1):
        lt = jnp.where(~decided & (a[:, j] < b[:, j]), True, lt)
        decided = decided | (a[:, j] != b[:, j])
    return lt


def gte_unsigned(a, b):
    return ~lt_unsigned(a, b)


def eq(a, b):
    return jnp.all(a == b, axis=1)


def is_zero(a):
    return jnp.all(a == 0, axis=1)


def multiply(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """256x256 -> low 256 bits (reference multiply, decimal_utils.cu:127-147):
    outer loop over b limbs with a running carry keeps all intermediates
    within uint64."""
    n = a.shape[0]
    r = [jnp.zeros((n,), jnp.uint64) for _ in range(NLIMBS)]
    for bj in range(NLIMBS):
        carry = jnp.zeros((n,), jnp.uint64)
        for ai in range(NLIMBS - bj):
            t = a[:, ai] * b[:, bj] + r[ai + bj] + carry
            r[ai + bj] = t & _M32
            carry = t >> jnp.uint64(32)
    return jnp.stack(r, axis=1)


def mul_small(a: jnp.ndarray, v) -> jnp.ndarray:
    """Multiply by a small (< 2^32) scalar or per-row uint64."""
    v = jnp.asarray(v, jnp.uint64)
    out = []
    carry = jnp.zeros(a.shape[:1], jnp.uint64)
    for j in range(NLIMBS):
        t = a[:, j] * v + carry
        out.append(t & _M32)
        carry = t >> jnp.uint64(32)
    return jnp.stack(out, axis=1)


def shift_left1(a: jnp.ndarray) -> jnp.ndarray:
    """Left shift by one bit."""
    hi = a >> jnp.uint64(31)
    shifted = (a << jnp.uint64(1)) & _M32
    carry_in = jnp.concatenate(
        [jnp.zeros((a.shape[0], 1), jnp.uint64), hi[:, :-1]], axis=1)
    return shifted | carry_in


def sub_unsigned(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b (wrap-around), via a + (~b + 1)."""
    return add(a, negate(b))


# powers of ten 10^0 .. 10^76 as (77, 8) limb constants (pow_ten,
# decimal_utils.cu:678+ generated table - here computed directly)
_POW10_LIMBS = None


def pow10_table() -> jnp.ndarray:
    global _POW10_LIMBS
    if _POW10_LIMBS is None:
        # cached as a HOST array, built with pure numpy: caching a traced
        # jnp value would leak the tracer into later jit traces (and a cold
        # cache inside a trace could not be converted back to numpy)
        _POW10_LIMBS = _from_int_np([10**k for k in range(77)])
    return jnp.asarray(_POW10_LIMBS)


def pow_ten(k) -> jnp.ndarray:
    """10^k as (n, 8) limbs for integer array k (clipped to [0, 76])."""
    tbl = pow10_table()
    return jnp.take(tbl, jnp.clip(jnp.asarray(k), 0, 76), axis=0)


def precision10(value: jnp.ndarray) -> jnp.ndarray:
    """First i with 10^i >= |value| (reference precision10,
    decimal_utils.cu:520-535). value may be negative."""
    a, _ = abs_(value)
    tbl = pow10_table()
    # count of i in [0, 76] with 10^i < value == index of first >=
    cnt = jnp.zeros(value.shape[:1], jnp.int32)
    for i in range(77):
        b = jnp.broadcast_to(tbl[i][None, :], a.shape)
        cnt = cnt + lt_unsigned(b, a).astype(jnp.int32)
    return cnt


def is_greater_than_decimal_38(a: jnp.ndarray) -> jnp.ndarray:
    """|a| >= 10^38 -> precision-38 overflow (decimal_utils.cu:537-542)."""
    mag, _ = abs_(a)
    p38 = jnp.broadcast_to(pow10_table()[38][None, :], mag.shape)
    return gte_unsigned(mag, p38)


def divide_unsigned(n: jnp.ndarray, d: jnp.ndarray):
    """Binary long division of unsigned 256-bit n by unsigned d
    (reference divide_unsigned, decimal_utils.cu:149-168).

    Returns (quotient (n,8), remainder (n,8)). d must be nonzero (callers
    pre-check and flag overflow, decimal_utils.cu:764-768)."""
    rows = n.shape[0]
    q0 = jnp.zeros((rows, NLIMBS), jnp.uint64)
    r0 = jnp.zeros((rows, NLIMBS), jnp.uint64)

    def body(it, carry):
        q, r = carry
        i = 255 - it
        block = i // 32
        bit = i % 32
        limb = jax.lax.dynamic_slice_in_dim(n, block, 1, axis=1)[:, 0]
        read = (limb >> jnp.uint64(bit)) & jnp.uint64(1)
        r = shift_left1(r)
        r = r.at[:, 0].set(r[:, 0] | read)
        ge = gte_unsigned(r, d)
        r = jnp.where(ge[:, None], sub_unsigned(r, d), r)
        qlimb = jax.lax.dynamic_slice_in_dim(q, block, 1, axis=1)[:, 0]
        qlimb = jnp.where(ge, qlimb | (jnp.uint64(1) << jnp.uint64(bit)), qlimb)
        q = jax.lax.dynamic_update_slice_in_dim(q, qlimb[:, None], block, axis=1)
        return q, r

    q, r = jax.lax.fori_loop(0, 256, body, (q0, r0))
    return q, r


def divide(n: jnp.ndarray, d: jnp.ndarray):
    """Signed divide (reference divide, decimal_utils.cu:170-191):
    quotient sign = n_sign ^ d_sign, remainder takes n's sign.
    Returns (quotient, remainder) as signed 256-bit limb arrays."""
    abs_n, n_neg = abs_(n)
    abs_d, d_neg = abs_(d)
    q, r = divide_unsigned(abs_n, abs_d)
    q = jnp.where((n_neg ^ d_neg)[:, None], negate(q), q)
    r = jnp.where(n_neg[:, None], negate(r), r)
    return q, r


def round_from_remainder(q, r, d):
    """HALF_UP rounding from a remainder (decimal_utils.cu:193-224):
    increment |q| by one (away from zero, direction = sign(n)^sign(d),
    which is the sign the quotient would have) when 2|r| >= |d|."""
    abs_r, r_neg = abs_(r)
    abs_d, d_neg = abs_(d)
    dbl = shift_left1(abs_r)
    need_inc = gte_unsigned(dbl, abs_d)
    # r carries n's sign; round away from zero in the quotient's direction
    round_down = r_neg ^ d_neg
    inc = jnp.where(need_inc, jnp.where(round_down, -1, 1), 0)
    neg_one = jnp.full_like(q, _M32)
    q_inc = jnp.where(inc[:, None] == 1, add_small(q, 1),
                      jnp.where(inc[:, None] == -1, add(q, neg_one), q))
    return q_inc


def divide_and_round(n, d):
    """divide + HALF_UP (decimal_utils.cu:226-233)."""
    q, r = divide(n, d)
    return round_from_remainder(q, r, d)


def integer_divide(n, d):
    """divide, drop remainder (Java DOWN rounding; decimal_utils.cu:235-244)."""
    q, _ = divide(n, d)
    return q
