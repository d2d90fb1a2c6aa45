"""DECIMAL32/64/128 → STRING with Spark's non-ANSI formatting.

TPU-native re-design of the reference kernel
(src/main/cpp/src/cast_decimal_to_string.cu:53-175): follows Java
BigDecimal.toString() — plain `[-]integer.fraction` when java-scale >= 0 and
adjusted exponent >= -6, scientific `d.dddE±x` otherwise.

Where the reference runs a two-pass size/write functor per row, here the
digits of every row are extracted at once with a static unrolled divide-by-10
loop (limb-wise long division for DECIMAL128 — no native int128 on TPU), and
the output is assembled positionally over an (n, width) char plane, then
compacted with the standard measure→gather strings pattern.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..columnar import Column, strings_from_padded
from ..dtypes import Kind

_MINUS = jnp.uint8(ord("-"))
_POINT = jnp.uint8(ord("."))
_E = jnp.uint8(ord("E"))
_PLUS = jnp.uint8(ord("+"))
_ZERO = jnp.uint8(ord("0"))


def _digits_dec128(limbs: jnp.ndarray, ndigits: int):
    """(n,4) uint32 two's-complement limbs -> (neg, (n,D) uint8 digits MSB-first)."""
    neg = (limbs[:, 3] >> jnp.uint32(31)) != 0
    # two's complement negate: ~x + 1 limb-wise with carry
    inv = (~limbs).astype(jnp.uint32)
    carry = jnp.ones_like(inv[:, 0])
    abs_limbs = []
    for i in range(4):
        s = inv[:, i].astype(jnp.uint64) + carry.astype(jnp.uint64)
        abs_limbs.append((s & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
        carry = (s >> jnp.uint64(32)).astype(jnp.uint32)
    abs_l = jnp.where(neg[:, None], jnp.stack(abs_limbs, axis=1), limbs)

    digs = []
    cur = [abs_l[:, i].astype(jnp.uint64) for i in range(4)]
    for _ in range(ndigits):
        r = jnp.zeros_like(cur[0])
        new = [None] * 4
        for i in (3, 2, 1, 0):              # long division by 10, high→low limb
            acc = (r << jnp.uint64(32)) | cur[i]
            new[i] = acc // jnp.uint64(10)
            r = acc % jnp.uint64(10)
        cur = new
        digs.append(r.astype(jnp.uint8))
    # digs is LSB-first; flip to MSB-first
    return neg, jnp.stack(digs[::-1], axis=1)


def _digits_fixed(data: jnp.ndarray, ndigits: int):
    """(n,) int32/int64 -> (neg, (n,D) uint8 digits MSB-first)."""
    neg = data < 0
    mag = jnp.abs(data.astype(jnp.int64)).astype(jnp.uint64)
    digs = []
    for _ in range(ndigits):
        digs.append((mag % jnp.uint64(10)).astype(jnp.uint8))
        mag = mag // jnp.uint64(10)
    return neg, jnp.stack(digs[::-1], axis=1)


def decimal_to_non_ansi_string(col: Column) -> Column:
    """Spark non-ANSI decimal formatting (cast_decimal_to_string.cu:210)."""
    if not col.dtype.is_decimal:
        raise TypeError(
            "Values for decimal_to_non_ansi_string function must be a decimal type.")
    n = col.length
    s = int(col.dtype.scale or 0)            # java scale; fraction digits if > 0
    D = {Kind.DECIMAL32: 10, Kind.DECIMAL64: 19, Kind.DECIMAL128: 39}[col.dtype.kind]
    if col.dtype.kind == Kind.DECIMAL128:
        neg, dig = _digits_dec128(col.data, D)
    else:
        neg, dig = _digits_fixed(col.data, D)

    # significant digit count of |v| (count_digits(0) == 1)
    nz = dig != 0
    first_nz = jnp.argmax(nz, axis=1)                         # D if all zero → 0
    any_nz = jnp.any(nz, axis=1)
    ndig = jnp.where(any_nz, D - first_nz, 1).astype(jnp.int32)
    adjusted = ndig - 1 - s                                   # adjusted exponent

    plain = jnp.logical_and(s >= 0, adjusted >= -6)

    # ---- plain layout: [-] int . frac ------------------------------------------
    int_len = jnp.maximum(ndig - s, 1)                        # "0" when |v| < 10^s
    has_pt = jnp.int32(1 if s > 0 else 0)
    p_len = neg.astype(jnp.int32) + int_len + has_pt + (s if s > 0 else 0)

    # ---- scientific layout: [-] d [. rest] E sign exp --------------------------
    exp_abs = jnp.abs(adjusted)
    exp_ndig = jnp.where(exp_abs >= 100, 3, jnp.where(exp_abs >= 10, 2, 1))
    multi = ndig > 1
    s_len = (neg.astype(jnp.int32) + 1 + jnp.where(multi, 1 + (ndig - 1), 0)
             + 1 + 1 + exp_ndig)

    length = jnp.where(plain, p_len, s_len)
    W = 1 + max(D, s + 1) + 1 + (s if s > 0 else 0) + 6       # static width bound
    j = jnp.arange(W, dtype=jnp.int32)[None, :]               # (1, W)

    def dig_at(idx):
        """Row-wise gather dig[row, idx] with clipping; idx (n, W)."""
        return jnp.take_along_axis(dig, jnp.clip(idx, 0, D - 1), axis=1) + _ZERO

    negi = neg.astype(jnp.int32)[:, None]
    ndigc = ndig[:, None]
    int_lenc = int_len[:, None]

    # plain characters
    b0 = negi                      # end of sign
    b1 = b0 + int_lenc            # end of integer part
    b2 = b1 + has_pt              # end of point
    # integer digits: dig columns [D-s-int_len, D-s); when |v|<10^s that
    # window starts at a zero digit, giving the required "0"
    p_char = jnp.where(
        j < b0, _MINUS,
        jnp.where(j < b1, dig_at(D - s - int_lenc + (j - b0)),
                  jnp.where((j < b2) & (has_pt > 0), _POINT,
                            dig_at(D - s + (j - b2)))))

    # scientific characters
    exp_dig = jnp.stack([(exp_abs // 100) % 10, (exp_abs // 10) % 10,
                         exp_abs % 10], axis=1).astype(jnp.uint8)
    exp_ndigc = exp_ndig[:, None]
    c0 = negi                       # sign end
    c1 = c0 + 1                     # first digit end
    c2 = c1 + jnp.where(multi, 1, 0)[:, None]      # point end
    c3 = c2 + jnp.where(multi[:, None], ndigc - 1, 0)   # frac end
    c4 = c3 + 1                     # E end
    c5 = c4 + 1                     # exp sign end
    exp_at = jnp.take_along_axis(
        exp_dig, jnp.clip(3 - exp_ndigc + (j - c5), 0, 2), axis=1) + _ZERO
    s_char = jnp.where(
        j < c0, _MINUS,
        jnp.where(j < c1, dig_at(D - ndigc + (j - c0)),
                  jnp.where(j < c2, _POINT,
                            jnp.where(j < c3, dig_at(D - ndigc + 1 + (j - c2)),
                                      jnp.where(j < c4, _E,
                                                jnp.where(j < c5,
                                                          jnp.where(adjusted[:, None] >= 0,
                                                                    _PLUS, _MINUS),
                                                          exp_at))))))

    chars = jnp.where(plain[:, None], p_char, s_char)
    in_row = j < length[:, None]
    chars = jnp.where(in_row & col.null_mask[:, None], chars, jnp.uint8(0))
    length = jnp.where(col.null_mask, length, 0)
    return strings_from_padded(chars, length, validity=col.validity)
