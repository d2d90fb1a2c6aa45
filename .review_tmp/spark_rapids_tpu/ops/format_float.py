"""format_number(x, d) — Spark's "#,###,###.##" float formatting.

Reference: /root/reference/src/main/cpp/src/format_float.cu (format_float_fn
:35) and ftos_converter.cuh's format half (:1174-1440): format the Ryu
*shortest* decimal digits (not the exact binary expansion) with half-even
rounding to `d` fraction digits (round_half_even :1195), comma thousands
grouping, and Java DecimalFormat specials — NaN -> U+FFFD replacement char,
+/-Infinity -> U+221E, zero -> "0.00…0" (golden vectors in
tests/format_float.cpp: format_float(123456789012.34f, 5) ->
"123,456,790,000.00000").

TPU-native design: a measure pass (jitted) computes each row's length from
the rounded digit count; the host takes the max to size a static-width char
grid; the format pass fills the grid with pure position arithmetic — for
every (row, char-position) pair it decides sign/comma/digit/point/zero in
vector math. That handles the 300+-digit integer parts of 1e300-scale values
without per-digit scatter lists.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes
from ..columnar.column import Column, strings_from_padded
from ..columnar.column import _round_bucket
from .cast_float_to_string import (_ryu_f32, _ryu_f64, _u, _POW10_U64,
                                   _decimal_length, float_bits)

_MAX_DIGITS_PARAM = 30


def _round_half_even(v, olength, keep):
    """Keep `keep` leading decimal digits of v (olength digits total),
    half-even (ftos_converter.cuh round_half_even :1195)."""
    p10 = jnp.asarray(_POW10_U64)
    div = p10[jnp.clip(olength - keep, 0, 19)]
    mod = v % div
    num = v // div
    up = (mod * _u(2) > div) | ((mod * _u(2) == div) & (num % _u(2) == 1) & (mod != 0))
    return num + up.astype(jnp.uint64)


def _format_plan(digits_frac: int, D, exp10, olength, sign, is_nan, is_inf,
                 is_zero):
    """Per-row formatting parameters shared by measure and fill passes.

    Returns a dict of vectors: int-part digit source (value V, left-shift S,
    digit count IL), fraction source, carry flag, and total length.
    """
    d = digits_frac
    special = is_nan | is_inf | is_zero
    exp = exp10
    p10 = jnp.asarray(_POW10_U64)

    br_a = (~special) & (exp < 0)
    br_b = (~special) & (exp >= 0) & (exp + 1 >= olength)
    br_c = (~special) & (exp >= 0) & (exp + 1 < olength)

    # --- branch A: value < 1 -----------------------------------------------
    neg_exp = jnp.maximum(-exp - 1, 0)            # zeros between point & digits
    z = jnp.minimum(neg_exp, d)
    proceed = d >= neg_exp
    actual_round = jnp.maximum(d - neg_exp, 0)
    actual_olength = jnp.minimum(olength, actual_round)
    rounded_a = _round_half_even(D, olength, actual_round)
    carry_a = proceed & (rounded_a >= p10[jnp.clip(actual_olength, 0, 19)])
    rounded_a = jnp.where(carry_a,
                          rounded_a - p10[jnp.clip(actual_olength, 0, 19)],
                          rounded_a)
    rounded_a = jnp.where(proceed, rounded_a, _u(0))
    a_width = jnp.where(proceed, actual_olength, 0)

    # --- branch C: point inside the digits ---------------------------------
    over = exp + d + 1 > olength
    temp_d = jnp.where(over, olength - exp - 1, d)
    rounded_c = _round_half_even(D, olength, exp + temp_d + 1)
    pw = p10[jnp.clip(temp_d, 0, 19)]
    integer_c = rounded_c // pw
    decimal_c = rounded_c % pw
    int_len_c = _decimal_length(integer_c)

    # --- unified integer-part source ---------------------------------------
    # int digits (incl. trailing zeros) = gather from V at (k - S) from right
    V = jnp.where(br_b, D, jnp.where(br_c, integer_c,
                                     jnp.where(carry_a & (z == 0), _u(1), _u(0))))
    S = jnp.where(br_b, exp + 1 - olength, 0)
    IL = jnp.where(br_b, exp + 1, jnp.where(br_c, int_len_c, 1))
    IL_chars = IL + (IL - 1) // 3

    # --- unified fraction source -------------------------------------------
    frac_lead = jnp.where(br_a, z, 0)             # leading zeros ('1' if carry)
    F = jnp.where(br_a, rounded_a, jnp.where(br_c, decimal_c, _u(0)))
    F_width = jnp.where(br_a, a_width, jnp.where(br_c, temp_d, 0))
    carry_in_lead = br_a & carry_a & (z > 0)
    # carry with z == 0 lands in the integer part (V above)

    s = sign.astype(jnp.int32)
    length = s + IL_chars + (1 + d if d > 0 else 0)
    length = jnp.where(is_zero, s + (2 + d if d > 0 else 1), length)
    length = jnp.where(is_inf, s + 3, length)
    length = jnp.where(is_nan, 3, length)
    return dict(V=V, S=S, IL=IL, IL_chars=IL_chars, F=F, F_width=F_width,
                frac_lead=frac_lead, carry_in_lead=carry_in_lead, s=s,
                length=length, is_nan=is_nan, is_inf=is_inf, is_zero=is_zero,
                sign=sign, special=special)


def _digit_at(v, k):
    """k-th decimal digit (from the right) of uint64 v; 0 beyond 19."""
    p10 = jnp.asarray(_POW10_U64)
    d = (v // p10[jnp.clip(k, 0, 19)]) % _u(10)
    return jnp.where((k < 0) | (k > 19), _u(0), d).astype(jnp.uint8)


@partial(jax.jit, static_argnames=("digits_frac", "is32"))
def _plan_pass(bits, *, digits_frac, is32):
    """Ryu + format plan, run once; _fill reuses the result as traced input."""
    ryu = _ryu_f32(bits) if is32 else _ryu_f64(bits)
    return _format_plan(digits_frac, *ryu)


@partial(jax.jit, static_argnames=("digits_frac", "width"))
def _fill(plan, *, digits_frac, width):
    d = digits_frac
    n = plan["s"].shape[0]
    W = width
    pos = jnp.arange(W, dtype=jnp.int32)[None, :]          # (1, W)

    s = plan["s"][:, None]
    IL = plan["IL"][:, None]
    IL_chars = plan["IL_chars"][:, None]
    V = plan["V"][:, None]
    S = plan["S"][:, None]

    out = jnp.full((n, W), ord(" "), jnp.uint8)

    # integer region [s, s + IL_chars): commas every 4th slot from the right
    in_int = (pos >= s) & (pos < s + IL_chars) & ~plan["special"][:, None]
    r = IL_chars - 1 - (pos - s)                  # 0-based from the right
    is_comma = (r % 4 == 3)
    digit_idx = r - (r + 1) // 4                  # digit number from right
    int_digit = _digit_at(V, digit_idx - S) + ord("0")
    int_char = jnp.where(is_comma, ord(","), int_digit)
    out = jnp.where(in_int, int_char.astype(jnp.uint8), out)

    if d > 0:
        # point + fraction region
        point_pos = s + IL_chars
        out = jnp.where((pos == point_pos) & ~plan["special"][:, None],
                        jnp.uint8(ord(".")), out)
        f = pos - point_pos - 1                   # 0-based fraction index
        in_frac = (f >= 0) & (f < d) & ~plan["special"][:, None]
        lead = plan["frac_lead"][:, None]
        Fw = plan["F_width"][:, None]
        F = plan["F"][:, None]
        frac_digit = jnp.where(
            f < lead,
            jnp.where(plan["carry_in_lead"][:, None] & (f == lead - 1), 1, 0),
            jnp.where(f < lead + Fw, _digit_at(F, lead + Fw - 1 - f), 0))
        out = jnp.where(in_frac, (frac_digit + ord("0")).astype(jnp.uint8), out)

    # sign
    neg = plan["sign"][:, None] & ~plan["is_nan"][:, None]
    out = jnp.where((pos == 0) & neg, jnp.uint8(ord("-")), out)

    # zero: [sign]0[.000…]
    zr = plan["is_zero"][:, None]
    out = jnp.where(zr & (pos == s), jnp.uint8(ord("0")), out)
    if d > 0:
        out = jnp.where(zr & (pos == s + 1), jnp.uint8(ord(".")), out)
        out = jnp.where(zr & (pos >= s + 2) & (pos < s + 2 + d),
                        jnp.uint8(ord("0")), out)

    # NaN -> U+FFFD, Infinity -> U+221E (3 UTF-8 bytes each)
    for i, b in enumerate(b"\xef\xbf\xbd"):
        out = jnp.where(plan["is_nan"][:, None] & (pos == i), jnp.uint8(b), out)
    for i, b in enumerate(b"\xe2\x88\x9e"):
        out = jnp.where(plan["is_inf"][:, None] & (pos == s + i),
                        jnp.uint8(b), out)

    return out, plan["length"]


def format_float(column: Column, digits: int) -> Column:
    """FLOAT32/FLOAT64 -> STRING with Spark format_number semantics
    (spark_rapids_jni::format_float, format_float.cu:119)."""
    if not 0 <= digits <= _MAX_DIGITS_PARAM:
        raise ValueError(f"digits must be in [0, {_MAX_DIGITS_PARAM}]")
    is32 = column.dtype.kind == dtypes.Kind.FLOAT32
    if not is32 and column.dtype.kind != dtypes.Kind.FLOAT64:
        raise TypeError(f"format_float expects a float column, got {column.dtype}")
    bits = float_bits(column.data)
    plan = _plan_pass(bits, digits_frac=digits, is32=is32)
    lengths = plan["length"]
    if column.validity is not None:
        lengths = jnp.where(column.validity, lengths, 0)
    max_len = int(jnp.max(lengths)) if column.length else 0
    width = _round_bucket(max(1, max_len))  # pow2 buckets bound recompiles
    mat, length = _fill(plan, digits_frac=digits, width=width)
    if column.validity is not None:
        length = jnp.where(column.validity, length, 0)
    return strings_from_padded(mat, length, column.validity)
