"""DECIMAL128 arithmetic with 256-bit intermediates and Spark-exact rounding.

Re-design of the reference's decimal_utils.cu (dec128_add_sub :561,
dec128_multiplier :657, dec128_divider :744, dec128_remainder :854) for the
XLA substrate. Each op returns (overflow bool column, result decimal128
column) exactly like the Java facade's Table {overflow, result}
(DecimalUtils.java:46-178).

Scales here are SPARK scales (>= 0, digits right of the point); the cudf
convention in the reference is the negation. `cast_interim_result` preserves
the deliberately bug-compatible Spark < 3.4.2 multiply that first rounds the
256-bit product to 38 digits (DecimalUtils.java:33-37, SPARK-40129).
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from .. import dtypes
from ..columnar import Column
from . import decimal256 as d256


def _limbs(col: Column) -> jnp.ndarray:
    assert col.dtype.kind == dtypes.Kind.DECIMAL128, col.dtype
    return d256.from_i128_limbs(col.data)


def _result(cols_valid, limbs, overflow, precision, scale) -> Tuple[Column, Column]:
    n = limbs.shape[0]
    ovf = Column(dtype=dtypes.BOOL, length=n, data=overflow,
                 validity=cols_valid)
    res = Column(dtype=dtypes.DType(dtypes.Kind.DECIMAL128,
                                    precision=precision, scale=scale),
                 length=n, data=d256.to_i128_limbs(limbs), validity=cols_valid)
    return ovf, res


def _combined_validity(a: Column, b: Column):
    if a.validity is None and b.validity is None:
        return None
    return a.null_mask & b.null_mask


def _set_scale_and_round(data, old_scale, new_scale):
    """cudf-scale change (decimal_utils.cu:544-558): lowering the scale
    multiplies, raising divides with HALF_UP."""
    if old_scale == new_scale:
        return data
    if new_scale < old_scale:
        mul = d256.pow_ten(jnp.full(data.shape[:1], old_scale - new_scale))
        return d256.multiply(data, mul)
    div = d256.pow_ten(jnp.full(data.shape[:1], new_scale - old_scale))
    return d256.divide_and_round(data, div)


def add_decimal128(a: Column, b: Column, target_scale: int,
                   is_sub: bool = False) -> Tuple[Column, Column]:
    """dec128_add / dec128_sub (decimal_utils.cu:561-654): rescale both to
    min cudf-scale, add/sub in 256 bits, rescale to target, flag >38-digit
    results."""
    av, bv = _limbs(a), _limbs(b)
    a_scale, b_scale = -a.dtype.scale, -b.dtype.scale
    result_scale = -target_scale
    inter = min(a_scale, b_scale)
    av = _set_scale_and_round(av, a_scale, inter)
    bv = _set_scale_and_round(bv, b_scale, inter)
    if is_sub:
        bv = d256.negate(bv)
    s = d256.add(av, bv)
    s = _set_scale_and_round(s, inter, result_scale)
    overflow = d256.is_greater_than_decimal_38(s)
    return _result(_combined_validity(a, b), s, overflow, 38, target_scale)


def sub_decimal128(a: Column, b: Column, target_scale: int):
    return add_decimal128(a, b, target_scale, is_sub=True)


def multiply_decimal128(a: Column, b: Column, product_scale: int,
                        cast_interim_result: bool = True):
    """dec128_multiplier (decimal_utils.cu:657-741)."""
    av, bv = _limbs(a), _limbs(b)
    n = av.shape[0]
    a_scale, b_scale = -a.dtype.scale, -b.dtype.scale
    prod_scale = -product_scale

    product = d256.multiply(av, bv)
    mult_scale = jnp.full((n,), a_scale + b_scale, jnp.int32)
    if cast_interim_result:
        # Spark < 3.4.2 first rounds the unbounded product to 38 digits
        # (SPARK-40129 bug compatibility, decimal_utils.cu:679-697)
        first_div_precision = d256.precision10(product) - 38
        needs = first_div_precision > 0
        div = d256.pow_ten(jnp.maximum(first_div_precision, 0))
        rounded = d256.divide_and_round(product, div)
        product = jnp.where(needs[:, None], rounded, product)
        mult_scale = mult_scale + jnp.where(needs, first_div_precision, 0)

    exponent = prod_scale - mult_scale
    # exponent < 0: multiply up unless that pushes precision past 38
    new_precision = d256.precision10(product)
    mul_overflow = (exponent < 0) & (new_precision - exponent > 38)
    scaled_up = d256.multiply(product, d256.pow_ten(jnp.maximum(-exponent, 0)))
    # exponent >= 0: divide_and_round down to target scale
    scaled_down = d256.divide_and_round(product,
                                        d256.pow_ten(jnp.maximum(exponent, 0)))
    result = jnp.where((exponent < 0)[:, None], scaled_up,
                       jnp.where((exponent > 0)[:, None], scaled_down, product))
    overflow = mul_overflow | d256.is_greater_than_decimal_38(result)
    return _result(_combined_validity(a, b), result, overflow, 38, product_scale)


def divide_decimal128(a: Column, b: Column, quotient_scale: int,
                      is_int_div: bool = False):
    """dec128_divider (decimal_utils.cu:744-851). is_int_div returns the
    integer quotient as DECIMAL with DOWN rounding (scale 0 output in the
    Java facade's integerDivide128)."""
    av, bv = _limbs(a), _limbs(b)
    n = av.shape[0]
    a_scale, b_scale = -a.dtype.scale, -b.dtype.scale
    quot_scale = -quotient_scale

    div_by_zero = d256.is_zero(bv)
    safe_d = jnp.where(div_by_zero[:, None],
                       d256.from_int([1]).repeat(n, axis=0), bv)

    n_shift_exp = quot_scale - (a_scale - b_scale)

    if n_shift_exp > 0:
        # divide twice: regular divide, then scale divide with rounding
        q1, _ = d256.divide(av, safe_d)
        scale_div = d256.pow_ten(jnp.full((n,), n_shift_exp))
        if is_int_div:
            result = d256.integer_divide(q1, scale_div)
        else:
            result = d256.divide_and_round(q1, scale_div)
    elif n_shift_exp < -38:
        # multiply by 10^38, divide, then handle the remaining shift on both
        # quotient and remainder (long division base 10^38,
        # decimal_utils.cu:795-826)
        num = d256.multiply(av, d256.pow_ten(jnp.full((n,), 38)))
        q1, r1 = d256.divide(num, safe_d)
        remaining = -n_shift_exp - 38
        scale_mult = d256.pow_ten(jnp.full((n,), remaining))
        result = d256.multiply(q1, scale_mult)
        scaled_r = d256.multiply(r1, scale_mult)
        q2, r2 = d256.divide(scaled_r, safe_d)
        result = d256.add(result, q2)
        if not is_int_div:
            result = d256.round_from_remainder(result, r2, safe_d)
    else:
        num = av if n_shift_exp == 0 else d256.multiply(
            av, d256.pow_ten(jnp.full((n,), -n_shift_exp)))
        if is_int_div:
            result = d256.integer_divide(num, safe_d)
        else:
            result = d256.divide_and_round(num, safe_d)

    result = jnp.where(div_by_zero[:, None], jnp.zeros_like(result), result)
    overflow = div_by_zero | d256.is_greater_than_decimal_38(result)
    if is_int_div:
        # integerDivide128 returns the low 64 bits as LONG; overflow is
        # still judged on the 128-bit value (DecimalUtilsTest.java:221-236)
        lo64 = (result[:, 0] | (result[:, 1] << jnp.uint64(32))).astype(jnp.int64)
        valid = _combined_validity(a, b)
        ovf = Column(dtype=dtypes.BOOL, length=n, data=overflow, validity=valid)
        res = Column(dtype=dtypes.INT64, length=n, data=lo64, validity=valid)
        return ovf, res
    return _result(_combined_validity(a, b), result, overflow, 38,
                   quotient_scale)


def remainder_decimal128(a: Column, b: Column, remainder_scale: int):
    """dec128_remainder (decimal_utils.cu:854-971): Java semantics
    a % b = a - (a // b) * b, sign follows the dividend."""
    av, bv = _limbs(a), _limbs(b)
    n = av.shape[0]
    a_scale, b_scale = -a.dtype.scale, -b.dtype.scale
    rem_scale = -remainder_scale

    div_by_zero = d256.is_zero(bv)
    safe_b = jnp.where(div_by_zero[:, None],
                       d256.from_int([1]).repeat(n, axis=0), bv)

    abs_n, n_neg = d256.abs_(av)
    abs_d, _ = d256.abs_(safe_b)

    d_shift_exp = rem_scale - b_scale
    n_shift_exp = rem_scale - a_scale
    if d_shift_exp > 0:
        abs_d = d256.divide_and_round(
            abs_d, d256.pow_ten(jnp.full((n,), d_shift_exp)))
    else:
        n_shift_exp -= d_shift_exp

    if n_shift_exp > 0:
        q1, _ = d256.divide(abs_n, abs_d)
        int_div = d256.integer_divide(
            q1, d256.pow_ten(jnp.full((n,), n_shift_exp)))
    else:
        if n_shift_exp < 0:
            abs_n = d256.multiply(
                abs_n, d256.pow_ten(jnp.full((n,), -n_shift_exp)))
        int_div = d256.integer_divide(abs_n, abs_d)

    less_n = d256.multiply(int_div, abs_d)
    if d_shift_exp < 0:
        less_n = d256.multiply(less_n, d256.pow_ten(jnp.full((n,), -d_shift_exp)))
    rem = d256.add(abs_n, d256.negate(less_n))
    overflow = div_by_zero | d256.is_greater_than_decimal_38(rem)
    rem = jnp.where(n_neg[:, None], d256.negate(rem), rem)
    rem = jnp.where(div_by_zero[:, None], jnp.zeros_like(rem), rem)
    return _result(_combined_validity(a, b), rem, overflow, 38,
                   remainder_scale)
