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

The second half of the file is what plans reach (docs/plan.md "Typed
expressions"): Spark's result types (`DecimalPrecision`, Spark 3.5,
`allowPrecisionLoss=true`, non-ANSI), `arithmetic` for a typed `+ - *`,
and `finish_sum` / `finish_mean` for the group-by's decimal aggregates.
They lower to the kernels above and to nothing else; every kernel runs
under a `decimal.<op>` scope (mul, add, sub, rescale, div, sum) that a
capped program's `device_op_owners(nested=True)` reads back.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import dtypes
from ..columnar import Column
from ..dtypes import Kind
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
    with jax.named_scope("decimal.rescale"):
        if new_scale < old_scale:
            mul = d256.pow_ten(jnp.full(data.shape[:1],
                                        old_scale - new_scale))
            return d256.multiply(data, mul)
        div = d256.pow_ten(jnp.full(data.shape[:1], new_scale - old_scale))
        return d256.divide_and_round(data, div)


def add_decimal128(a: Column, b: Column, target_scale: int,
                   is_sub: bool = False) -> Tuple[Column, Column]:
    """dec128_add / dec128_sub (decimal_utils.cu:561-654): rescale both to
    min cudf-scale, add/sub in 256 bits, rescale to target, flag >38-digit
    results."""
    a_scale, b_scale = -a.dtype.scale, -b.dtype.scale
    result_scale = -target_scale
    inter = min(a_scale, b_scale)
    with jax.named_scope("decimal.sub" if is_sub else "decimal.add"):
        av, bv = _limbs(a), _limbs(b)
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
    """dec128_multiplier (decimal_utils.cu:657-741). Without the interim
    cast (Spark 3.4.2+, what plan expressions use) the rescale's exponent
    is known when the program is traced, so only the one branch it takes
    is built: a product already at its scale costs the multiply and the
    38-digit check, no division."""
    with jax.named_scope("decimal.mul"):
        av, bv = _limbs(a), _limbs(b)
        n = av.shape[0]
        a_scale, b_scale = -a.dtype.scale, -b.dtype.scale
        prod_scale = -product_scale
        product = d256.multiply(av, bv)
        if not cast_interim_result:
            exponent = prod_scale - (a_scale + b_scale)
            result = _set_scale_and_round(product, a_scale + b_scale,
                                          prod_scale)
            overflow = d256.is_greater_than_decimal_38(result)
            if exponent < 0:
                # multiplying up may wrap 256 bits: judge it beforehand
                overflow = overflow | (
                    d256.precision10(product) - exponent > 38)
            return _result(_combined_validity(a, b), result, overflow, 38,
                           product_scale)
        mult_scale = jnp.full((n,), a_scale + b_scale, jnp.int32)
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
        scaled_up = d256.multiply(
            product, d256.pow_ten(jnp.maximum(-exponent, 0)))
        # exponent >= 0: divide_and_round down to target scale
        scaled_down = d256.divide_and_round(
            product, d256.pow_ten(jnp.maximum(exponent, 0)))
        result = jnp.where((exponent < 0)[:, None], scaled_up,
                           jnp.where((exponent > 0)[:, None], scaled_down,
                                     product))
        overflow = mul_overflow | d256.is_greater_than_decimal_38(result)
    return _result(_combined_validity(a, b), result, overflow, 38, product_scale)


def _scoped(name: str):
    """Run the whole kernel under one `jax.named_scope`."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*a, **kw):
            with jax.named_scope(name):
                return fn(*a, **kw)
        return inner
    return wrap


@_scoped("decimal.div")
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


@_scoped("decimal.div")
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


# ---- what plans reach: Spark's result types ---------------------------------
# DecimalType / DecimalPrecision of Spark 3.5 under
# spark.sql.decimalOperations.allowPrecisionLoss=true, non-ANSI (overflow
# gives null). Multiply is Spark 3.4.2+'s (no interim cast).

MAX_PRECISION = dtypes.MAX_DEC128_PRECISION
MIN_ADJUSTED_SCALE = 6
# an integral operand beside a decimal is cast to the decimal that holds
# every value of its type (DecimalType.forType)
_INTEGRAL_PRECISION = {Kind.INT8: 3, Kind.INT16: 5, Kind.INT32: 10,
                       Kind.INT64: 20}


def adjust_precision_scale(precision: int, scale: int) -> Tuple[int, int]:
    """DecimalType.adjustPrecisionScale: past 38 digits the integral part
    is kept and the scale gives way, down to min(scale, 6)."""
    if precision <= MAX_PRECISION:
        return precision, scale
    int_digits = precision - scale
    min_scale = min(scale, MIN_ADJUSTED_SCALE)
    return MAX_PRECISION, max(MAX_PRECISION - int_digits, min_scale)


def bounded(precision: int, scale: int) -> dtypes.DType:
    """DecimalType.bounded."""
    return dtypes.decimal(min(precision, MAX_PRECISION),
                          min(scale, MAX_PRECISION))


def as_decimal_type(dt: Optional[dtypes.DType]) -> Optional[dtypes.DType]:
    """The decimal type an operand of a decimal expression has: its own,
    or an integral type's `decimal(p, 0)`; None for anything else."""
    if dt is None:
        return None
    if dt.is_decimal:
        return dt
    p = _INTEGRAL_PRECISION.get(dt.kind)
    return None if p is None else dtypes.decimal(p, 0)


def literal_type(value: int) -> dtypes.DType:
    """An integer literal beside a decimal: `decimal(digits, 0)`."""
    return dtypes.decimal(max(1, len(str(abs(int(value))))), 0)


def arithmetic_type(op: str, lt: dtypes.DType, rt: dtypes.DType
                    ) -> dtypes.DType:
    """Result type of `lt op rt` for op in + - *."""
    p1, s1, p2, s2 = lt.precision, lt.scale, rt.precision, rt.scale
    if op in ("+", "-"):
        s = max(s1, s2)
        p = max(p1 - s1, p2 - s2) + s + 1
    elif op == "*":
        p, s = p1 + p2 + 1, s1 + s2
    else:
        raise ValueError(f"no decimal result type for {op!r}")
    return dtypes.decimal(*adjust_precision_scale(p, s))


def divide_type(lt: dtypes.DType, rt: dtypes.DType) -> dtypes.DType:
    s = max(MIN_ADJUSTED_SCALE, lt.scale + rt.precision + 1)
    p = lt.precision - lt.scale + rt.scale + s
    return dtypes.decimal(*adjust_precision_scale(p, s))


def sum_type(dt: dtypes.DType) -> dtypes.DType:
    """Sum.resultType: ten more digits, the same scale."""
    return bounded(dt.precision + 10, dt.scale)


COUNT_TYPE = dtypes.decimal(20, 0)      # DecimalType.LongDecimal


def average_types(dt: dtypes.DType):
    """Average: (the sum's type, the quotient's type by the divide rule,
    the result's type the quotient is then cast to, HALF_UP). Two
    roundings, which is how Spark 3.3 and earlier evaluate it
    (docs/plan.md)."""
    st = sum_type(dt)
    return st, divide_type(st, COUNT_TYPE), bounded(dt.precision + 4,
                                                    dt.scale + 4)


# ---- overflow rows of a request ----------------------------------------------

_collector = threading.local()


@contextlib.contextmanager
def overflow_counts():
    """Collect, as device scalars, how many live rows or groups each
    decimal kernel under this context nulled by overflow (the
    `decimal_overflow_rows` of `plan.execute`). -> the list they land in."""
    prev = getattr(_collector, "counts", None)
    _collector.counts = counts = []
    try:
        yield counts
    finally:
        _collector.counts = prev


def _note_overflow(nulled) -> None:
    counts = getattr(_collector, "counts", None)
    if counts is not None:
        counts.append(jnp.sum(nulled.astype(jnp.int64)))


# ---- columns in, columns out ---------------------------------------------------

def _i64_limbs(v: jnp.ndarray) -> jnp.ndarray:
    """(n,) int64 -> (n, 4) uint32 two's-complement limbs."""
    ext = (v >> 63).astype(jnp.uint32)
    return jnp.stack([v.astype(jnp.uint32), (v >> 32).astype(jnp.uint32),
                      ext, ext], axis=1)


def widen(col: Column) -> Column:
    """The column in DECIMAL128's layout under its own (precision, scale);
    an integral column as its `decimal(p, 0)`."""
    dt = as_decimal_type(col.dtype)
    if dt is None:
        raise TypeError(f"{col.dtype} is not an operand of decimal arithmetic")
    if col.dtype.kind == Kind.DECIMAL128:
        return col
    wide = dtypes.DType(Kind.DECIMAL128, precision=dt.precision, scale=dt.scale)
    return Column(dtype=wide, length=col.length,
                  data=_i64_limbs(col.data.astype(jnp.int64)),
                  validity=col.validity)


def _exceeds(limbs: jnp.ndarray, precision: int) -> jnp.ndarray:
    """|value| >= 10^precision over (n, 8) limbs."""
    mag, _ = d256.abs_(limbs)
    bound = jnp.broadcast_to(d256.pow10_table()[precision][None, :],
                             mag.shape)
    return d256.gte_unsigned(mag, bound)


def _null_overflow(col: Column, overflow, alive=None) -> Column:
    """Spark's non-ANSI overflow: the row is null, never wrapped."""
    live = col.null_mask if alive is None else col.null_mask & alive
    _note_overflow(overflow & live)
    return Column(dtype=col.dtype, length=col.length, data=col.data,
                  validity=col.null_mask & ~overflow)


def arithmetic(op: str, a: Column, b: Column, alive=None) -> Column:
    """`a op b` (op in + - *) under Spark's result type. Operands are
    decimal or integral columns. A result of at most 18 digits stays in
    int64 (exact: the unadjusted type holds every result); anything wider
    goes through the 256-bit limb kernels. `alive` only keeps padded rows
    out of the overflow count."""
    lt, rt = as_decimal_type(a.dtype), as_decimal_type(b.dtype)
    if lt is None or rt is None:
        raise TypeError(f"decimal {op!r} over {a.dtype} and {b.dtype}")
    out = arithmetic_type(op, lt, rt)
    name = {"+": "add", "-": "sub", "*": "mul"}[op]
    if out.precision <= dtypes.MAX_DEC64_PRECISION:
        x, y = a.data.astype(jnp.int64), b.data.astype(jnp.int64)
        if op != "*":
            with jax.named_scope("decimal.rescale"):
                x = x * (10 ** (out.scale - lt.scale))
                y = y * (10 ** (out.scale - rt.scale))
        with jax.named_scope("decimal." + name):
            r = x * y if op == "*" else (x + y if op == "+" else x - y)
        return Column(dtype=out, length=a.length,
                      data=r.astype(out.storage_dtype()),
                      validity=_combined_validity(a, b))
    wa, wb = widen(a), widen(b)
    if op == "*":
        ovf, res = multiply_decimal128(wa, wb, out.scale,
                                       cast_interim_result=False)
    else:
        ovf, res = add_decimal128(wa, wb, out.scale, is_sub=op == "-")
    res = Column(dtype=out, length=res.length, data=res.data,
                 validity=res.validity)
    if out.precision < MAX_PRECISION:
        return res      # an unadjusted type holds every result
    return _null_overflow(res, ovf.data, alive)


def comparison_scale(lt: dtypes.DType, rt: dtypes.DType) -> int:
    """The scale a comparison casts its two decimal sides to: Spark's
    wider common type has the larger scale (`DecimalPrecision`), and a
    side of the smaller scale is rescaled, which is exact. TypeError where
    that is not lowered: the rescaled side must stay within 18 digits (a
    literal, an int64 column; a plan states any other cast). The verifier
    asks here too."""
    scale = max(lt.scale, rt.scale)
    for dt in (lt, rt):
        if dt.scale < scale and (
                dt.precision + scale - dt.scale > dtypes.MAX_DEC64_PRECISION):
            raise TypeError(
                f"a comparison between {lt} and {rt} rescales the side of "
                "the smaller scale past 18 digits (state the cast)")
    return scale


def compare(op: str, a: Column, b: Column) -> jnp.ndarray:
    """`a op b` (a comparison) over two decimal or integral columns, as a
    bool array, both sides at `comparison_scale`. A DECIMAL128 side is
    compared limb by limb, the top one signed. A column of one row (a
    literal) broadcasts."""
    lt, rt = as_decimal_type(a.dtype), as_decimal_type(b.dtype)
    if lt is None or rt is None:
        raise TypeError(f"comparison between {a.dtype} and {b.dtype}")
    scale = comparison_scale(lt, rt)

    def at_scale(col: Column, dt: dtypes.DType) -> Column:
        up = scale - dt.scale
        if not up:
            return col
        with jax.named_scope("decimal.rescale"):
            data = col.data.astype(jnp.int64) * (10 ** up)
        return Column(dtype=dtypes.decimal(dt.precision + up, scale),
                      length=col.length, data=data, validity=col.validity)
    a, b = at_scale(a, lt), at_scale(b, rt)
    if Kind.DECIMAL128 not in (a.dtype.kind, b.dtype.kind):
        x, y = a.data.astype(jnp.int64), b.data.astype(jnp.int64)
        less, equal = x < y, x == y
    else:
        x, y = widen(a).data, widen(b).data           # (n, 4) uint32
        signed = lambda w: jax.lax.bitcast_convert_type(w, jnp.int32)
        less = signed(x[:, 3]) < signed(y[:, 3])
        equal = x[:, 3] == y[:, 3]
        for j in (2, 1, 0):
            less = less | (equal & (x[:, j] < y[:, j]))
            equal = equal & (x[:, j] == y[:, j])
    return {"<": less, "<=": less | equal, ">": ~(less | equal),
            ">=": ~less, "==": equal, "!=": ~equal}[op]


def literal_column(value: int, n: int) -> Column:
    dt = literal_type(value)
    return Column(dtype=dt, length=n,
                  data=jnp.full((n,), int(value), dt.storage_dtype()))


# ---- decimal aggregates --------------------------------------------------------

def limb_planes(col: Column) -> List[jnp.ndarray]:
    """The unscaled value as sum(plane_j * 2**(32 j)), every plane a
    32-bit array (unsigned, the top one signed) that a group-by kernel
    widens to int64 as it sums, so that the sum of a plane over up to
    2**31 rows is exact and a plane costs a sort or a gather one word a
    row. A group-by sums planes; `finish_sum` puts them together."""
    if col.dtype.kind == Kind.DECIMAL128:
        d = col.data
        return [d[:, j] for j in range(3)] + [
            jax.lax.bitcast_convert_type(d[:, 3], jnp.int32)]
    if col.dtype.kind == Kind.DECIMAL32:
        return [col.data.astype(jnp.int32)]
    v = col.data.astype(jnp.int64)
    return [v.astype(jnp.uint32), (v >> 32).astype(jnp.int32)]


def _planes_total(planes) -> jnp.ndarray:
    """Per-group int64 plane sums -> (g, 8) limbs of the 256-bit total."""
    g = planes[0].shape[0]
    total = jnp.zeros((g, d256.NLIMBS), jnp.uint64)
    for j, s in enumerate(planes):
        ext = (s >> 63).astype(jnp.uint64) & jnp.uint64(0xFFFFFFFF)
        limbs = [jnp.zeros((g,), jnp.uint64)] * j + [
            s.astype(jnp.uint64) & jnp.uint64(0xFFFFFFFF),
            (s >> 32).astype(jnp.uint64) & jnp.uint64(0xFFFFFFFF)] \
            + [ext] * (d256.NLIMBS - j - 2)
        total = d256.add(total, jnp.stack(limbs, axis=1))
    return total


@functools.partial(jax.jit, static_argnames=("precision",))
def _total_limbs(planes, precision: int):
    """Per-group plane sums -> (the total's low 128 bits as (g, 4) uint32
    limbs, whether it passes `precision` digits). One program: called
    eagerly over millions of groups, the 256-bit intermediates ((g, 8)
    uint64, 64 bytes a group each) would each be an array in memory."""
    with jax.named_scope("decimal.sum"):
        total = _planes_total(planes)
        return d256.to_i128_limbs(total), _exceeds(total, precision)


def _sum_column(planes, count, dt: dtypes.DType):
    """-> (the sum as a DECIMAL128-layout column of Sum's type, null for
    an all-null group and on overflow; Sum's type)."""
    st = sum_type(dt)
    limbs, overflow = _total_limbs(tuple(planes), st.precision)
    col = Column(dtype=dtypes.DType(Kind.DECIMAL128, precision=st.precision,
                                    scale=st.scale),
                 length=limbs.shape[0], data=limbs, validity=count > 0)
    return _null_overflow(col, overflow), st


def _stored(col: Column, dt: dtypes.DType) -> Column:
    """A limb-layout result under its type `dt` in that type's own
    storage: limbs past 18 digits, else the int64 (int32) it fits."""
    data = col.data
    if dt.kind != Kind.DECIMAL128:
        data = (data[:, 0].astype(jnp.int64)
                | (data[:, 1].astype(jnp.int64) << 32)
                ).astype(dt.storage_dtype())
    return Column(dtype=dt, length=col.length, data=data,
                  validity=col.validity)


def finish_sum(planes, count, dt: dtypes.DType) -> Column:
    """Spark's Sum over a decimal(p, s) column from the per-group plane
    sums and non-null counts: `decimal(p + 10, s)` bounded, null on
    overflow and for a group without a value."""
    return _stored(*_sum_column(planes, count, dt))


def finish_mean(planes, count, dt: dtypes.DType) -> Column:
    """Spark's Average over a decimal(p, s) column: the sum as
    `decimal(p + 10, s)` divided by the count as `decimal(20, 0)` at the
    divide rule's type, then cast HALF_UP to `decimal(p + 4, s + 4)`: two
    roundings, as Spark has. Null for a group without a value and
    wherever the sum, the quotient or the cast overflows."""
    total, _ = _sum_column(planes, count, dt)
    _, qt, rt = average_types(dt)
    cnt = Column(dtype=dtypes.DType(Kind.DECIMAL128, precision=20, scale=0),
                 length=total.length, data=_i64_limbs(count))
    ovf, quot = divide_decimal128(total, cnt, qt.scale)
    limbs = _set_scale_and_round(d256.from_i128_limbs(quot.data),
                                 -qt.scale, -rt.scale)
    col = Column(dtype=dtypes.DType(Kind.DECIMAL128, precision=rt.precision,
                                    scale=rt.scale),
                 length=total.length, data=d256.to_i128_limbs(limbs),
                 validity=total.validity)
    overflow = (ovf.data & (count > 0)) | _exceeds(limbs, rt.precision)
    return _stored(_null_overflow(col, overflow), rt)
