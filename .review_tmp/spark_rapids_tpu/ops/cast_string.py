"""Spark-exact string→numeric casts (ANSI-aware), TPU-vectorized.

Re-design of the reference's cast kernels (cast_string.cu:158-244 string→int,
cast_string_to_float.cu:56-653 string→float, CastStringJni.cpp:159-258 base
conversions) for the XLA substrate: the reference marches one CUDA thread (or
warp) per row over the chars; here every rule is a dense boolean-matrix
computation over the padded (rows, max_len) char matrix, and digit
accumulation is a closed-form positional-weight multiply-reduce (each digit
times 10^rank-from-the-right in u64) rather than a sequential loop — one
fused XLA pass over the matrix instead of max_len dependent steps.

Spark semantics preserved:
- whitespace = {space, \\r, \\t, \\n} only (cast_string.cu:46-56);
- int casts: optional leading/trailing whitespace (strip), sign, truncation
  at the first '.' in non-ANSI mode with the tail still validated
  (cast_string.cu:210-213), digit-by-digit overflow detection against the
  target type's limits (cast_string.cu:100-143);
- ANSI mode errors carry the first failing row index and its string
  (cast_string.hpp:26-56, validate_ansi_column cast_string.cu:601-634);
- float casts: 'nan' only as the exact 3-char string, 'inf'/'infinity'
  (case-insensitive) must end the string, at most 19 significant digits
  accumulated into a uint64 with greedy 20th-digit absorption, 4-digit manual
  exponents, trailing f/F/d/D suffix allowed, value built as
  sign*digits*10^exp in double then cast (cast_string_to_float.cu:309-474);
  a zero mantissa skips trailing-suffix handling, so '0e5' and '0\\n' are
  valid zeros but '0f' is invalid (cast_string_to_float.cu:131-141) - a
  deliberate quirk kept for parity.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column
from ..dtypes import DType, Kind


class CastError(RuntimeError):
    """ANSI cast failure carrying the first bad row (cast_string.hpp:26-56)."""

    def __init__(self, row_number: int, string_with_error: str):
        super().__init__(
            f"Error casting data on row {row_number}: {string_with_error!r}")
        self.row_number = row_number
        self.string_with_error = string_with_error


_INT_LIMITS = {
    Kind.INT8: (-128, 127),
    Kind.INT16: (-32768, 32767),
    Kind.INT32: (-(2**31), 2**31 - 1),
    Kind.INT64: (-(2**63), 2**63 - 1),
}


def _is_ws(c):
    return (c == 32) | (c == 13) | (c == 9) | (c == 10)


def _first_idx(mask, default: int):
    """Per-row first True column index in (n, L) mask, `default` if none."""
    has = jnp.any(mask, axis=1)
    return jnp.where(has, jnp.argmax(mask, axis=1).astype(jnp.int32),
                     jnp.int32(default))


def _char_at(C, idx):
    """Per-row char at (clipped) dynamic index. C: (n, L) int32."""
    L = C.shape[1]
    return jnp.take_along_axis(C, jnp.clip(idx, 0, L - 1)[:, None], axis=1)[:, 0]


def _rank_in_mask(mask):
    """Exclusive per-row running count of True positions in an (n, L) mask:
    rank[i, j] = number of True entries strictly left of j in row i."""
    c = jnp.cumsum(mask, axis=1, dtype=jnp.int32)
    return c - mask.astype(jnp.int32)


# 10^k as u64 for k in [0, 19] (10^19 < 2^64); jnp.take per (n, L) exponent
# plane gives each digit its positional weight so a whole row's magnitude is
# one masked multiply-reduce instead of an L-step sequential accumulator
_POW10_U64 = np.array([10**k for k in range(20)], dtype=np.uint64)


def _raise_first_error(col: Column, error_mask):
    """ANSI contract: raise for the first flagged row with its content
    (validate_ansi_column, cast_string.cu:601-634)."""
    errors = np.asarray(error_mask)
    if errors.any():
        row = int(np.argmax(errors))
        strings = col.to_pylist()
        raise CastError(row, strings[row] if strings[row] is not None else "")


def string_to_integer(col: Column, out_type: DType, ansi_mode: bool = False,
                      strip: bool = True, pad_to: Optional[int] = None) -> Column:
    """Spark-exact string→INT8/16/32/64 (cast_string.cu:158-244).

    Returns a column of out_type; invalid rows null (or CastError in ANSI).
    """
    assert out_type.kind in _INT_LIMITS, f"not an integer type: {out_type}"
    tmin, tmax = _INT_LIMITS[out_type.kind]

    padded, lens = col.padded_chars(pad_to)
    C = padded.astype(jnp.int32)
    n, L = C.shape
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    lens2 = lens[:, None]
    in_str = pos < lens2
    ws = _is_ws(C)
    digit = (C >= 48) & (C <= 57)
    dot = C == 46

    valid_in = col.null_mask
    # leading whitespace skip
    if strip:
        i0 = _first_idx(~ws & in_str, 0)
        i0 = jnp.where(jnp.any(~ws & in_str, axis=1), i0, lens)
    else:
        i0 = jnp.zeros((n,), jnp.int32)
    # optional sign
    c0 = _char_at(C, i0)
    has_sign = ((c0 == 43) | (c0 == 45)) & (i0 < lens)
    neg = (c0 == 45) & has_sign
    istart = i0 + has_sign.astype(jnp.int32)

    valid = valid_in & (lens > 0) & (istart < lens)

    region = (pos >= istart[:, None]) & in_str
    # any char that is not digit / dot / whitespace is invalid
    valid &= ~jnp.any(region & ~digit & ~dot & ~ws, axis=1)
    # whitespace rules: with strip, the first ws begins the trailing region
    # (must not be the first char, everything after must be ws); without
    # strip any ws is invalid (cast_string.cu:207-222)
    ws_in = ws & region
    if strip:
        fw = _first_idx(ws_in, L)
        after_fw = region & (pos >= fw[:, None])
        valid &= ~jnp.any(after_fw & ~ws, axis=1)
        valid &= fw != istart
    else:
        valid &= ~jnp.any(ws_in, axis=1)
        fw = jnp.full((n,), L, jnp.int32)
    # dot rules: ANSI forbids; else truncate at the first, a second is invalid
    dot_in = dot & region
    if ansi_mode:
        valid &= ~jnp.any(dot_in, axis=1)
        first_dot = jnp.full((n,), L, jnp.int32)
    else:
        first_dot = _first_idx(dot_in, L)
        valid &= jnp.sum(dot_in, axis=1) <= 1

    dend = jnp.minimum(jnp.minimum(first_dot, fw), lens)

    # Closed-form digit accumulation (replaces an L-step sequential loop):
    # appending a digit never shrinks the magnitude, so the reference's
    # per-step overflow checks (cast_string.cu:100-143) fire iff the final
    # magnitude exceeds the type bound. Give each digit its positional
    # weight 10^(dend-1-pos) and reduce — exact in u64 once rows with more
    # than 19 significant digits (which always overflow every int type) are
    # flagged up front. Rows already invalid from the region checks may
    # compute garbage here; their validity is already false.
    dig_run = (pos >= istart[:, None]) & (pos < dend[:, None])
    nzrun = dig_run & (C != 48)
    first_nz = _first_idx(nzrun, 0)
    first_nz = jnp.where(jnp.any(nzrun, axis=1), first_nz, dend)
    nd_eff = dend - first_nz                  # digits after leading zeros
    e = dend[:, None] - 1 - pos
    w = jnp.take(jnp.asarray(_POW10_U64), jnp.clip(e, 0, 19))
    d_u = jnp.clip(C - 48, 0, 9).astype(jnp.uint64)
    dmask = dig_run & (pos >= first_nz[:, None])
    mag = jnp.sum(jnp.where(dmask, d_u * w, jnp.uint64(0)), axis=1)
    of = (nd_eff > 19) | jnp.where(neg, mag > jnp.uint64(-tmin),
                                   mag > jnp.uint64(tmax))
    valid &= ~of
    val = jax.lax.bitcast_convert_type(
        jnp.where(neg, jnp.uint64(0) - mag, mag), jnp.int64)

    out = Column(dtype=out_type, length=n,
                 data=val.astype(out_type.storage_dtype()),
                 validity=valid)
    if ansi_mode:
        _raise_first_error(col, valid_in & ~valid)
    return out


# ---------------------------------------------------------------------------
# string -> float
# ---------------------------------------------------------------------------
_MAX_HOLDING = (2**64 - 1 - 9) // 10  # cast_string_to_float.cu:396-404

# Correctly-rounded powers of ten (the reference uses device exp10; a constant
# table is exact on CPU and avoids the TPU f64-emulation's inexact pow)
_P10_MIN, _P10_MAX = -350, 350
_P10_TABLE = None


def _pow10(k):
    """10.0**k for integer array k via correctly-rounded table lookup."""
    global _P10_TABLE
    if _P10_TABLE is None:
        # cached as a HOST array: caching a jnp array created during a jit
        # trace would leak the tracer into later traces
        _P10_TABLE = np.asarray(
            [float(f"1e{i}") if -324 < i <= 308 else (0.0 if i <= -324 else np.inf)
             for i in range(_P10_MIN, _P10_MAX + 1)], dtype=np.float64)
    idx = jnp.clip(k - _P10_MIN, 0, _P10_MAX - _P10_MIN)
    return jnp.take(jnp.asarray(_P10_TABLE), idx)


def _ci_match(C, start, lens, word: bytes):
    """Case-insensitive match of `word` at per-row dynamic index `start`."""
    m = jnp.ones((C.shape[0],), jnp.bool_)
    for k, ch in enumerate(word):
        c = _char_at(C, start + k)
        m &= ((c == ch) | (c == ch - 32)) & (start + k < lens)
    return m


def string_to_float(col: Column, out_type: DType, ansi_mode: bool = False,
                    pad_to: Optional[int] = None) -> Column:
    """Spark-exact string→FLOAT32/64 (cast_string_to_float.cu:56-653)."""
    assert out_type.kind in (Kind.FLOAT32, Kind.FLOAT64)
    padded, lens = col.padded_chars(pad_to)
    C = padded.astype(jnp.int32)
    n, L = C.shape
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    in_str = pos < lens[:, None]
    ws = _is_ws(C)
    digit = (C >= 48) & (C <= 57)
    dot = C == 46

    valid_in = col.null_mask
    lens_i = lens.astype(jnp.int32)

    def skip_ws(start):
        """First non-ws index >= start (per row), else lens."""
        m = ~ws & in_str & (pos >= start[:, None])
        idx = _first_idx(m, 0)
        return jnp.where(jnp.any(m, axis=1), idx, lens_i)

    i0 = skip_ws(jnp.zeros((n,), jnp.int32))
    c0 = _char_at(C, i0)
    has_sign = ((c0 == 43) | (c0 == 45)) & (i0 < lens_i)
    neg = (c0 == 45) & has_sign
    sign = jnp.where(neg, -1.0, 1.0)
    p0 = i0 + has_sign.astype(jnp.int32)

    # --- nan: only the exact 3-char string is valid; 'nan'+junk raises in
    # ANSI (cast_string_to_float.cu:235-255)
    starts_nan = _ci_match(C, p0, lens_i, b"nan")
    nan_valid = starts_nan & (lens_i == 3)
    nan_except = starts_nan & (lens_i != 3)

    # --- inf / infinity: must end the string; junk after silently nulls
    # without an ANSI exception (cast_string_to_float.cu:257-306)
    inf3 = _ci_match(C, p0, lens_i, b"inf") & ~starts_nan
    inf8 = inf3 & _ci_match(C, p0 + 3, lens_i, b"inity")
    inf_valid = (inf3 & (p0 + 3 == lens_i)) | (inf8 & (p0 + 8 == lens_i))
    is_inf_path = inf3

    # --- digit parsing over [p0, term) where term is the first char that is
    # neither digit nor '.'
    reg = (pos >= p0[:, None]) & in_str
    nondig = reg & ~digit & ~dot
    term = _first_idx(nondig, 0)
    term = jnp.where(jnp.any(nondig, axis=1), term, lens_i)

    mant = reg & (pos < term[:, None])
    dots_in_mant = jnp.sum(dot & mant, axis=1)
    multi_dot = dots_in_mant > 1
    dot_idx = _first_idx(dot & mant, L)
    has_dot = dots_in_mant == 1
    # a '.' appearing at/after term ends up invalid (decimal_pos check,
    # cast_string_to_float.cu:372-376)
    stray_dot = jnp.any(dot & in_str & (pos >= term[:, None]), axis=1)

    predot_end = jnp.minimum(dot_idx, term)
    # leading zeros stripped while no decimal seen and value still zero
    pre_region = mant & (pos < predot_end[:, None])
    nonzero_pre = pre_region & (C != 48)
    first_nz = _first_idx(nonzero_pre, 0)
    first_nz = jnp.where(jnp.any(nonzero_pre, axis=1), first_nz, predot_end)
    z = first_nz - p0                                   # stripped zeros
    a1 = predot_end - first_nz                          # counted pre-dot digits
    a2 = jnp.where(has_dot, term - dot_idx - 1, 0)      # post-dot digits
    total_digits = a1 + a2
    seen_digit = (z > 0) | (total_digits > 0)

    # accumulate at most 19 digits + greedy 20th (cast_string_to_float.cu:390-440)
    # mask of counted digit positions: digits in [first_nz, term) excluding dot
    counted = (pos >= first_nz[:, None]) & (pos < term[:, None]) & digit

    # Closed form (replaces an L-step sequential accumulator): the loop
    # absorbs exactly min(total, 19) digits unconditionally, then at most ONE
    # guarded 20th (after a 20th digit the count passes 19 and nothing more
    # can ever absorb). So rank every counted digit, weight the first k19 by
    # 10^(k19-1-rank), reduce in u64 (k19 <= 19 keeps it exact), and apply
    # the single 20th-digit guard (check order of cast_string_to_float.cu:
    # 404-427: the <= max_holding test precedes the multiply so it can't wrap).
    r = _rank_in_mask(counted)
    total_counted = jnp.sum(counted, axis=1).astype(jnp.int32)
    k19 = jnp.minimum(total_counted, 19)
    e19 = k19[:, None] - 1 - r
    w19 = jnp.take(jnp.asarray(_POW10_U64), jnp.clip(e19, 0, 19))
    d_u = jnp.clip(C - 48, 0, 9).astype(jnp.uint64)
    take19 = counted & (r < k19[:, None])
    dval19 = jnp.sum(jnp.where(take19, d_u * w19, jnp.uint64(0)), axis=1)
    d20 = jnp.sum(jnp.where(counted & (r == 19), d_u, jnp.uint64(0)), axis=1)
    extra_ok = (total_counted >= 20) & (dval19 <= jnp.uint64(_MAX_HOLDING)) & \
        (dval19 * jnp.uint64(10) + d20 <= jnp.uint64(_MAX_HOLDING))
    dval = jnp.where(extra_ok, dval19 * jnp.uint64(10) + d20, dval19)
    absorbed = k19 + extra_ok.astype(jnp.int32)
    truncated = total_digits - absorbed
    exp_base = truncated - jnp.where(has_dot, total_digits - a1, 0)

    zero_mantissa = dval == jnp.uint64(0)

    # --- manual exponent (cast_string_to_float.cu:479-528)
    has_e = (term < lens_i) & ((_char_at(C, term) == 101) | (_char_at(C, term) == 69))
    ce = _char_at(C, term + 1)
    e_sign_char = ((ce == 43) | (ce == 45)) & has_e & (term + 1 < lens_i)
    e_neg = (ce == 45) & e_sign_char
    estart = term + 1 + e_sign_char.astype(jnp.int32)
    # count leading digits at estart, capped at 4
    nd = jnp.zeros((n,), jnp.int32)
    eval_ = jnp.zeros((n,), jnp.int32)
    for k in range(4):
        ck = _char_at(C, estart + k)
        is_d = (ck >= 48) & (ck <= 57) & (estart + k < lens_i) & (nd == k)
        eval_ = jnp.where(is_d, eval_ * 10 + (ck - 48), eval_)
        nd = nd + is_d.astype(jnp.int32)
    manual_exp = jnp.where(e_neg, -eval_, eval_)
    exp_invalid = has_e & (nd == 0)
    after_exp = jnp.where(has_e, estart + nd, term)

    # --- trailing: one optional f/F/d/D, then ws, then end
    # (cast_string_to_float.cu:530-553)
    cq = _char_at(C, after_exp)
    has_suffix = ((cq == 102) | (cq == 70) | (cq == 100) | (cq == 68)) & \
        (after_exp < lens_i)
    q = after_exp + has_suffix.astype(jnp.int32)
    after_ws = skip_ws(q)
    trailing_junk = after_ws < lens_i

    # zero-mantissa path: the manual exponent IS parsed first (operator()
    # order, cast_string_to_float.cu:119-141), then only ws may follow —
    # so '0e5' is valid 0 but '0f' is invalid (no suffix handling here)
    zero_after_ws = skip_ws(after_exp)
    zero_junk = zero_after_ws < lens_i

    # --- assemble validity
    number_valid = ~multi_dot & ~stray_dot & seen_digit & ~exp_invalid & \
        jnp.where(zero_mantissa, ~zero_junk, ~trailing_junk)
    valid = valid_in & jnp.where(
        starts_nan, nan_valid, jnp.where(is_inf_path, inf_valid, number_valid))

    # ANSI exception flag: inf-with-junk does NOT raise (quirk kept;
    # compute_validity only sees except from nan/digit paths); empty and
    # ws-only strings raise via the no-digit rule
    number_except = multi_dot | stray_dot | ~seen_digit | exp_invalid | \
        jnp.where(zero_mantissa, zero_junk, trailing_junk)
    except_flag = valid_in & jnp.where(
        starts_nan, nan_except,
        jnp.where(is_inf_path, jnp.zeros((n,), jnp.bool_), number_except))

    # --- construct the value in f64 (cast_string_to_float.cu:150-196)
    digitsf = sign * dval.astype(jnp.float64)
    exp_ten = (exp_base + manual_exp).astype(jnp.int32)
    overflow = exp_ten > 308
    subnormal_shift = -307 - exp_ten
    safe_dval = jnp.maximum(dval, jnp.uint64(1)).astype(jnp.float64)
    num_digits = jnp.floor(jnp.log10(safe_dval)).astype(jnp.int32) + 1
    # subnormal branch
    sub_digitsf = digitsf / _pow10(num_digits - 1 + subnormal_shift)
    sub_result = sub_digitsf * _pow10(exp_ten + num_digits - 1 + subnormal_shift)
    # normal branch
    expf = _pow10(jnp.abs(exp_ten))
    norm_result = jnp.where(exp_ten < 0, digitsf / expf, digitsf * expf)
    result = jnp.where(subnormal_shift > 0, sub_result, norm_result)
    result = jnp.where(overflow, sign * jnp.inf, result)
    result = jnp.where(zero_mantissa, sign * 0.0, result)
    result = jnp.where(is_inf_path, sign * jnp.inf, result)
    result = jnp.where(starts_nan, jnp.nan, result)

    out = Column(dtype=out_type, length=n,
                 data=result.astype(out_type.storage_dtype()), validity=valid)
    if ansi_mode:
        _raise_first_error(col, except_flag & ~valid)
    return out


# ---------------------------------------------------------------------------
# base conversion (Spark `conv`) - CastStringJni.cpp:159-258
# ---------------------------------------------------------------------------
def string_to_integer_with_base(col: Column, out_type: DType, base: int = 10,
                                ansi_mode: bool = False,
                                pad_to: Optional[int] = None) -> Column:
    """toIntegersWithBase: leading-token extraction with regex semantics
    ^\\s*(-?[0-9a-fA-F]+).* — non-matching rows become 0 (not null),
    whitespace-only rows become null, arithmetic wraps modulo 2^bits."""
    if base not in (10, 16):
        raise CastError(0, f"Bases supported 10, 16; Actual: {base}")
    padded, lens = col.padded_chars(pad_to)
    C = padded.astype(jnp.int32)
    n, L = C.shape
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    in_str = pos < lens[:, None]
    # regex \s class: the reference implements conv via cudf regexes
    # (CastStringJni.cpp:174-210), so \f and \v count here, unlike the
    # 4-char Spark set used by the int/float casts
    ws = _is_ws(C) | (C == 12) | (C == 11)

    i0 = _first_idx(~ws & in_str, 0)
    all_ws = ~jnp.any(~ws & in_str, axis=1)
    i0 = jnp.where(all_ws, lens, i0)
    c0 = _char_at(C, i0)
    neg = (c0 == 45) & (i0 < lens)
    istart = i0 + neg.astype(jnp.int32)

    if base == 10:
        is_dig = (C >= 48) & (C <= 57)
        dval = C - 48
    else:
        is_dig = ((C >= 48) & (C <= 57)) | ((C >= 97) & (C <= 102)) | \
            ((C >= 65) & (C <= 70))
        dval = jnp.where((C >= 48) & (C <= 57), C - 48,
                         jnp.where((C >= 97) & (C <= 102), C - 87, C - 55))
    run = (pos >= istart[:, None]) & in_str
    non_dig_in_run = run & ~is_dig
    run_end = _first_idx(non_dig_in_run, 0)
    run_end = jnp.where(jnp.any(non_dig_in_run, axis=1), run_end, lens)
    matched = run_end > istart  # at least one digit after optional sign

    # Closed form mod 2^64 (conv arithmetic wraps): weight each digit by
    # base^(run_end-1-pos) mod 2^64 — the wrapped power table is computed
    # host-side with exact bigints, so the masked multiply-reduce matches the
    # sequential val*base+d chain bit for bit.
    btbl = jnp.asarray(np.array([pow(base, k, 2**64) for k in range(max(L, 1))],
                                dtype=np.uint64))
    eb = run_end[:, None] - 1 - pos
    wb = jnp.take(btbl, jnp.clip(eb, 0, L - 1))
    brun = (pos >= istart[:, None]) & (pos < run_end[:, None])
    mag = jnp.sum(jnp.where(brun, dval.astype(jnp.uint64) * wb, jnp.uint64(0)),
                  axis=1)
    val = jax.lax.bitcast_convert_type(
        jnp.where(neg, jnp.uint64(0) - mag, mag), jnp.int64)
    val = jnp.where(matched, val, 0)
    validity = col.null_mask & ~all_ws & (lens > 0)
    return Column(dtype=out_type, length=n,
                  data=val.astype(out_type.storage_dtype()),
                  validity=validity)


def integer_to_string_with_base(col: Column, base: int = 10) -> Column:
    """fromIntegersWithBase: base 10 decimal strings; base 16 uppercase hex of
    the two's-complement value with leading zeros stripped."""
    from ..columnar.column import strings_from_padded

    if base not in (10, 16):
        raise CastError(0, f"Bases supported 10, 16; Actual: {base}")
    nbits = col.dtype.itemsize() * 8
    n = col.length
    if base == 16:
        u = col.data.astype(jnp.int64).astype(jnp.uint64)
        if nbits < 64:
            u = u & jnp.uint64((1 << nbits) - 1)
        ndig = nbits // 4
        shifts = jnp.arange(ndig - 1, -1, -1, dtype=jnp.uint64) * 4
        nibbles = ((u[:, None] >> shifts[None, :]) & jnp.uint64(0xF)).astype(jnp.int32)
        chars = jnp.where(nibbles < 10, nibbles + 48, nibbles + 55)  # uppercase
        nz = nibbles != 0
        first = _first_idx(nz, ndig - 1)  # value 0 -> single '0'
        lens_out = ndig - jnp.minimum(first, ndig - 1)
        # shift each row left so its first significant nibble is at column 0
        idx = jnp.minimum(first, ndig - 1)[:, None] + jnp.arange(ndig)[None, :]
        out = jnp.take_along_axis(chars, jnp.clip(idx, 0, ndig - 1), axis=1)
        return strings_from_padded(out.astype(jnp.uint8), lens_out, col.validity)
    # base 10
    if col.dtype.kind == Kind.UINT64:
        # Spark conv() prints the unsigned value ("-510" parsed base 10 comes
        # back as 18446744073709551106, CastStringsTest.baseDec2HexTestMixed)
        mag = col.data.astype(jnp.uint64)
        neg = jnp.zeros((n,), jnp.bool_)
    else:
        v = col.data.astype(jnp.int64)
        neg = v < 0
        mag = jnp.where(neg, -v.astype(jnp.uint64), v.astype(jnp.uint64))
        # careful: -INT64_MIN wraps to itself, the correct magnitude bits
        mag = jnp.where(v == jnp.int64(-(2**63)), jnp.uint64(2**63), mag)
    ndig = 20
    pows = jnp.asarray([10**k for k in range(ndig)], dtype=jnp.uint64)
    digs = ((mag[:, None] // pows[None, ::-1]) % jnp.uint64(10)).astype(jnp.int32)
    nzd = digs != 0
    first = _first_idx(nzd, ndig - 1)
    first = jnp.minimum(first, ndig - 1)
    mag_len = ndig - first
    lens_out = mag_len + neg.astype(jnp.int32)
    width = ndig + 1
    j = jnp.arange(width, dtype=jnp.int32)[None, :]
    # digit j of output (after optional '-') is digs[first + j - neg]
    src = first[:, None] + j - neg.astype(jnp.int32)[:, None]
    dchars = jnp.take_along_axis(digs, jnp.clip(src, 0, ndig - 1), axis=1) + 48
    out = jnp.where((j == 0) & neg[:, None], 45, dchars)
    return strings_from_padded(out.astype(jnp.uint8), lens_out, col.validity)
