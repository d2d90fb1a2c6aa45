"""Spark-exact string→DECIMAL32/64/128 cast, TPU-vectorized.

Re-design of the reference's two-pass decimal parser
(validate_and_exponent cast_string.cu:247-374, string_to_decimal_kernel
cast_string.cu:376-599): the reference marches one CUDA thread per row; here
the structural validation is boolean-matrix algebra over the padded char
matrix, the digit/significance bookkeeping is exclusive prefix sums, and the
value itself is a closed-form positional-weight multiply-reduce into 256-bit
limbs (per-limb u64 sums + one carry propagation, decimal256.py) so
DECIMAL128 needs no native int128 and no per-character sequential loop.

Semantics preserved:
- grammar ws* sign? digits* ('.' digits*)? ([eE] sign? digits*)? ws* with the
  reference's quirks: no digits required ('.', '+e5' parse to 0), trailing
  whitespace may start in the mantissa or immediately after 'e' but nowhere
  else ('1e5 ' is invalid), empty exponents are fine ('1e', '1e+');
- digit accumulation stops at `precision` significant digits or at the
  scale-determined last digit, then rounds HALF_UP on the next digit with
  carry-digit detection (999->1000 grows the digit count,
  cast_string.cu:468-506);
- zero padding up to the decimal point and out to the scale, each step
  overflow-checked against the storage type's limits;
- precision check: significant digits before the decimal must fit
  precision - spark_scale (cast_string.cu:547-553);
- ANSI mode raises CastError with the first failing row.

Known deviation: exponent values are accumulated in int64 even for
DECIMAL128 (the reference uses int128), so exponents with |e| > 2^63 parse
invalid instead of producing a zero/overflow — unreachable for sane data.
Exponents that pass that bound are then clamped to ±2^40 before the
decimal-location arithmetic: every downstream comparison is against
quantities ≤ 39 + precision + row length, so any |e| beyond the clamp
behaves identically (huge positive → overflow/null via the zero-padding
check, huge negative → all digits insignificant → 0) while `dl + e` can
no longer wrap int64 (an exponent like 9e9223372036854775807 previously
wrapped to a *valid 0* instead of null).

Known deviation (zero mantissa, huge positive exponent): '0e<big>' nulls
here via the zeros-to-decimal ≤ 39 cap, while the reference's padding loop
on a zero value never overflows and yields a valid 0. Spark itself parses
the exponent as a Java int inside BigDecimal, so the null (cast failure)
matches Spark's observable behavior; this is intentional and cemented by a
regression test.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes
from ..columnar import Column
from ..dtypes import Kind
from . import decimal256 as d256
from .cast_string import (CastError, _POW10_U64, _char_at, _first_idx, _is_ws,
                          _raise_first_error)

_BOUNDS = {
    Kind.DECIMAL32: (2**31 - 1, 2**31),
    Kind.DECIMAL64: (2**63 - 1, 2**63),
    Kind.DECIMAL128: (2**127 - 1, 2**127),
}


def string_to_decimal(col: Column, precision: int, scale: int,
                      ansi_mode: bool = False, strip: bool = True,
                      pad_to: Optional[int] = None) -> Column:
    """string -> decimal(precision, scale); storage width picked by precision
    exactly like the reference host API (cast_string.cu:818-827)."""
    out_type = dtypes.decimal(precision, scale)
    tmax_pos, tmax_negmag = _BOUNDS[out_type.kind]
    cudf_scale = -scale

    padded, lens = col.padded_chars(pad_to)
    C = padded.astype(jnp.int32)
    n, L = C.shape
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    lens_i = lens.astype(jnp.int32)
    in_str = pos < lens_i[:, None]
    ws = _is_ws(C)
    digit = (C >= 48) & (C <= 57)
    dot = C == 46

    valid_in = col.null_mask

    # ---- leading ws / sign ----------------------------------------------------
    if strip:
        nonws = ~ws & in_str
        i0 = jnp.where(jnp.any(nonws, axis=1), _first_idx(nonws, 0), lens_i)
    else:
        i0 = jnp.zeros((n,), jnp.int32)
    c0 = _char_at(C, i0)
    has_sign = ((c0 == 43) | (c0 == 45)) & (i0 < lens_i)
    positive = ~((c0 == 45) & has_sign)
    istart = i0 + has_sign.astype(jnp.int32)
    valid = valid_in & (lens_i > 0) & (istart < lens_i)

    # ---- structural regions ---------------------------------------------------
    region = (pos >= istart[:, None]) & in_str
    is_e = ((C == 101) | (C == 69)) & region
    e_idx = jnp.where(jnp.any(is_e, axis=1), _first_idx(is_e, 0), lens_i)
    if strip:
        ws_in = ws & region
        fw = jnp.where(jnp.any(ws_in, axis=1), _first_idx(ws_in, 0), lens_i)
    else:
        valid &= ~jnp.any(ws & region, axis=1)
        fw = lens_i
    mant_end = jnp.minimum(jnp.minimum(e_idx, fw), lens_i)
    mant = region & (pos < mant_end[:, None])
    dots_in_mant = jnp.sum(dot & mant, axis=1)
    dot_idx = jnp.where(dots_in_mant > 0, _first_idx(dot & mant, 0), lens_i)
    has_dot = dots_in_mant == 1

    has_e = e_idx < lens_i
    ce = _char_at(C, e_idx + 1)
    e_sign_char = ((ce == 43) | (ce == 45)) & has_e & (e_idx + 1 < lens_i)
    exp_positive = ~((ce == 45) & e_sign_char)
    estart = e_idx + 1 + e_sign_char.astype(jnp.int32)

    # trailing ws may begin in the mantissa (after istart) or exactly at
    # e_idx+1 (the EXP_OR_SIGN state, cast_string.cu:293-307); all chars at or
    # after fw must be ws
    fw_ok = (fw >= lens_i) | ((fw == mant_end) & (fw > istart)) | (fw == e_idx + 1)
    valid &= fw_ok
    valid &= ~jnp.any(region & (pos >= fw[:, None]) & ~ws, axis=1)
    valid &= dots_in_mant <= 1

    # every char must be: a digit, THE dot, THE e, the exp sign, or trailing ws
    ok = digit | (pos == dot_idx[:, None]) | (pos == e_idx[:, None]) | \
        ((pos == (e_idx + 1)[:, None]) & e_sign_char[:, None]) | \
        (ws & (pos >= fw[:, None]))
    valid &= ~jnp.any(region & ~ok, axis=1)

    # ---- exponent value (int64, overflow-checked vs storage bounds) ----------
    exp_region = region & (pos >= estart[:, None]) & (pos < jnp.minimum(
        fw, lens_i)[:, None])

    # exponent bounds: the storage type's limits, clamped to int64 for
    # DECIMAL128 (documented deviation in the module docstring)
    emax = min(tmax_pos, 2**63 - 1)
    emin = -min(tmax_negmag, 2**63)

    # Closed-form exponent accumulation (replaces an L-step sequential loop):
    # appending a digit never shrinks the magnitude, so the loop's per-step
    # overflow checks fire iff the final magnitude exceeds the bound. Weight
    # each exponent digit by 10^(digits-to-its-right), reduce in u64 (exact
    # once >19-significant-digit rows — which always exceed any bound here —
    # are flagged), then compare against the bound once. Rows already invalid
    # from the structural checks may compute garbage; their validity is false.
    d_u = jnp.clip(C - 48, 0, 9).astype(jnp.uint64)
    em = exp_region & digit
    erfr = jnp.sum(em, axis=1)[:, None] - jnp.cumsum(em, axis=1)  # digits right
    enz = em & (C != 48)
    e_nd_eff = jnp.max(jnp.where(enz, erfr + 1, 0), axis=1)
    wE = jnp.take(jnp.asarray(_POW10_U64), jnp.clip(erfr, 0, 19))
    emag = jnp.sum(jnp.where(em, d_u * wE, jnp.uint64(0)), axis=1)
    eof = (e_nd_eff > 19) | jnp.where(exp_positive, emag > jnp.uint64(emax),
                                      emag > jnp.uint64(-emin))
    valid &= ~eof
    exp_val = jax.lax.bitcast_convert_type(
        jnp.where(exp_positive, emag, jnp.uint64(0) - emag), jnp.int64)
    # clamp far past any digit-count scale so dl + exp_val cannot wrap int64
    # (see module docstring: downstream only compares against ≤ 39 + p + L)
    exp_val = jnp.clip(exp_val, -(2**40), 2**40)

    # ---- decimal location -----------------------------------------------------
    # chars-from-istart index of the '.', or the mantissa digit count
    dl = jnp.where(has_dot, dot_idx - istart, mant_end - istart).astype(jnp.int64)
    dl = dl + exp_val
    last_digit_cnt = dl + scale  # decimal_location - cudf_scale

    # ---- digit indexing & significance (prefix sums) -------------------------
    dmask = mant & digit
    kidx = jnp.cumsum(dmask, axis=1) - dmask.astype(jnp.int32)  # exclusive ordinal
    nonzero_dig = dmask & (C != 48)
    anynz = jnp.cumsum(nonzero_dig, axis=1) > 0  # nonzero seen through this pos
    # digit at ordinal k is significant if (k+1 > dl) or a nonzero digit has
    # been seen (cast_string.cu:509-513)
    sig = dmask & (((kidx + 1) > dl[:, None]) | anynz)
    np_before = jnp.cumsum(sig, axis=1) - sig.astype(jnp.int32)

    accumulate = dmask & (np_before < precision) & (kidx < last_digit_cnt[:, None])
    nd_acc = jnp.sum(accumulate, axis=1).astype(jnp.int64)
    np_final = jnp.sum(sig & accumulate, axis=1).astype(jnp.int64)

    # rounding digit: first digit char not accumulated (cast_string.cu:466-506)
    stop_mask = dmask & ~accumulate
    has_round = jnp.any(stop_mask, axis=1) & (last_digit_cnt >= 0)
    round_digit = jnp.where(
        has_round,
        jnp.take_along_axis(C, _first_idx(stop_mask, 0)[:, None], axis=1)[:, 0] - 48,
        0)

    # significant digits before the decimal, measured on the string
    # (count_significant_digits, cast_string.cu:435-453) - uses dl BEFORE
    # rounding adjustments
    sig_str = dmask & (kidx < dl[:, None]) & anynz
    sig_before_in_string = jnp.sum(sig_str, axis=1).astype(jnp.int64)

    # ---- value accumulation (256-bit magnitude + sign) -----------------------
    bound = d256.from_int([tmax_pos])
    bound_neg = d256.from_int([tmax_negmag])
    bnd = jnp.where(positive[:, None], jnp.broadcast_to(bound, (n, 8)),
                    jnp.broadcast_to(bound_neg, (n, 8)))

    # Closed-form 256-bit value accumulation (replaces an L-step sequential
    # loop of limb multiply-adds). Weight each accumulated digit by
    # 10^(accumulated-digits-to-its-right) — any NONZERO accumulated digit
    # has at most 38 significant accumulated digits to its right (np_before
    # < precision bounds them), so clipping the weight index at 39 only ever
    # affects zero digits. Per limb j: sum d * limb_j(10^k) over the row in
    # u64 — each term < 9*2^32 and L terms can't wrap u64 — then one 8-step
    # carry propagation normalizes back to u32 limbs. Exact, since the true
    # value < 10^39 < 2^256. The loop's per-step overflow check fires iff
    # the final magnitude exceeds the bound (appending digits only grows
    # it), so one final compare replaces it.
    acc_i32 = accumulate.astype(jnp.int32)
    vrfr = jnp.sum(acc_i32, axis=1)[:, None] - jnp.cumsum(acc_i32, axis=1)
    widx = jnp.clip(vrfr, 0, 39)
    tblW = d256.pow10_table()                       # (77, 8) u32-in-u64 limbs
    c_carry = jnp.zeros((n,), jnp.uint64)
    mag_limbs = []
    for j in range(8):
        Wj = jnp.take(tblW[:, j], widx)
        s = jnp.sum(jnp.where(accumulate, d_u * Wj, jnp.uint64(0)), axis=1)
        t = s + c_carry
        mag_limbs.append(t & jnp.uint64(0xFFFFFFFF))
        c_carry = t >> jnp.uint64(32)
    mag = jnp.stack(mag_limbs, axis=1)
    valid &= ~d256.lt_unsigned(bnd, mag)

    # ---- HALF_UP rounding with carry-digit detection -------------------------
    do_round = has_round & (round_digit >= 5)
    mag_r = d256.add_small(mag, 1)
    round_of = d256.lt_unsigned(bnd, mag_r) & do_round
    valid &= ~round_of
    was_zero = d256.is_zero(mag)
    # digit count grows iff the incremented magnitude is a power of ten
    tbl = d256.pow10_table()
    is_p10 = jnp.zeros((n,), jnp.bool_)
    for k in range(1, 40):
        is_p10 = is_p10 | d256.eq(mag_r, jnp.broadcast_to(tbl[k][None, :], (n, 8)))
    carry_grew = do_round & ~was_zero & is_p10
    mag = jnp.where(do_round[:, None], mag_r, mag)
    total_digits = nd_acc + carry_grew.astype(jnp.int64)
    np_final = np_final + carry_grew.astype(jnp.int64)
    dl = dl + carry_grew.astype(jnp.int64)
    rounding_digits = carry_grew.astype(jnp.int64)

    # ---- zero padding & precision checks (cast_string.cu:538-585) ------------
    sig_preceding_zeros = jnp.maximum(0, -dl)
    if cudf_scale > 0:
        zeros_to_decimal = jnp.maximum(0, dl - total_digits - cudf_scale)
    else:
        zeros_to_decimal = jnp.maximum(0, dl - total_digits)
    sig_before_decimal = sig_before_in_string + zeros_to_decimal + rounding_digits
    valid &= (precision + cudf_scale) >= sig_before_decimal

    # pad up to the decimal point; >39 steps always overflows 38-digit storage
    valid &= zeros_to_decimal <= 39

    def pad_step(i, carry):
        mag, vok, npd = carry
        active = i < zeros_to_decimal
        mag_new = d256.mul_small(mag, jnp.uint64(10))
        of = d256.lt_unsigned(bnd, mag_new) & active
        mag = jnp.where((active & ~of)[:, None], mag_new, mag)
        return mag, vok & ~of, npd + active.astype(jnp.int64)

    mag, vok, np_final = jax.lax.fori_loop(0, 40, pad_step,
                                           (mag, valid, np_final))
    valid &= vok

    digits_after_decimal = np_final - sig_before_decimal + sig_preceding_zeros
    digits_needed = jnp.minimum(precision - sig_before_decimal,
                                jnp.int64(-cudf_scale))
    pad2 = jnp.maximum(0, digits_needed - digits_after_decimal)
    valid &= pad2 <= 39

    def pad2_step(i, carry):
        mag, vok = carry
        active = i < pad2
        mag_new = d256.mul_small(mag, jnp.uint64(10))
        of = d256.lt_unsigned(bnd, mag_new) & active
        mag = jnp.where((active & ~of)[:, None], mag_new, mag)
        return mag, vok & ~of

    mag, vok = jax.lax.fori_loop(0, 40, pad2_step, (mag, valid))
    valid &= vok

    # ---- assemble output ------------------------------------------------------
    signed = jnp.where(positive[:, None], mag, d256.negate(mag))
    if out_type.kind == Kind.DECIMAL128:
        data = d256.to_i128_limbs(signed)
    else:
        lo = (signed[:, 0] | (signed[:, 1] << jnp.uint64(32))).astype(jnp.int64)
        data = lo.astype(out_type.storage_dtype())
    out = Column(dtype=out_type, length=n, data=data, validity=valid)
    if ansi_mode:
        _raise_first_error(col, valid_in & ~valid)
    return out
