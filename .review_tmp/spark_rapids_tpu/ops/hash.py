"""Spark-exact row hashes: murmur3_32 (Spark variant) and xxhash64 (Spark variant).

Re-design of the reference's hash kernels for the TPU/XLA substrate
(reference: src/main/cpp/src/murmur_hash.cuh:36-207, murmur_hash.cu:64-207,
xxhash64.cu:42-274, hash.cuh:33-103). Where the reference runs one CUDA
thread per row, here every step is a dense vectorized op over all rows (VPU
lanes), with variable-length byte streams handled as a masked scan over the
padded (rows, max_len) char matrix.

Spark-specific semantics preserved exactly:
- column chaining: the hash of column k seeds column k+1; the whole-row seed
  starts the chain (murmur_hash.cu:64-85, xxhash64.cu:277-330);
- null element -> the seed passes through unchanged;
- murmur tail bytes processed one at a time as *signed* chars — NOT standard
  MurmurHash3 (murmur_hash.cuh:74-93);
- bool/int8/int16 promote to 4 bytes sign-extended; decimal32/64 promote to
  8 bytes sign-extended (murmur_hash.cuh:135-167, 186-199);
- floats: murmur normalizes NaNs only (so -0.0 != +0.0, Spark < 3.2
  behavior); xxhash64 normalizes NaNs *and* zeros (hash.cuh:33-52);
- decimal128 hashes the minimal big-endian two's-complement byte form of
  java.math.BigDecimal.unscaledValue().toByteArray() (hash.cuh:54-103);
- murmur supports struct/list nesting by flattening + chaining; LIST-of-
  STRUCT rejected (murmur_hash.cu:163-183); xxhash64 rejects nested
  (Hash.java:78).
"""
from __future__ import annotations

from typing import List, Sequence, Union

import jax
import jax.numpy as jnp

from .. import dtypes
from ..columnar import Column, Table
from ..dtypes import Kind

DEFAULT_XXHASH64_SEED = 42  # Hash.java:26

# ---------------------------------------------------------------------------
# murmur3_32 primitives (uint32 lane math)
# ---------------------------------------------------------------------------
_MM_C1 = jnp.uint32(0xCC9E2D51)
_MM_C2 = jnp.uint32(0x1B873593)
_MM_C3 = jnp.uint32(0xE6546B64)


def _rotl32(x, r):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def _mm_round(h, k1):
    k1 = k1 * _MM_C1
    k1 = _rotl32(k1, 15)
    k1 = k1 * _MM_C2
    h = h ^ k1
    h = _rotl32(h, 13)
    return h * jnp.uint32(5) + _MM_C3


def _mm_fmix(h):
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def _mm_fixed(seed_u32, words, nbytes: int):
    """Hash rows of a fixed word count. words: (n, k) uint32 little-endian."""
    h = seed_u32
    for w in range(words.shape[1]):
        h = _mm_round(h, words[:, w])
    h = h ^ jnp.uint32(nbytes)
    return _mm_fmix(h)


def _le_words(padded_u8):
    """(n, L) uint8 -> (n, L//4) uint32 little-endian words."""
    n, L = padded_u8.shape
    b = padded_u8.reshape(n, L // 4, 4).astype(jnp.uint32)
    return b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24)


def _mm_var(seed_u32, padded_u8, lens):
    """Hash variable-length byte rows (Spark murmur: 4-byte blocks then
    per-byte signed-char tail)."""
    n, L = padded_u8.shape
    assert L % 4 == 0
    words = _le_words(padded_u8)
    lens = lens.astype(jnp.int32)
    nblocks = lens // 4

    def block_step(i, h):
        w = jax.lax.dynamic_slice_in_dim(words, i, 1, axis=1)[:, 0]
        return jnp.where(i < nblocks, _mm_round(h, w), h)

    h = jax.lax.fori_loop(0, L // 4, block_step, seed_u32)

    # Spark tail: remaining 0-3 bytes, each as a sign-extended char
    # (murmur_hash.cuh:74-93).
    tail_start = nblocks * 4
    for j in range(3):
        pos = tail_start + j
        byte = jnp.take_along_axis(
            padded_u8, jnp.clip(pos, 0, L - 1)[:, None], axis=1)[:, 0]
        k1 = byte.astype(jnp.int8).astype(jnp.int32).astype(jnp.uint32)
        h = jnp.where(pos < lens, _mm_round(h, k1), h)

    h = h ^ lens.astype(jnp.uint32)
    return _mm_fmix(h)


# ---------------------------------------------------------------------------
# xxhash64 primitives (uint64 lane math; XLA:TPU emulates u64 correctly)
# ---------------------------------------------------------------------------
_XX_P1 = jnp.uint64(0x9E3779B185EBCA87)
_XX_P2 = jnp.uint64(0xC2B2AE3D27D4EB4F)
_XX_P3 = jnp.uint64(0x165667B19E3779F9)
_XX_P4 = jnp.uint64(0x85EBCA77C2B2AE63)
_XX_P5 = jnp.uint64(0x27D4EB2F165667C5)


def _rotl64(x, r):
    return (x << jnp.uint64(r)) | (x >> jnp.uint64(64 - r))


def _xx_merge_round(h, v):
    v = v * _XX_P2
    v = _rotl64(v, 31)
    v = v * _XX_P1
    h = h ^ v
    return h * _XX_P1 + _XX_P4


def _xx_round8(h, w64):
    k1 = w64 * _XX_P2
    k1 = _rotl64(k1, 31)
    k1 = k1 * _XX_P1
    h = h ^ k1
    return _rotl64(h, 27) * _XX_P1 + _XX_P4


def _xx_round4(h, w32_u64):
    h = h ^ (w32_u64 * _XX_P1)
    return _rotl64(h, 23) * _XX_P2 + _XX_P3


def _xx_round1(h, byte_u64):
    h = h ^ (byte_u64 * _XX_P5)
    return _rotl64(h, 11) * _XX_P1


def _xx_finalize(h):
    h = h ^ (h >> jnp.uint64(33))
    h = h * _XX_P2
    h = h ^ (h >> jnp.uint64(29))
    h = h * _XX_P3
    h = h ^ (h >> jnp.uint64(32))
    return h


def _xx_fixed(seed_u64, words64, nbytes: int):
    """nbytes in (4, 8, 16): small fixed-width path (xxhash64.cu:108-183).
    words64: list of (n,) uint64 (for nbytes==4 a zero-extended u32)."""
    h = seed_u64 + _XX_P5 + jnp.uint64(nbytes)
    rem = nbytes
    for w in words64:
        if rem >= 8:
            h = _xx_round8(h, w)
            rem -= 8
        else:
            h = _xx_round4(h, w)
            rem -= 4
    return _xx_finalize(h)


def _xx_var(seed_u64, padded_u8, lens):
    """Variable-length xxhash64 over padded rows: 32-byte stripes, then
    8/4/1-byte tail chunks, all masked per row (xxhash64.cu:78-186)."""
    n, L = padded_u8.shape
    Lp = ((L + 31) // 32) * 32
    if Lp != L:
        padded_u8 = jnp.pad(padded_u8, ((0, 0), (0, Lp - L)))
        L = Lp
    w32 = _le_words(padded_u8).astype(jnp.uint64)          # (n, L//4)
    w64 = w32[:, 0::2] | (w32[:, 1::2] << jnp.uint64(32))  # (n, L//8)
    lens = lens.astype(jnp.int64)
    nbytes = lens

    nstripes = (nbytes // 32).astype(jnp.int32)

    def stripe_step(i, vs):
        v1, v2, v3, v4 = vs
        base = i * 4
        active = i < nstripes

        def upd(v, k):
            w = jax.lax.dynamic_slice_in_dim(w64, base + k, 1, axis=1)[:, 0]
            nv = v + w * _XX_P2
            nv = _rotl64(nv, 31) * _XX_P1
            return jnp.where(active, nv, v)

        return (upd(v1, 0), upd(v2, 1), upd(v3, 2), upd(v4, 3))

    v1 = seed_u64 + _XX_P1 + _XX_P2
    v2 = seed_u64 + _XX_P2
    v3 = seed_u64
    v4 = seed_u64 - _XX_P1
    v1, v2, v3, v4 = jax.lax.fori_loop(0, L // 32, stripe_step, (v1, v2, v3, v4))

    merged = _rotl64(v1, 1) + _rotl64(v2, 7) + _rotl64(v3, 12) + _rotl64(v4, 18)
    for v in (v1, v2, v3, v4):
        merged = _xx_merge_round(merged, v)
    h = jnp.where(nbytes >= 32, merged, seed_u64 + _XX_P5)
    h = h + nbytes.astype(jnp.uint64)

    offset = (nbytes // 32) * 32
    rem = nbytes % 32
    # up to three 8-byte chunks
    for j in range(3):
        pos = offset + j * 8
        active = (rem // 8) > j
        w = jnp.take_along_axis(w64, jnp.clip(pos // 8, 0, L // 8 - 1)[:, None],
                                axis=1)[:, 0]
        h = jnp.where(active, _xx_round8(h, w), h)
    offset = offset + (rem // 8) * 8
    rem = rem % 8
    # at most one 4-byte chunk
    w = jnp.take_along_axis(w32, jnp.clip(offset // 4, 0, L // 4 - 1)[:, None],
                            axis=1)[:, 0]
    h = jnp.where(rem >= 4, _xx_round4(h, w), h)
    offset = offset + (rem // 4) * 4
    rem = rem % 4
    # up to three single bytes
    for j in range(3):
        pos = offset + j
        byte = jnp.take_along_axis(padded_u8, jnp.clip(pos, 0, L - 1)[:, None],
                                   axis=1)[:, 0].astype(jnp.uint64)
        h = jnp.where(rem > j, _xx_round1(h, byte), h)
    return _xx_finalize(h)


# ---------------------------------------------------------------------------
# element byte representations
# ---------------------------------------------------------------------------
def _canonical_nan(x):
    """normalize_nans (hash.cuh:33-40): any NaN -> quiet NaN canonical bits."""
    return jnp.where(jnp.isnan(x), jnp.asarray(jnp.nan, dtype=x.dtype), x)


def f64_bits_u64(x):
    """IEEE-754 bits of float64 as (n,) uint64, computed
    arithmetically: XLA:TPU's x64 rewriter cannot lower any f64 bitcast /
    frexp / signbit, but its emulated f64 *arithmetic* is exact, and every
    step here is a power-of-two scale or exact subtract. NaNs must already
    be canonicalized by the caller.

    Known platform limits (documented deviations, not bugs in this routine):
    - XLA flushes f64 subnormals to zero (DAZ), so subnormal inputs hash as
      +/-0.0;
    - the TPU device emulates f64 as an f32 pair (double-double): full 53-bit
      precision but f32 exponent range, so |x| > ~1e38 degrades on-device
      (host/CPU execution is exact over the full range)."""
    neg = (x < 0) | ((x == 0) & (1.0 / x < 0))  # arithmetic signbit (catches -0.0)
    a = jnp.abs(x)
    is_zero = a == 0
    is_inf = jnp.isinf(a)
    # normalize a into [1, 2) by exact power-of-two scaling; e = unbiased exponent
    y = jnp.where(is_zero | is_inf, 1.0, a)
    e = jnp.zeros(x.shape, jnp.int32)
    # two passes: one pass scales by at most 2^1023, deep subnormals need 2^1074
    for _ in range(2):
        for k in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
            big = y >= (2.0 ** k)
            y = jnp.where(big, y * (2.0 ** -k), y)
            e = e + jnp.where(big, k, 0)
            # scaling up by 2**k is applied only when it does not overshoot
            small = y < 1.0
            ynew = y * (2.0 ** k)
            ok = ynew < 2.0
            y = jnp.where(small & ok, ynew, y)
            e = e - jnp.where(small & ok, k, 0)
    biased = e + 1023
    normal = biased >= 1
    # normal: mantissa = (y - 1) * 2^52 (exact); subnormal: |x| * 2^1074 done
    # in two exact steps to stay in range
    mant_n = ((y - 1.0) * 2.0 ** 52).astype(jnp.int64)
    mant_s = ((a * 2.0 ** 537) * 2.0 ** 537).astype(jnp.int64)
    mant = jnp.where(normal, mant_n, mant_s)
    expf = jnp.where(normal, biased, 0).astype(jnp.int64)
    expf = jnp.where(is_inf, 0x7FF, expf)
    mant = jnp.where(is_inf | is_zero, 0, mant)
    expf = jnp.where(is_zero, 0, expf)
    bits = (jnp.where(neg, jnp.int64(1), 0) << 63) | (expf << 52) | mant
    return bits.astype(jnp.uint64)


def _normalize_zeros(x):
    """normalize_nans_and_zeros zero half (hash.cuh:43-52): -0.0 -> +0.0."""
    return jnp.where(x == 0, jnp.zeros_like(x), x)


def _encode_fixed_u64(col: Column, normalize_zero: bool):
    """Return ((n,) uint64 LE value, nbytes in (4, 8)) for a fixed-width column.

    Spark's byte forms: bool/int8/int16 sign-extend to 4 bytes, decimal32/64
    sign-extend to 8 (murmur_hash.cuh:135-167, 186-199); floats normalize
    NaNs (and zeros for xxhash64, hash.cuh:33-52)."""
    k = col.dtype.kind
    d = col.data
    if k in (Kind.BOOL, Kind.INT8, Kind.INT16, Kind.INT32, Kind.DATE32):
        return d.astype(jnp.int32).astype(jnp.uint32).astype(jnp.uint64), 4
    if k in (Kind.INT64, Kind.TIMESTAMP_US):
        return d.astype(jnp.uint64), 8
    if k in (Kind.DECIMAL32, Kind.DECIMAL64):
        return d.astype(jnp.int64).astype(jnp.uint64), 8
    if k == Kind.FLOAT32:
        x = _canonical_nan(d)
        if normalize_zero:
            x = _normalize_zeros(x)
        return jax.lax.bitcast_convert_type(x, jnp.uint32).astype(jnp.uint64), 4
    if k == Kind.FLOAT64:
        x = d
        if normalize_zero:
            x = _normalize_zeros(x)
        bits = f64_bits_u64(x)
        # canonical quiet-NaN bits substituted in integer domain (f64 NaN
        # arithmetic paths can't produce them portably)
        return jnp.where(jnp.isnan(x), jnp.uint64(0x7FF8000000000000), bits), 8
    raise TypeError(f"unsupported fixed-width dtype {col.dtype}")


def _words_u32(u64: jnp.ndarray, nbytes: int) -> jnp.ndarray:
    """(n,) uint64 -> (n, nbytes//4) uint32 little-endian words."""
    lo = (u64 & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    if nbytes == 4:
        return lo[:, None]
    return jnp.stack([lo, (u64 >> jnp.uint64(32)).astype(jnp.uint32)], axis=1)


def java_bigdecimal_bytes(limbs_u32: jnp.ndarray):
    """decimal128 -> (big-endian padded (n,16) uint8, (n,) length): the minimal
    two's-complement byte form java.math.BigDecimal.unscaledValue().toByteArray()
    produces (hash.cuh:54-103), vectorized over rows."""
    n = limbs_u32.shape[0]
    # little-endian bytes (n, 16)
    shifts = jnp.arange(4, dtype=jnp.uint32) * 8
    le = ((limbs_u32[:, :, None] >> shifts[None, None, :]) &
          jnp.uint32(0xFF)).astype(jnp.uint8).reshape(n, 16)
    is_neg = (limbs_u32[:, 3] >> 31).astype(jnp.bool_)
    zero_byte = jnp.where(is_neg, jnp.uint8(0xFF), jnp.uint8(0x00))
    # count of redundant leading (most-significant) bytes
    rev = le[:, ::-1]
    nonzero = rev != zero_byte[:, None]
    any_nonzero = jnp.any(nonzero, axis=1)
    first_sig = jnp.where(any_nonzero, jnp.argmax(nonzero, axis=1), 16)
    length = jnp.maximum(1, 16 - first_sig).astype(jnp.int32)
    # preserve the sign bit: add a byte back if the top retained byte's sign
    # bit disagrees with the value's sign (hash.cuh:90-96)
    top = jnp.take_along_axis(le, (length - 1)[:, None], axis=1)[:, 0]
    top_bit = (top >> 7).astype(jnp.bool_)
    length = jnp.where((length < 16) & (is_neg ^ top_bit), length + 1, length)
    # reverse the first `length` LE bytes into big-endian order, zero padded
    j = jnp.arange(16, dtype=jnp.int32)[None, :]
    src = jnp.clip(length[:, None] - 1 - j, 0, 15)
    be = jnp.where(j < length[:, None],
                   jnp.take_along_axis(le, src, axis=1), jnp.uint8(0))
    return be, length


# ---------------------------------------------------------------------------
# per-column chained hashing
# ---------------------------------------------------------------------------
def _check_murmur_compat(col: Column):
    """LIST-of-STRUCT rejected (murmur_hash.cu:163-183)."""
    if col.dtype.kind == Kind.LIST:
        child = col.children[0]
        if child.dtype.kind == Kind.STRUCT:
            raise TypeError(
                "Cannot compute hash of a table with a LIST of STRUCT columns.")
        _check_murmur_compat(child)
    elif col.dtype.kind == Kind.STRUCT:
        for c in col.children:
            _check_murmur_compat(c)


def _leaf_of_list(col: Column):
    """Descend LIST nesting to the leaf column, composing offsets so that
    row i's leaf span is [start[i], end[i]) (murmur_hash.cu:118-131)."""
    starts = col.offsets[:-1]
    ends = col.offsets[1:]
    cur = col.children[0]
    while cur.dtype.kind == Kind.LIST:
        starts = jnp.take(cur.offsets, starts)
        ends = jnp.take(cur.offsets, ends)
        cur = cur.children[0]
    return cur, starts, ends


def _var_bytes(col: Column, pad_to):
    """Padded byte matrix + lengths for variable-byte-length element types."""
    if col.dtype.is_string:
        return col.padded_chars(pad_to)
    return java_bigdecimal_bytes(col.data)  # decimal128: at most 16 bytes


def _murmur_element(col: Column, h: jnp.ndarray, parent_valid,
                    pad_to=None, max_span=None) -> jnp.ndarray:
    """Hash one column's elements with per-row seed h; nulls pass h through.

    `pad_to` (string char-matrix width) and `max_span` (max flattened list
    length) may be passed as static bounds so the whole hash traces under
    jax.jit; left as None they are computed from the data (host sync)."""
    valid = col.null_mask if parent_valid is None else (col.null_mask & parent_valid)
    k = col.dtype.kind
    if k == Kind.STRUCT:
        # decomposed struct: chain over children; null struct nulls its fields
        for c in col.children:
            h = _murmur_element(c, h, valid, pad_to, max_span)
        return h
    if k == Kind.LIST:
        leaf, starts, ends = _leaf_of_list(col)
        if max_span is None:
            span = ends - starts
            max_span = int(jnp.max(span)) if col.length else 0
        if leaf.dtype.is_string or leaf.dtype.kind == Kind.DECIMAL128:
            padded, lens = _var_bytes(leaf, pad_to)
            elem_valid = leaf.null_mask

            def body(j, hh):
                idx = jnp.clip(starts + j, 0, max(leaf.length - 1, 0))
                active = ((starts + j) < ends) & valid & jnp.take(elem_valid, idx)
                hv = _mm_var(hh, jnp.take(padded, idx, axis=0), jnp.take(lens, idx))
                return jnp.where(active, hv, hh)
        else:
            u64, nbytes = _encode_fixed_u64(leaf, normalize_zero=False)
            words = _words_u32(u64, nbytes)
            elem_valid = leaf.null_mask

            def body(j, hh):
                idx = jnp.clip(starts + j, 0, max(leaf.length - 1, 0))
                active = ((starts + j) < ends) & valid & jnp.take(elem_valid, idx)
                hv = _mm_fixed(hh, jnp.take(words, idx, axis=0), nbytes)
                return jnp.where(active, hv, hh)

        return jax.lax.fori_loop(0, max_span, body, h)
    if k == Kind.STRING or k == Kind.DECIMAL128:
        padded, lens = _var_bytes(col, pad_to)
        return jnp.where(valid, _mm_var(h, padded, lens), h)
    u64, nbytes = _encode_fixed_u64(col, normalize_zero=False)
    return jnp.where(valid, _mm_fixed(h, _words_u32(u64, nbytes), nbytes), h)


def _as_columns(table) -> List[Column]:
    if isinstance(table, Table):
        return list(table.columns)
    if isinstance(table, Column):
        return [table]
    return list(table)


def murmur_hash3_32(table: Union[Table, Column, Sequence[Column]],
                    seed: int = 0, pad_to=None, max_span=None) -> Column:
    """Spark's 32-bit murmur3 hash of each row (Hash.java:40-58 parity).

    Pass static `pad_to` / `max_span` bounds to make the call traceable
    under an enclosing jax.jit (otherwise they are measured from the data)."""
    cols = _as_columns(table)
    if len(cols) < 1:
        raise ValueError("Murmur3 hashing requires at least 1 column of input")
    for c in cols:
        _check_murmur_compat(c)
    n = cols[0].length
    h = jnp.full((n,), jnp.uint32(seed & 0xFFFFFFFF))
    for c in cols:
        h = _murmur_element(c, h, None, pad_to, max_span)
    return Column(dtype=dtypes.INT32, length=n, data=h.astype(jnp.int32))


def _xxhash_element(col: Column, h: jnp.ndarray, pad_to=None) -> jnp.ndarray:
    valid = col.null_mask
    k = col.dtype.kind
    if col.dtype.is_nested:
        raise TypeError("xxhash64 does not support nested types")  # Hash.java:78
    if k == Kind.STRING or k == Kind.DECIMAL128:
        padded, lens = _var_bytes(col, pad_to)
        return jnp.where(valid, _xx_var(h, padded, lens), h)
    u64, nbytes = _encode_fixed_u64(col, normalize_zero=True)
    return jnp.where(valid, _xx_fixed(h, [u64], nbytes), h)


def xxhash64(table: Union[Table, Column, Sequence[Column]],
             seed: int = DEFAULT_XXHASH64_SEED, pad_to=None) -> Column:
    """Spark's xxhash64 hash of each row, seed 42 default (Hash.java:60-86)."""
    cols = _as_columns(table)
    if len(cols) < 1:
        raise ValueError("xxhash64 hashing requires at least 1 column of input")
    n = cols[0].length
    h = jnp.full((n,), jnp.uint64(seed & 0xFFFFFFFFFFFFFFFF))
    for c in cols:
        h = _xxhash_element(c, h, pad_to)
    return Column(dtype=dtypes.INT64, length=n, data=h.astype(jnp.int64))
