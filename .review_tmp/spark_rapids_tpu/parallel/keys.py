"""Typed key codec for the distributed relational ops.

Round-1 limitation: the mesh ops shipped int64 keys only, while the local
path (`ops/sort.py::_key_operands`) already ordered any dtype. This module
closes that gap the TPU way — not by teaching every SPMD body about string
layouts, but by encoding ANY key column into a fixed tuple of (n,) int64
**key words** that flow through the existing exchange machinery unchanged:

- equality:  two rows are equal ⇔ their word tuples are equal
- ordering:  lexicographic int64 order over the tuple == the column's
             sort order (nulls first), so `_merge_groups`' sort-based
             grouping and the sort-merge join spans work verbatim
- decodable: the original column (values + validity) is reconstructible
             from the words — group keys / join keys come back typed

Spark-exact placement: `spark_partition_hash` reconstructs each column's
logical bytes from the words *inside the traced SPMD body* and runs the
same murmur3_32(seed 42) chain as `ops.murmur_hash3_32`, so distributed
placement matches GpuHashPartitioning exactly (Hash.java:40-58), strings
and decimal128 included.

Width rules (static, SPMD-friendly):

| dtype | words |
|---|---|
| bool/int8..64/date/timestamp/decimal32/64 | 1 (sign-extended value) |
| float32/float64 | 1 (total-order bits; NaN canonical, -0.0 → +0.0) |
| decimal128 | 2 (signed hi, bias-flipped lo) |
| string | max_bytes/8 (+1 length word), big-endian bias-flipped |
| any nullable column | +1 leading null-flag word (nulls first, data zeroed) |

Strings require a static `max_bytes` (the SPMD program shape); pick it per
pipeline the way the local string kernels pick `pad_to` buckets
(columnar/column.py `padded_chars`).
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from ..columnar.column import Column, strings_from_padded
from ..dtypes import DType, Kind

# XOR with the sign bit turns unsigned u64 order into signed int64 order
_SIGN64 = jnp.uint64(1 << 63)

_ONE_WORD_KINDS = (Kind.BOOL, Kind.INT8, Kind.INT16, Kind.INT32, Kind.INT64,
                   Kind.DATE32, Kind.TIMESTAMP_US, Kind.TIMESTAMP_S,
                   Kind.TIMESTAMP_MS, Kind.DECIMAL32, Kind.DECIMAL64)


@dataclasses.dataclass(frozen=True)
class KeySpec:
    """Static per-column encoding recipe (part of the SPMD program shape)."""
    dtype: DType
    n_words: int          # data words (excluding the null-flag word)
    nullable: bool
    max_bytes: int = 0    # strings only: padded byte width (multiple of 8)

    @property
    def total_words(self) -> int:
        return self.n_words + (1 if self.nullable else 0)


def _u64_to_word(u: jnp.ndarray) -> jnp.ndarray:
    """uint64 → int64 whose signed order equals the unsigned order."""
    return (u ^ _SIGN64).astype(jnp.int64)


def _word_to_u64(w: jnp.ndarray) -> jnp.ndarray:
    return w.astype(jnp.uint64) ^ _SIGN64


def _words_from_limbs(limbs: jnp.ndarray) -> List[jnp.ndarray]:
    """(n, 4) LE u32 decimal128 limbs → [signed hi word, bias-flipped lo]."""
    u = limbs.astype(jnp.uint64)
    hi = (u[:, 3] << jnp.uint64(32)) | u[:, 2]
    lo = (u[:, 1] << jnp.uint64(32)) | u[:, 0]
    return [hi.astype(jnp.int64), _u64_to_word(lo)]


def _limbs_from_words(hi_word: jnp.ndarray, lo_word: jnp.ndarray) -> jnp.ndarray:
    hi = hi_word.astype(jnp.uint64)
    lo = _word_to_u64(lo_word)
    return jnp.stack(
        [(lo & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
         (lo >> jnp.uint64(32)).astype(jnp.uint32),
         (hi & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
         (hi >> jnp.uint64(32)).astype(jnp.uint32)], axis=1)


def _float_order_word(col: Column) -> jnp.ndarray:
    """Total-order int64 word for float columns: NaNs canonical (one group),
    -0.0 folded into +0.0 (Spark groupby equality), order-preserving."""
    from ..ops.hash import _canonical_nan, _normalize_zeros, f64_bits_u64
    x = _normalize_zeros(_canonical_nan(col.data))
    if col.dtype.kind == Kind.FLOAT32:
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32).astype(jnp.uint64) \
            << jnp.uint64(32)
    else:
        # f64_bits_u64 needs NaN bits substituted in the integer domain
        # (same contract as ops/hash.py's murmur encoding)
        bits = jnp.where(jnp.isnan(x), jnp.uint64(0x7FF8000000000000),
                         f64_bits_u64(x))
    # IEEE total order: negative floats reverse, positive floats offset
    neg = (bits >> jnp.uint64(63)) != 0
    tot = jnp.where(neg, ~bits, bits | _SIGN64)
    return _u64_to_word(tot)


def _float_from_word(w: jnp.ndarray, kind: Kind) -> jnp.ndarray:
    tot = _word_to_u64(w)
    neg = (tot >> jnp.uint64(63)) == 0
    bits = jnp.where(neg, ~tot, tot & ~_SIGN64)
    if kind == Kind.FLOAT32:
        return jax.lax.bitcast_convert_type(
            (bits >> jnp.uint64(32)).astype(jnp.uint32), jnp.float32)
    from ..ops.hash import f64_bits_u64  # noqa: F401 (encode counterpart)
    return _f64_from_bits(bits)


def _f64_from_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """Arithmetic IEEE-754 reconstruction (no f64 bitcast on TPU — the
    inverse of ops/hash.py's f64_bits_u64)."""
    sign = (bits >> jnp.uint64(63)) != 0
    expf = ((bits >> jnp.uint64(52)) & jnp.uint64(0x7FF)).astype(jnp.int32)
    mant = (bits & jnp.uint64((1 << 52) - 1)).astype(jnp.float64)
    normal = expf >= 1
    frac = jnp.where(normal, 1.0 + mant * 2.0 ** -52, mant * 2.0 ** -52)
    e = jnp.where(normal, expf - 1023, -1022)
    # exact two-step scaling (integer exponents only — exp2 of an integer is
    # exact; a fractional exponent would round) keeps intermediates in range
    h = (e // 2).astype(jnp.float64)
    mag = frac * jnp.exp2(h) * jnp.exp2(e.astype(jnp.float64) - h)
    is_inf = (expf == 0x7FF) & (mant == 0)
    is_nan = (expf == 0x7FF) & (mant != 0)
    mag = jnp.where(is_inf, jnp.inf, mag)
    mag = jnp.where(is_nan, jnp.nan, mag)
    return jnp.where(sign, -mag, mag)


def encode_key_column(col: Column,
                      max_bytes: Optional[int] = None,
                      spec: Optional[KeySpec] = None
                      ) -> Tuple[List[jnp.ndarray], KeySpec]:
    """Encode one key column into its int64 word list + static spec.

    Pass `spec` (e.g. the other join side's) to force the layout: a
    non-null column encoded under a nullable spec gets an all-valid flag
    word, so both sides of a join produce identical word counts even when
    only one side carries nulls."""
    k = col.dtype.kind
    valid = col.null_mask
    nullable = col.validity is not None
    if spec is not None:
        if spec.dtype.kind != k:
            raise TypeError(f"spec dtype {spec.dtype} != column {col.dtype}")
        if nullable and not spec.nullable:
            raise ValueError(
                "column has nulls but the target spec is non-nullable; "
                "encode the nullable side first (its specs then force the "
                "flag word on the other side)")
        nullable = spec.nullable
        if k == Kind.STRING:
            max_bytes = spec.max_bytes
    words: List[jnp.ndarray] = []

    if k in _ONE_WORD_KINDS:
        words = [col.data.astype(jnp.int64)]
        spec = KeySpec(col.dtype, 1, nullable)
    elif k in (Kind.FLOAT32, Kind.FLOAT64):
        words = [_float_order_word(col)]
        spec = KeySpec(col.dtype, 1, nullable)
    elif k == Kind.DECIMAL128:
        words = _words_from_limbs(col.data)
        spec = KeySpec(col.dtype, 2, nullable)
    elif k == Kind.STRING:
        if max_bytes is None:
            max_bytes = max(8, col.max_string_length())
        M = 8 * math.ceil(max_bytes / 8)
        padded, lens = col.padded_chars(pad_to=M)
        padded = jnp.where(valid[:, None], padded, jnp.uint8(0))
        lens = jnp.where(valid, lens, 0)
        b = padded.reshape(padded.shape[0], M // 8, 8).astype(jnp.uint64)
        w = jnp.zeros(b.shape[:2], jnp.uint64)
        for i in range(8):                        # big-endian pack
            w = (w << jnp.uint64(8)) | b[:, :, i]
        words = [_u64_to_word(w[:, i]) for i in range(M // 8)]
        words.append(lens.astype(jnp.int64))      # prefix-equal tiebreak
        spec = KeySpec(col.dtype, M // 8 + 1, nullable, max_bytes=M)
    else:
        raise TypeError(f"unsupported distributed key dtype {col.dtype}")

    if nullable:
        # nulls first (flag 0) and their data words zeroed so all nulls are
        # one equal tuple, like the local sort's null handling
        words = [jnp.where(valid, w, jnp.int64(0)) for w in words]
        words.insert(0, valid.astype(jnp.int64))
    return words, spec


def encode_key_columns(cols: Sequence[Column],
                       max_bytes: Union[None, int, Sequence[Optional[int]]] = None,
                       specs: Optional[Sequence[KeySpec]] = None
                       ) -> Tuple[List[jnp.ndarray], List[KeySpec]]:
    """Encode several key columns; returns the flat word list + specs.

    For joins, encode one side first and pass its `specs` when encoding
    the other so both sides share one static layout:

        lw, specs = encode_key_columns(lcols, max_bytes=16)
        rw, _     = encode_key_columns(rcols, specs=specs)
    """
    if max_bytes is None or isinstance(max_bytes, int):
        max_bytes = [max_bytes] * len(cols)
    if specs is None:
        specs = [None] * len(cols)
    words: List[jnp.ndarray] = []
    out_specs: List[KeySpec] = []
    for c, mb, sp in zip(cols, max_bytes, specs):
        w, s = encode_key_column(c, mb, spec=sp)
        words.extend(w)
        out_specs.append(s)
    return words, out_specs


def decode_key_columns(words: Sequence[jnp.ndarray], specs: Sequence[KeySpec],
                       alive: Optional[jnp.ndarray] = None) -> List[Column]:
    """Rebuild typed key columns from word arrays (the inverse of encode).

    `alive` (optional bool mask, e.g. the distributed op's `valid` output)
    is folded into each column's validity so padded slots read as null —
    and their words (which carry the exchange's dead-slot sentinel) are
    zeroed first so reassembly math (string offsets) never sees them."""
    if alive is not None:
        words = [jnp.where(alive, w, jnp.int64(0)) for w in words]
    cols: List[Column] = []
    i = 0
    for spec in specs:
        validity = None
        if spec.nullable:
            validity = words[i].astype(jnp.bool_)
            i += 1
        if alive is not None:
            base = validity if validity is not None else True
            validity = jnp.logical_and(base, alive)
        data_words = words[i:i + spec.n_words]
        i += spec.n_words
        n = data_words[0].shape[0]
        k = spec.dtype.kind
        if k in _ONE_WORD_KINDS:
            data = data_words[0].astype(spec.dtype.storage_dtype())
            cols.append(Column(dtype=spec.dtype, length=n, data=data,
                               validity=validity))
        elif k in (Kind.FLOAT32, Kind.FLOAT64):
            cols.append(Column(dtype=spec.dtype, length=n,
                               data=_float_from_word(data_words[0], k),
                               validity=validity))
        elif k == Kind.DECIMAL128:
            limbs = _limbs_from_words(data_words[0], data_words[1])
            cols.append(Column(dtype=spec.dtype, length=n, data=limbs,
                               validity=validity))
        elif k == Kind.STRING:
            W = spec.n_words - 1
            lens = jnp.clip(data_words[-1], 0, spec.max_bytes).astype(jnp.int32)
            padded = _unpack_string_words(data_words[:W], spec.max_bytes)
            v = validity
            cols.append(strings_from_padded(padded, lens, v))
        else:
            raise TypeError(f"unsupported key spec {spec}")
    return cols


def _unpack_string_words(wordlist: Sequence[jnp.ndarray],
                         M: int) -> jnp.ndarray:
    """Word list → (n, M) uint8 padded char matrix (big-endian unpack)."""
    cols8 = []
    for w in wordlist:
        u = _word_to_u64(w)
        for shift in range(56, -1, -8):
            cols8.append(((u >> jnp.uint64(shift)) &
                          jnp.uint64(0xFF)).astype(jnp.uint8))
    return jnp.stack(cols8, axis=1)[:, :M]


def keys_null_mask(words: Sequence[jnp.ndarray],
                   specs: Sequence[KeySpec]) -> jnp.ndarray:
    """(n,) bool, True where ANY key column is null. Equi-join semantics:
    a NULL key never matches (Spark `l.k = r.k` is never true on NULL), so
    the keyed joins exclude these rows from matching — unlike groupby,
    where nulls form one group. Dead exchange slots carry non-zero
    sentinel words and read as not-null; they are excluded by the alive
    masks instead."""
    null = None
    i = 0
    for spec in specs:
        if spec.nullable:
            col_null = words[i] == 0
            null = col_null if null is None else (null | col_null)
        i += spec.total_words
    if null is None:
        return jnp.zeros(words[0].shape, jnp.bool_)
    return null


def spark_partition_hash(words: Sequence[jnp.ndarray],
                         specs: Sequence[KeySpec]) -> jnp.ndarray:
    """Spark murmur3_32(seed 42) of the key tuple, straight off the words —
    the exact GpuHashPartitioning hash (Hash.java:40-58), computable inside
    a traced SPMD body (all shapes static). Placement therefore matches what
    the Spark plugin would compute on the same rows. (One documented
    deviation: float keys were normalized at encode per Spark's SPARK-26021
    grouping rule, so -0.0 hashes as +0.0 here.)

    Null rows pass the seed through unchanged, like `_murmur_element`."""
    from ..ops import hash as H
    # seed derived from the data (not jnp.full) so that under shard_map it
    # carries the same varying mesh axis as the words — a replicated
    # constant seed trips fori_loop's carry-type check inside _mm_var
    h = (words[0] * 0).astype(jnp.uint32) + jnp.uint32(42)
    i = 0
    for spec in specs:
        valid = None
        if spec.nullable:
            valid = words[i] != 0
            i += 1
        dw = words[i:i + spec.n_words]
        i += spec.n_words
        k = spec.dtype.kind
        if k == Kind.STRING:
            padded = _unpack_string_words(dw[:-1], spec.max_bytes)
            lens = dw[-1].astype(jnp.int32)
            hv = H._mm_var(h, padded, lens)
        elif k == Kind.DECIMAL128:
            be, lens = H.java_bigdecimal_bytes(_limbs_from_words(dw[0], dw[1]))
            hv = H._mm_var(h, be, lens)
        else:
            col = decode_key_columns(dw, [dataclasses.replace(spec,
                                                              nullable=False)])[0]
            u64, nbytes = H._encode_fixed_u64(col, normalize_zero=False)
            hv = H._mm_fixed(h, H._words_u32(u64, nbytes), nbytes)
        h = hv if valid is None else jnp.where(valid, hv, h)
    return h.astype(jnp.int32)
