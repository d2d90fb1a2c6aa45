"""Pallas TPU kernels for the fixed-width row-hash hot path.

The jnp implementations in ops/hash.py are semantically complete (strings,
nested types, decimal128); this module is the performance path for the case a
Spark plan hashes hardest — hash-partition / hash-join / hash-aggregate keys
over fixed-width columns (reference hot kernels: murmur_hash.cu:64-207,
xxhash64.cu:277-330, both one-thread-per-row CUDA).

TPU-first redesign rather than a translation:
- one `pallas_call` fuses the whole per-row chain (every column's rounds +
  finalization for BOTH hashes) in VMEM, so each input byte crosses HBM once;
- rows are laid out as (rows/128, 128) u32 *word planes* (lo/hi) so every
  step is an 8x128 VPU op — there is no 64-bit scalar unit to lean on;
- uint64 arithmetic is hand-built from u32 planes: adds via compare-carry,
  rotates via plane shifts, multiplies by the (constant) xxhash primes via
  16-bit limb partial products (TPU has no widening 32x32 multiply, so the
  limbs keep every partial product exact in u32);
- validity is a per-column u32 plane consumed as a select; columns with
  validity=None skip the plane and the select entirely (kernel specialization
  happens at trace time, like the reference's type_dispatcher but compiled
  per column-set).

Float columns are supported through the same bit-encoding helpers as the jnp
path (NaN canonicalization; xxhash additionally normalizes zeros,
hash.cuh:33-52), applied before the planes enter the kernel.

Measured (v5e-1, 10M rows x 2 int64 cols): ~3.2 ms vs ~2.8 ms for the fused
XLA path in ops/hash.py. The op is ALU-bound in u32-emulated u64 math, which
XLA already schedules well, and the pallas_call boundary forces the word
planes to materialize in HBM (Mosaic cannot de-interleave the raw little-
endian i64 pairs in-register: strided lane slices and minor-dim reshapes are
unsupported). Kept as the explicit-kernel path — it documents the layout and
wins when the planes are already split (e.g. reused across several hash
calls); the jnp path stays the default. ops/join_pallas.py is exactly that
reuse case: its hash-join build/probe kernels consume this module's word
planes (and round/fmix chain) in-kernel, with selection owned by the
kernel registry (ops/registry.py, docs/kernels.md).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import dtypes
from ..columnar import Column, Table
from ..dtypes import Kind
from .hash import (DEFAULT_XXHASH64_SEED, _canonical_nan, _normalize_zeros,
                   f64_bits_u64)

_LANES = 128
_U32 = jnp.uint32


def _u32c(v: int):
    return _U32(v & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# u64-as-two-u32-planes arithmetic
# ---------------------------------------------------------------------------
def _limbs16(c: int) -> Tuple[int, int, int, int]:
    return (c & 0xFFFF, (c >> 16) & 0xFFFF, (c >> 32) & 0xFFFF, (c >> 48) & 0xFFFF)


def _mul64_const(lo, hi, c: int):
    """(lo,hi) * c mod 2**64. Partial products of 16-bit limbs: each product
    is exact in u32, and each 16-bit accumulation column sums at most 7
    sixteen-bit terms (< 2**19), so no carry is ever lost."""
    a = (lo & _u32c(0xFFFF), lo >> _U32(16), hi & _u32c(0xFFFF), hi >> _U32(16))
    b = _limbs16(c)
    acc = [None, None, None, None]  # 16-bit columns of the result

    def add(k, term):
        acc[k] = term if acc[k] is None else acc[k] + term

    for i in range(4):
        for j in range(4 - i):
            if b[j] == 0:
                continue
            p = a[i] * _u32c(b[j])
            k = i + j
            add(k, p & _u32c(0xFFFF))
            if k + 1 < 4:
                add(k + 1, p >> _U32(16))
    z = jnp.zeros_like(lo)
    r0 = acc[0] if acc[0] is not None else z
    r1 = (acc[1] if acc[1] is not None else z) + (r0 >> _U32(16))
    r2 = (acc[2] if acc[2] is not None else z) + (r1 >> _U32(16))
    r3 = (acc[3] if acc[3] is not None else z) + (r2 >> _U32(16))
    out_lo = (r0 & _u32c(0xFFFF)) | (r1 << _U32(16))
    out_hi = (r2 & _u32c(0xFFFF)) | (r3 << _U32(16))
    return out_lo, out_hi


def _add64_const(lo, hi, c: int):
    blo, bhi = c & 0xFFFFFFFF, (c >> 32) & 0xFFFFFFFF
    s = lo + _u32c(blo)
    carry = (s < _u32c(blo)).astype(_U32)
    return s, hi + _u32c(bhi) + carry


def _rotl64(lo, hi, r: int):
    r &= 63
    if r == 0:
        return lo, hi
    if r == 32:
        return hi, lo
    if r < 32:
        return ((lo << _U32(r)) | (hi >> _U32(32 - r)),
                (hi << _U32(r)) | (lo >> _U32(32 - r)))
    r -= 32
    return ((hi << _U32(r)) | (lo >> _U32(32 - r)),
            (lo << _U32(r)) | (hi >> _U32(32 - r)))


def _xor_shr64(lo, hi, r: int):
    """h ^= h >> r for 32 <= r < 64 and 0 < r < 32."""
    if r >= 32:
        return lo ^ (hi >> _U32(r - 32)) if r > 32 else lo ^ hi, hi
    return lo ^ ((lo >> _U32(r)) | (hi << _U32(32 - r))), hi ^ (hi >> _U32(r))


# ---------------------------------------------------------------------------
# murmur3_32 (plain u32 planes)
# ---------------------------------------------------------------------------
def _mm_round(h, k1):
    k1 = k1 * _u32c(0xCC9E2D51)
    k1 = (k1 << _U32(15)) | (k1 >> _U32(17))
    k1 = k1 * _u32c(0x1B873593)
    h = h ^ k1
    h = (h << _U32(13)) | (h >> _U32(19))
    return h * _U32(5) + _u32c(0xE6546B64)


def _mm_fmix(h):
    h = h ^ (h >> _U32(16))
    h = h * _u32c(0x85EBCA6B)
    h = h ^ (h >> _U32(13))
    h = h * _u32c(0xC2B2AE35)
    return h ^ (h >> _U32(16))


# ---------------------------------------------------------------------------
# xxhash64 rounds on planes (constants match xxhash64.cu:42-56)
# ---------------------------------------------------------------------------
_P1 = 0x9E3779B185EBCA87
_P2 = 0xC2B2AE3D27D4EB4F
_P3 = 0x165667B19E3779F9
_P4 = 0x85EBCA77C2B2AE63
_P5 = 0x27D4EB2F165667C5


def _xx_fixed(seed_lo, seed_hi, wlo, whi, nbytes: int):
    """xxhash64 of one 4- or 8-byte value per row (xxhash64.cu:108-183)."""
    hlo, hhi = _add64_const(seed_lo, seed_hi, _P5 + nbytes)
    if nbytes == 8:
        klo, khi = _mul64_const(wlo, whi, _P2)
        klo, khi = _rotl64(klo, khi, 31)
        klo, khi = _mul64_const(klo, khi, _P1)
        hlo, hhi = hlo ^ klo, hhi ^ khi
        hlo, hhi = _rotl64(hlo, hhi, 27)
        hlo, hhi = _mul64_const(hlo, hhi, _P1)
        hlo, hhi = _add64_const(hlo, hhi, _P4)
    else:
        mlo, mhi = _mul64_const(wlo, jnp.zeros_like(wlo), _P1)
        hlo, hhi = hlo ^ mlo, hhi ^ mhi
        hlo, hhi = _rotl64(hlo, hhi, 23)
        hlo, hhi = _mul64_const(hlo, hhi, _P2)
        hlo, hhi = _add64_const(hlo, hhi, _P3)
    # finalize (avalanche)
    hlo, hhi = _xor_shr64(hlo, hhi, 33)
    hlo, hhi = _mul64_const(hlo, hhi, _P2)
    hlo, hhi = _xor_shr64(hlo, hhi, 29)
    hlo, hhi = _mul64_const(hlo, hhi, _P3)
    hlo, hhi = _xor_shr64(hlo, hhi, 32)
    return hlo, hhi


# ---------------------------------------------------------------------------
# plane encoding (host-of-kernel side, still inside jit)
# ---------------------------------------------------------------------------
def _planes(col: Column, normalize_zero: bool):
    """-> (lo_u32, hi_u32_or_None, nbytes). Encoding parity with
    hash.py _encode_fixed_u64 (Spark byte forms, murmur_hash.cuh:135-199)."""
    k = col.dtype.kind
    d = col.data
    if k in (Kind.BOOL, Kind.INT8, Kind.INT16, Kind.INT32, Kind.DATE32):
        return d.astype(jnp.int32).astype(_U32), None, 4
    if k in (Kind.INT64, Kind.TIMESTAMP_US, Kind.DECIMAL32, Kind.DECIMAL64):
        u = d.astype(jnp.int64).astype(jnp.uint64)
        return ((u & jnp.uint64(0xFFFFFFFF)).astype(_U32),
                (u >> jnp.uint64(32)).astype(_U32), 8)
    if k == Kind.FLOAT32:
        x = _canonical_nan(d)
        if normalize_zero:
            x = _normalize_zeros(x)
        return jax.lax.bitcast_convert_type(x, _U32), None, 4
    if k == Kind.FLOAT64:
        x = _normalize_zeros(d) if normalize_zero else d
        u = jnp.where(jnp.isnan(d), jnp.uint64(0x7FF8000000000000),
                      f64_bits_u64(x))
        return ((u & jnp.uint64(0xFFFFFFFF)).astype(_U32),
                (u >> jnp.uint64(32)).astype(_U32), 8)
    raise TypeError(f"pallas row hash: unsupported dtype {col.dtype}")


def _to_tiles(x, n_pad, lanes: int = _LANES, fill=0):
    """Pad a flat (n,) array to n_pad rows and tile it (n_pad/lanes,
    lanes) — the one word-plane layout transform shared by every Pallas
    module here (join_pallas, topk_pallas, select_pallas); `fill` is the
    padding value (topk pads with its sentinel)."""
    x = jnp.pad(x, (0, n_pad - x.shape[0]), constant_values=fill)
    return x.reshape(n_pad // lanes, lanes)


def _u16_halves(w) -> Tuple:
    """u32 word -> (lo16, hi16) as f32 — the split that keeps one-hot MXU
    gathers bit-exact (a single <=16-bit term per product fits the f32
    mantissa). Shared by the join/select compaction kernels."""
    # via int32: Mosaic has no uint32 -> float32 cast (both halves < 2^16)
    return ((w & _u32c(0xFFFF)).astype(jnp.int32).astype(jnp.float32),
            (w >> _U32(16)).astype(jnp.int32).astype(jnp.float32))


def _exact_dot(a, b):
    """f32 matmul at full precision. The one-hot gathers of the join/select
    kernels carry 16-bit integers through the MXU; at the default precision
    Mosaic feeds it bf16 passes and values above 2^8 come back rounded (seen
    on the v5e: every compacted row wrong), so the products are pinned to
    Precision.HIGHEST."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _pack_inputs(cols: Sequence[Column], normalize_zero: bool, n: int,
                 block_rows: int):
    """Flat list of (M, 128) u32 plane arrays (each its own ref — stacking
    them would cost an extra HBM copy of every input) + static layout of
    (nbytes, has_nulls, plane_count) per column."""
    n_pad = max(block_rows, ((n + block_rows - 1) // block_rows) * block_rows)
    arrays, layout = [], []
    for c in cols:
        lo, hi, nbytes = _planes(c, normalize_zero)
        planes = [_to_tiles(lo, n_pad)]
        if hi is not None:
            planes.append(_to_tiles(hi, n_pad))
        has_nulls = c.validity is not None
        if has_nulls:
            planes.append(_to_tiles(c.validity.astype(_U32), n_pad))
        arrays.extend(planes)
        layout.append((nbytes, has_nulls, len(planes)))
    return arrays, layout, n_pad


def _hash_kernel_body(layout, mm_seed, xx_seed, emit_mm, emit_xx,
                      in_refs, out_refs):
    shape = in_refs[0].shape  # (TM, 128)
    if emit_mm:
        mh = jnp.full(shape, _u32c(mm_seed))
    if emit_xx:
        xlo = jnp.full(shape, _u32c(xx_seed))
        xhi = jnp.full(shape, _u32c(xx_seed >> 32))
    p = 0
    for (nbytes, has_nulls, nplanes) in layout:
        lo = in_refs[p][...]
        hi = in_refs[p + 1][...] if nbytes == 8 else None
        valid = None
        if has_nulls:
            valid = in_refs[p + nplanes - 1][...] != _U32(0)
        p += nplanes
        if emit_mm:
            nh = _mm_round(mh, lo)
            if nbytes == 8:
                nh = _mm_round(nh, hi)
            nh = _mm_fmix(nh ^ _U32(nbytes))
            mh = jnp.where(valid, nh, mh) if has_nulls else nh
        if emit_xx:
            nlo, nhi = _xx_fixed(xlo, xhi, lo, hi, nbytes)
            if has_nulls:
                xlo = jnp.where(valid, nlo, xlo)
                xhi = jnp.where(valid, nhi, xhi)
            else:
                xlo, xhi = nlo, nhi
    i = 0
    if emit_mm:
        out_refs[i][...] = mh.astype(jnp.int32)
        i += 1
    if emit_xx:
        out_refs[i][0] = xlo
        out_refs[i][1] = xhi


def _as_columns(table) -> List[Column]:
    if isinstance(table, Table):
        return list(table.columns)
    if isinstance(table, Column):
        return [table]
    return list(table)


def supports(table) -> bool:
    """True if every column is a fixed-width type this kernel handles."""
    ok = (Kind.BOOL, Kind.INT8, Kind.INT16, Kind.INT32, Kind.DATE32,
          Kind.INT64, Kind.TIMESTAMP_US, Kind.DECIMAL32, Kind.DECIMAL64,
          Kind.FLOAT32, Kind.FLOAT64)
    return all(c.dtype.kind in ok for c in _as_columns(table))


def murmur_hash3_32_pallas(table, seed: int = 0, block_rows: int = 128 * 128,
                           interpret: Optional[bool] = None) -> Column:
    """Spark murmur3_32 row hash, fused Pallas path (fixed-width columns)."""
    cols = _as_columns(table)
    if not cols:
        raise ValueError("Murmur3 hashing requires at least 1 column of input")
    # murmur does NOT normalize float zeros (Spark < 3.2 behavior,
    # murmur_hash.cuh:112-133)
    [col] = _run_custom(cols, mm_seed=seed & 0xFFFFFFFF, xx_seed=None,
                        normalize_zero=False, block_rows=block_rows,
                        interpret=interpret)
    return col


def _run_custom(cols, mm_seed, xx_seed, normalize_zero, block_rows, interpret):
    # index_map constants are written `i - i` (not 0): under x64 a literal 0
    # traces as i64 and Mosaic rejects the mixed (i64, i32, i64) index tuple
    if block_rows < _LANES or block_rows % _LANES:
        raise ValueError(f"block_rows must be a multiple of {_LANES}, "
                         f"got {block_rows}")
    n = cols[0].length
    if any(c.length != n for c in cols):
        # plain-list inputs bypass Table validation; a short column would
        # otherwise silently hash its zero padding
        raise ValueError("all hashed columns must have equal length")
    arrays, layout, n_pad = _pack_inputs(cols, normalize_zero, n, block_rows)
    M = n_pad // _LANES
    TM = block_rows // _LANES
    emit_mm, emit_xx = mm_seed is not None, xx_seed is not None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def kernel(*refs):
        _hash_kernel_body(layout, mm_seed or 0, xx_seed or 0, emit_mm, emit_xx,
                          refs[:len(arrays)], refs[len(arrays):])

    in_specs = [pl.BlockSpec((TM, _LANES), lambda i: (i, i - i),
                             memory_space=pltpu.VMEM) for _ in arrays]
    out_shape, out_specs = [], []
    if emit_mm:
        out_shape.append(jax.ShapeDtypeStruct((M, _LANES), jnp.int32))
        out_specs.append(pl.BlockSpec((TM, _LANES), lambda i: (i, i - i),
                                      memory_space=pltpu.VMEM))
    if emit_xx:
        out_shape.append(jax.ShapeDtypeStruct((2, M, _LANES), _U32))
        out_specs.append(pl.BlockSpec((2, TM, _LANES), lambda i: (i - i, i, i - i),
                                      memory_space=pltpu.VMEM))
    outs = pl.pallas_call(
        kernel, out_shape=out_shape, in_specs=in_specs, out_specs=out_specs,
        grid=(M // TM,), interpret=interpret)(*arrays)
    res, i = [], 0
    if emit_mm:
        res.append(Column(dtype=dtypes.INT32, length=n,
                          data=outs[i].reshape(-1)[:n]))
        i += 1
    if emit_xx:
        xlo = outs[i][0].reshape(-1)[:n].astype(jnp.uint64)
        xhi = outs[i][1].reshape(-1)[:n].astype(jnp.uint64)
        res.append(Column(dtype=dtypes.INT64, length=n,
                          data=((xhi << jnp.uint64(32)) | xlo).astype(jnp.int64)))
    return res


def xxhash64_pallas(table, seed: int = DEFAULT_XXHASH64_SEED,
                    block_rows: int = 128 * 128,
                    interpret: Optional[bool] = None) -> Column:
    """Spark xxhash64 row hash, fused Pallas path (fixed-width columns)."""
    cols = _as_columns(table)
    if not cols:
        raise ValueError("xxhash64 hashing requires at least 1 column of input")
    [col] = _run_custom(cols, mm_seed=None, xx_seed=seed & (2**64 - 1),
                        normalize_zero=True, block_rows=block_rows,
                        interpret=interpret)
    return col


def fused_row_hash(table, mm_seed: int = 0,
                   xx_seed: int = DEFAULT_XXHASH64_SEED,
                   block_rows: int = 128 * 128,
                   interpret: Optional[bool] = None) -> Tuple[Column, Column]:
    """Both Spark row hashes in one HBM pass. Restricted to integer-family
    columns: float columns need different zero normalization per hash
    (hash.cuh:33-52), so mixed float tables must use the single-hash entry
    points."""
    cols = _as_columns(table)
    if any(c.dtype.kind in (Kind.FLOAT32, Kind.FLOAT64) for c in cols):
        raise TypeError("fused_row_hash: float columns need per-hash zero "
                        "normalization; use the single-hash pallas calls")
    mm, xx = _run_custom(cols, mm_seed=mm_seed & 0xFFFFFFFF,
                         xx_seed=xx_seed & (2**64 - 1), normalize_zero=False,
                         block_rows=block_rows, interpret=interpret)
    return mm, xx
