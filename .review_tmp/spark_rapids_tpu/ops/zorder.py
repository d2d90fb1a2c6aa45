"""DeltaLake Z-order helpers: InterleaveBits and Hilbert index.

TPU-native re-design of the reference's zorder kernels
(src/main/cpp/src/zorder.cu:138-222 interleave, :74-135 hilbert). Where the
reference computes each output *byte* with a scalar bit loop in one CUDA
thread, here the whole column is expanded to a dense (rows, bits) plane and
interleaved with pure reshapes — XLA fuses the shifts/packs into a couple of
elementwise kernels on the VPU.

Semantics (exact InterleaveBits parity, zorder.cu:175-209):
- all input columns must share one fixed-width type; nulls read as 0;
- each value is taken in big-endian bit order (MSB first), column 0 is the
  most significant column;
- output row = num_cols * sizeof(type) bytes: bit stream c0[msb], c1[msb],
  ..., c0[msb-1], ... packed MSB-first into bytes → LIST<UINT8> column.

Hilbert (zorder.cu:224-273): INT32 columns only, nbits in (0,32],
nbits*ncols <= 64, nulls read 0; Skilling transpose then bit interleave,
result INT64.
"""
from __future__ import annotations

from typing import Sequence, Union

import jax
import jax.numpy as jnp

from .. import dtypes
from ..columnar import Column, Table
from ..dtypes import Kind


def _as_columns(table) -> list:
    if isinstance(table, Table):
        return list(table.columns)
    if isinstance(table, Column):
        return [table]
    return list(table)


def _to_unsigned_bits(col: Column) -> jnp.ndarray:
    """(n, nbits) uint8 bits of each value, MSB first; nulls -> 0."""
    size = col.dtype.itemsize()
    nbits = size * 8
    unsigned = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[size]
    if col.dtype.kind == Kind.BOOL:
        u = col.data.astype(jnp.uint8)
    elif col.dtype.kind in (Kind.FLOAT32, Kind.FLOAT64):
        u = jax.lax.bitcast_convert_type(
            col.data, jnp.uint32 if size == 4 else jnp.uint64)
    else:
        u = col.data.astype(unsigned)
    if col.validity is not None:
        u = jnp.where(col.validity, u, u.dtype.type(0))
    shifts = jnp.arange(nbits - 1, -1, -1, dtype=u.dtype)
    return ((u[:, None] >> shifts[None, :]) & u.dtype.type(1)).astype(jnp.uint8)


def interleave_bits(table: Union[Table, Column, Sequence[Column]]) -> Column:
    """InterleaveBits over same-typed fixed-width columns → BINARY rows."""
    cols = _as_columns(table)
    if len(cols) == 0:
        raise ValueError("The input table must have at least one column.")
    t0 = cols[0].dtype
    if t0.is_string or t0.is_nested:
        raise TypeError("Only fixed width columns can be used")
    if any(c.dtype.kind != t0.kind for c in cols):
        raise TypeError("All columns of the input table must be the same type.")
    n = cols[0].length
    nbits = t0.itemsize() * 8
    # (n, nbits, ncols): [i, b, c] = bit b (MSB first) of column c
    planes = jnp.stack([_to_unsigned_bits(c) for c in cols], axis=2)
    stream = planes.reshape(n, nbits * len(cols))
    byts = stream.reshape(n, -1, 8)
    weights = (jnp.uint8(1) << jnp.arange(7, -1, -1, dtype=jnp.uint8))
    packed = jnp.sum(byts.astype(jnp.uint32) * weights[None, None, :].astype(jnp.uint32),
                     axis=2).astype(jnp.uint8)
    row_bytes = t0.itemsize() * len(cols)
    offsets = jnp.arange(n + 1, dtype=jnp.int32) * row_bytes
    child = Column(dtype=dtypes.UINT8, length=n * row_bytes, data=packed.reshape(-1))
    return Column.make_list(offsets, child)


def hilbert_index(num_bits: int, table: Union[Table, Column, Sequence[Column]]) -> Column:
    """Hilbert curve distance of each row's point (zorder.cu:224-273)."""
    cols = _as_columns(table)
    ncols = len(cols)
    if not (0 < num_bits <= 32):
        raise ValueError("the number of bits must be >0 and <= 32.")
    if num_bits * ncols > 64:
        raise ValueError("we only support up to 64 bits of output right now.")
    if ncols == 0:
        raise ValueError("at least one column is required.")
    if any(c.dtype.kind != Kind.INT32 for c in cols):
        raise TypeError("All columns of the input table must be INT32.")
    n = cols[0].length
    mask_bits = jnp.uint64((1 << num_bits) - 1)
    # x: list of (n,) uint64 coordinate components, truncated to num_bits
    # (the reference's uint_backed_array masks on every set); nulls -> 0
    x = []
    for c in cols:
        u = c.data.astype(jnp.uint32).astype(jnp.uint64)
        if c.validity is not None:
            u = jnp.where(c.validity, u, jnp.uint64(0))
        x.append(u & mask_bits)

    # Skilling inverse-undo + gray encode (transposed index), vectorized over
    # rows; loops below are over dims/bit positions only (static, unrolled).
    q = 1 << (num_bits - 1)
    while q > 1:
        p = jnp.uint64(q - 1)
        qq = jnp.uint64(q)
        for i in range(ncols):
            cond = (x[i] & qq) != 0
            inv = x[0] ^ p                      # invert branch
            t = (x[0] ^ x[i]) & p               # exchange branch
            if i == 0:
                # t == 0 in the exchange branch when i == 0, so it's a no-op
                x[0] = jnp.where(cond, inv, x[0])
            else:
                x0 = jnp.where(cond, inv, x[0] ^ t)
                x[i] = jnp.where(cond, x[i], x[i] ^ t)
                x[0] = x0
        q >>= 1

    for i in range(1, ncols):
        x[i] = (x[i] ^ x[i - 1]) & mask_bits
    t = jnp.zeros_like(x[0])
    q = 1 << (num_bits - 1)
    while q > 1:
        t = jnp.where((x[ncols - 1] & jnp.uint64(q)) != 0,
                      t ^ jnp.uint64(q - 1), t)
        q >>= 1
    for i in range(ncols):
        x[i] = (x[i] ^ t) & mask_bits

    # interleave transposed-index bits, dim 0 most significant (zorder.cu:74-91)
    b = jnp.zeros((n,), jnp.uint64)
    b_index = num_bits * ncols - 1
    for bit in range(num_bits - 1, -1, -1):
        m = jnp.uint64(1 << bit)
        for j in range(ncols):
            b = jnp.where((x[j] & m) != 0, b | jnp.uint64(1 << b_index), b)
            b_index -= 1
    return Column(dtype=dtypes.INT64, length=n, data=b.astype(jnp.int64))
