"""Columnar <-> row-major conversion (JCUDF row format).

Reference: /root/reference/src/main/java/com/nvidia/spark/rapids/jni/
RowConversion.java (layout documentation :44-117: C-struct row layout,
per-column alignment padding, one validity byte per 8 columns appended
byte-aligned after the last column, rows padded to a 64-bit boundary;
fixed-width types only) binding cudf's convert_to_rows /
convert_to_rows_fixed_width_optimized / convert_from_rows kernels
(RowConversionJni.cpp:35-113).

TPU-native design: the row image is one dense (n_rows, row_size) uint8
matrix. `to_rows` bitcasts every column's data buffer to little-endian bytes
(`lax.bitcast_convert_type`), packs validity bits into bytes with shifts, and
assembles the row matrix with one `jnp.concatenate` along the byte axis —
a single fused XLA kernel, no per-row loop. `from_rows` slices the byte
matrix per column and bitcasts back. The row matrix is returned as a
LIST<UINT8> column (same shape the reference returns) whose offsets are the
constant row stride.

Unlike the GPU version there is no 2 GB-per-ColumnVector constraint, so the
result is always a single list column; `convert_to_rows` still returns a
list for API parity.
"""
from __future__ import annotations

from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes
from ..columnar.column import Column
from ..columnar.table import Table

# row-size cap of the fixed-width-optimized path (RowConversion.java:116)
_OPTIMIZED_MAX_ROW_BYTES = 1024
_OPTIMIZED_MAX_COLUMNS = 100

_FIXED_KINDS = {
    dtypes.Kind.BOOL, dtypes.Kind.INT8, dtypes.Kind.UINT8, dtypes.Kind.INT16,
    dtypes.Kind.INT32, dtypes.Kind.INT64, dtypes.Kind.FLOAT32,
    dtypes.Kind.FLOAT64, dtypes.Kind.DECIMAL32, dtypes.Kind.DECIMAL64,
    dtypes.Kind.DECIMAL128, dtypes.Kind.DATE32, dtypes.Kind.TIMESTAMP_US,
    dtypes.Kind.TIMESTAMP_S, dtypes.Kind.TIMESTAMP_MS,
}


def _check_fixed_width(dts: Sequence[dtypes.DType]) -> None:
    for dt in dts:
        if dt.kind not in _FIXED_KINDS:
            raise TypeError(f"row conversion supports fixed-width types only, got {dt}")


def row_layout(dts: Sequence[dtypes.DType]):
    """Compute (column byte offsets, validity byte offset, row size).

    Columns keep their given order; each is aligned to min(its width, 8)
    (RowConversion.java:68-86: 'padding in front of it to align it
    properly'); validity bytes are byte-aligned right after the last column;
    the row is padded to the next 64-bit boundary.
    """
    _check_fixed_width(dts)
    offsets = []
    pos = 0
    for dt in dts:
        w = dt.itemsize()
        align = min(w, 8)
        pos = (pos + align - 1) // align * align
        offsets.append(pos)
        pos += w
    validity_offset = pos                      # byte aligned, no padding
    n_validity_bytes = (len(dts) + 7) // 8
    pos += n_validity_bytes
    row_size = (pos + 7) // 8 * 8
    return offsets, validity_offset, row_size


def _use_word_kernel() -> bool:
    """Backend dispatch for the conversion kernels. The u32 word kernels
    exist for TPU tiling (narrow u8 slices pad to (32, 128) tiles; a CPU
    A/B in round 5, not measured on the chip: the word kernel is ~1.4x
    SLOWER on CPU where the concat lowers to clean memcpys, so CPU keeps the byte
    kernels). Selection lives in the kernel registry (ops/registry.py,
    docs/kernels.md): "word" is the universal fallback, "concat" registers
    for the cpu backend. Override:
    SPARK_RAPIDS_TPU_KERNELS=row_conversion=word|concat (legacy
    SPARK_RAPIDS_TPU_ROW_CONVERSION_KERNEL honored as an alias)."""
    from .registry import REGISTRY
    return REGISTRY.select("row_conversion").name == "word"


def _word_plan(dts: Sequence[dtypes.DType]):
    """Static u32-word assembly plan for the row image.

    The JCUDF alignment rule (min(width, 8)) means every >=4-byte column
    starts 4-aligned and every 2-byte column never straddles a u32 word, so
    each output u32 word is either exactly one WORD of one column ("w") or
    a static pack of four byte sources ("b": column byte / validity byte /
    zero). Assembling at word granularity is the roofline move on TPU: a
    216-column row becomes ~180 full-lane u32 ops + ONE (words, n) ->
    (n, words) transpose, instead of 216 narrow (n, 1..8) u8 concatenate
    parts whose (32, 128) tile padding wastes ~97% of each copy.
    """
    col_offsets, validity_offset, row_size = row_layout(dts)
    byte_src = [("z",)] * row_size
    for i, (dt, off) in enumerate(zip(dts, col_offsets)):
        for k in range(dt.itemsize()):
            byte_src[off + k] = ("c", i, k)
    for b in range((len(dts) + 7) // 8):
        byte_src[validity_offset + b] = ("v", b)
    words = []
    for wpos in range(row_size // 4):
        srcs = byte_src[wpos * 4:(wpos + 1) * 4]
        s0 = srcs[0]
        if (s0[0] == "c" and s0[2] % 4 == 0 and
                all(s[0] == "c" and s[1] == s0[1] and s[2] == s0[2] + j
                    for j, s in enumerate(srcs))):
            words.append(("w", s0[1], s0[2] // 4))
        else:
            words.append(("b", tuple(srcs)))
    return tuple(words), validity_offset, row_size


def _require_untraced_f64(data) -> None:
    """Both row-image kernels lower FLOAT64 through a HOST-SIDE numpy view
    on non-CPU backends (the TPU X64 pass has no bitcast *from* f64), which
    is impossible on traced data. Raise a clear error instead of the
    TracerArrayConversionError numpy would throw."""
    if isinstance(data, jax.core.Tracer):
        raise NotImplementedError(
            "convert_to_rows over a FLOAT64 column cannot run inside an "
            "outer jax.jit on this backend: the f64 word image is built "
            "from a host-side numpy view (no f64 bitcast in the X64 pass), "
            "which traced data cannot provide. Call the op eagerly, or "
            "convert the column to INT64 bits on the host first.")


def _column_words(col: Column):
    """(n, w//4) uint32 LE word image of a >=4-byte column's data."""
    data = col.data
    kind = col.dtype.kind
    if kind == dtypes.Kind.DECIMAL128:
        return data                     # already (n, 4) LE u32 limbs
    if kind == dtypes.Kind.FLOAT64 and jax.default_backend() != "cpu":
        # the TPU X64 pass has no bitcast *from* f64 — take the view host-side
        _require_untraced_f64(data)
        return jnp.asarray(np.asarray(data).view("<u4").reshape(-1, 2))
    out = jax.lax.bitcast_convert_type(data, jnp.uint32)
    return out.reshape(-1, 1) if out.ndim == 1 else out


def _column_small_bytes(col: Column) -> jnp.ndarray:
    """(n, w) uint8 byte image of a 1/2-byte column's data."""
    if col.dtype.kind == dtypes.Kind.BOOL:
        return col.data.astype(jnp.uint8)[:, None]
    if col.dtype.itemsize() == 1:
        return jax.lax.bitcast_convert_type(
            col.data, jnp.uint8).reshape(-1, 1)
    return jax.lax.bitcast_convert_type(col.data, jnp.uint8)


@partial(jax.jit, static_argnames=("plan", "n_cols"))
def _to_rows_kernel(wides, smalls, masks, *, plan, n_cols: int):
    words_plan, validity_offset, row_size = plan
    n = (wides + smalls)[0].shape[0] if (wides or smalls) else 0
    # validity bytes as u32: bit i%8 of byte i//8 set when column i is valid
    vbytes = []
    for b in range((n_cols + 7) // 8):
        byte = jnp.zeros((n,), jnp.uint32)
        for bit in range(min(8, n_cols - b * 8)):
            byte = byte | (masks[b * 8 + bit].astype(jnp.uint32) << bit)
        vbytes.append(byte)

    def byte_val(src):
        tag = src[0]
        if tag == "z":
            return None
        if tag == "v":
            return vbytes[src[1]]
        # "c" sources in byte-packed words are always SMALL columns: a
        # >=4-byte column is 4-aligned with width a multiple of 4, so all
        # its words classify as "w" in _word_plan
        _, i, k = src
        return smalls[i][:, k].astype(jnp.uint32)

    cols32 = []
    for w in words_plan:
        if w[0] == "w":
            cols32.append(wides[w[1]][:, w[2]])
        else:
            acc = jnp.zeros((n,), jnp.uint32)
            for j, src in enumerate(w[1]):
                v = byte_val(src)
                if v is not None:
                    acc = acc | (v << (8 * j))
            cols32.append(acc)
    stacked = jnp.stack(cols32, axis=0)            # (row_words, n) u32
    rows32 = stacked.T                             # ONE transpose
    return jax.lax.bitcast_convert_type(rows32, jnp.uint8).reshape(
        n, row_size)


def _column_bytes(col: Column) -> jnp.ndarray:
    """(n, w) little-endian byte image of a fixed-width column's data
    (concat-kernel path)."""
    w = col.dtype.itemsize()
    data = col.data
    if col.dtype.kind == dtypes.Kind.BOOL:
        return data.astype(jnp.uint8)[:, None]
    if col.dtype.kind == dtypes.Kind.DECIMAL128:
        # (n, 4) uint32 limbs, little-endian limb order -> (n, 4, 4) -> (n, 16)
        return jax.lax.bitcast_convert_type(data, jnp.uint8).reshape(-1, 16)
    if w == 1:
        return data.astype(jnp.uint8).reshape(-1, 1)
    if col.dtype.kind == dtypes.Kind.FLOAT64 and jax.default_backend() != "cpu":
        # the TPU X64 pass has no bitcast *from* f64 — take the view host-side
        _require_untraced_f64(data)
        return jnp.asarray(np.asarray(data).view(np.uint8).reshape(-1, 8))
    return jax.lax.bitcast_convert_type(data, jnp.uint8)


@partial(jax.jit, static_argnames=("layout",))
def _to_rows_concat_kernel(datas, masks, *, layout):
    """Byte-concatenate assembly: one (n, w) u8 part per column. Lowers to
    clean memcpys on CPU; on TPU each narrow u8 part pads to (32, 128)
    tiles, which is why the word kernel exists."""
    col_offsets, validity_offset, row_size = layout
    n = datas[0].shape[0] if datas else 0
    parts = []
    pos = 0
    for off, block in zip(col_offsets, datas):
        if off > pos:
            parts.append(jnp.zeros((n, off - pos), jnp.uint8))
        parts.append(block)
        pos = off + block.shape[1]
    if validity_offset > pos:
        parts.append(jnp.zeros((n, validity_offset - pos), jnp.uint8))
    # validity bytes: bit i%8 of byte i//8 set when column i is valid
    n_vbytes = (len(datas) + 7) // 8
    for b in range(n_vbytes):
        byte = jnp.zeros((n,), jnp.uint8)
        for bit in range(min(8, len(datas) - b * 8)):
            byte = byte | (masks[b * 8 + bit].astype(jnp.uint8) << bit)
        parts.append(byte[:, None])
    pos = validity_offset + n_vbytes
    if row_size > pos:
        parts.append(jnp.zeros((n, row_size - pos), jnp.uint8))
    return jnp.concatenate(parts, axis=1)


def convert_to_rows(table: Table) -> List[Column]:
    """Table -> row-major LIST<UINT8> column (RowConversion.convertToRows).

    Jit caveat (non-CPU backends only): a FLOAT64 column's byte/word image
    is built from a HOST-SIDE numpy view in BOTH kernels (the TPU X64 pass
    has no bitcast from f64), so this op cannot be wrapped in an outer
    `jax.jit` when the table has f64 columns — it raises a clear
    NotImplementedError under tracing instead of numpy's
    TracerArrayConversionError — and each f64 column costs one
    device-to-host sync in eager use there. CPU is unaffected."""
    cols = list(table.columns)
    dts = [c.dtype for c in cols]
    n = table.num_rows
    masks = tuple(c.null_mask for c in cols)
    if _use_word_kernel():
        plan = _word_plan(dts)
        empty = jnp.zeros((n, 0), jnp.uint32)
        empty8 = jnp.zeros((n, 0), jnp.uint8)
        wides = tuple(_column_words(c) if c.dtype.itemsize() >= 4 else empty
                      for c in cols)
        smalls = tuple(_column_small_bytes(c) if c.dtype.itemsize() < 4
                       else empty8 for c in cols)
        rows = _to_rows_kernel(wides, smalls, masks, plan=plan,
                               n_cols=len(cols))
        row_size = plan[2]
    else:
        col_offsets, validity_offset, row_size = row_layout(dts)
        datas = tuple(_column_bytes(c) for c in cols)
        rows = _to_rows_concat_kernel(
            datas, masks,
            layout=(tuple(col_offsets), validity_offset, row_size))
    offsets = (jnp.arange(n + 1, dtype=jnp.int32) * row_size)
    return [Column.make_list(offsets, Column(dtype=dtypes.UINT8,
                                             length=n * row_size,
                                             data=rows.reshape(-1)))]


def _check_optimized_limits(dts: Sequence[dtypes.DType]) -> None:
    """Optimized-path limits: <100 columns, row <= 1KB
    (RowConversion.java:32-34,:116)."""
    if len(dts) >= _OPTIMIZED_MAX_COLUMNS:
        raise ValueError(
            f"fixed-width-optimized conversion handles < {_OPTIMIZED_MAX_COLUMNS} columns")
    _, _, row_size = row_layout(dts)
    if row_size > _OPTIMIZED_MAX_ROW_BYTES:
        raise ValueError(f"row size {row_size} exceeds {_OPTIMIZED_MAX_ROW_BYTES} bytes")


def convert_to_rows_fixed_width_optimized(table: Table) -> List[Column]:
    """Same result as convert_to_rows; enforces the optimized path's limits."""
    _check_optimized_limits([c.dtype for c in table.columns])
    return convert_to_rows(table)


def convert_from_rows_fixed_width_optimized(
        rows_col: Column, schema: Sequence[dtypes.DType]) -> Table:
    """Same result as convert_from_rows with the optimized path's limits
    (the reference routes narrow schemas to a distinct kernel,
    RowConversionJni.cpp:113; one kernel serves both here)."""
    _check_optimized_limits(list(schema))
    return convert_from_rows(rows_col, schema)


@partial(jax.jit, static_argnames=("layout", "kinds"))
def _from_rows_slice_kernel(rows, *, layout, kinds):
    """Byte-slice decode (concat-kernel sibling): one narrow u8 slice +
    bitcast per column. CPU path; see _use_word_kernel."""
    col_offsets, validity_offset, row_size = layout
    datas = []
    masks = []
    for i, (off, kind) in enumerate(zip(col_offsets, kinds)):
        dt = dtypes.DType(kind)
        w = dt.itemsize()
        block = jax.lax.slice_in_dim(rows, off, off + w, axis=1)
        if kind == dtypes.Kind.BOOL:
            datas.append(block[:, 0] != 0)
        elif kind == dtypes.Kind.DECIMAL128:
            datas.append(jax.lax.bitcast_convert_type(
                block.reshape(-1, 4, 4), jnp.uint32))
        elif w == 1:
            datas.append(block[:, 0].astype(dt.storage_dtype()))
        elif kind == dtypes.Kind.FLOAT64:
            # u8[8] -> u32[2] -> f64: the TPU X64 pass implements bitcasts
            # *to* f64 only from 32-bit sources. The barrier stops XLA from
            # fusing the pair into a (malformed) direct u8->f64 bitcast.
            u32 = jax.lax.bitcast_convert_type(block.reshape(-1, 2, 4),
                                               jnp.uint32)
            u32 = jax.lax.optimization_barrier(u32)
            datas.append(jax.lax.bitcast_convert_type(u32, jnp.float64))
        else:
            datas.append(jax.lax.bitcast_convert_type(block,
                                                      dt.storage_dtype()))
        vbyte = rows[:, validity_offset + i // 8]
        masks.append((vbyte >> (i % 8)) & 1 != 0)
    return datas, masks


@partial(jax.jit, static_argnames=("layout", "kinds"))
def _from_rows_kernel(rows, *, layout, kinds):
    """Word-wise decode: ONE u8->u32 bitcast of the whole row image, then
    every column is full-lane u32 slices + shifts/bitcasts (no narrow u8
    slicing — the same tiling argument as _to_rows_kernel)."""
    col_offsets, validity_offset, row_size = layout
    n = rows.shape[0]
    W = jax.lax.bitcast_convert_type(
        rows.reshape(n, row_size // 4, 4), jnp.uint32)   # (n, row_words)
    datas = []
    masks = []
    for i, (off, kind) in enumerate(zip(col_offsets, kinds)):
        dt = dtypes.DType(kind)
        w = dt.itemsize()
        wpos, sh = off // 4, 8 * (off % 4)
        if w >= 4:
            block = jax.lax.slice_in_dim(W, wpos, wpos + w // 4, axis=1)
        if kind == dtypes.Kind.BOOL:
            datas.append((W[:, wpos] >> sh) & 0xFF != 0)
        elif kind == dtypes.Kind.DECIMAL128:
            datas.append(block)                          # (n, 4) LE limbs
        elif w == 1:
            b = ((W[:, wpos] >> sh) & 0xFF).astype(jnp.uint8)
            datas.append(jax.lax.bitcast_convert_type(b, dt.storage_dtype()))
        elif w == 2:                    # 2-aligned: never straddles a word
            h = ((W[:, wpos] >> sh) & 0xFFFF).astype(jnp.uint16)
            datas.append(jax.lax.bitcast_convert_type(h, dt.storage_dtype()))
        elif kind == dtypes.Kind.FLOAT64:
            # u32[2] -> f64: the TPU X64 pass implements bitcasts *to* f64
            # only from 32-bit sources; the barrier stops XLA from fusing
            # into a (malformed) direct bitcast.
            u32 = jax.lax.optimization_barrier(block)
            datas.append(jax.lax.bitcast_convert_type(u32, jnp.float64))
        elif w == 4:
            datas.append(jax.lax.bitcast_convert_type(block[:, 0],
                                                      dt.storage_dtype()))
        else:                           # 8-byte ints/timestamps
            datas.append(jax.lax.bitcast_convert_type(block,
                                                      dt.storage_dtype()))
        vpos = validity_offset + i // 8
        vbyte = (W[:, vpos // 4] >> (8 * (vpos % 4))) & 0xFF
        masks.append((vbyte >> (i % 8)) & 1 != 0)
    return datas, masks


def convert_from_rows(rows_col: Column, schema: Sequence[dtypes.DType]) -> Table:
    """Row-major LIST<UINT8> column -> Table (RowConversion.convertFromRows).

    `schema` gives the per-column logical types, like the DType[] argument of
    the reference API.
    """
    schema = list(schema)
    _check_fixed_width(schema)
    col_offsets, validity_offset, row_size = row_layout(schema)
    if rows_col.dtype.kind != dtypes.Kind.LIST:
        raise TypeError("expected a LIST<UINT8> rows column")
    n = rows_col.length
    if n and not isinstance(rows_col.offsets, jax.core.Tracer):
        # stride sanity check needs concrete offsets; under jit the layout is
        # fully determined by the (static) schema anyway
        offs = np.asarray(rows_col.offsets)
        if not (offs[0] == 0 and (np.diff(offs) == row_size).all()):
            raise ValueError(
                f"rows column must be contiguous with a uniform {row_size}-byte "
                "stride matching the schema's row layout")
    rows = rows_col.children[0].data[: n * row_size].reshape(n, row_size)
    kernel = _from_rows_kernel if _use_word_kernel() else \
        _from_rows_slice_kernel
    datas, masks = kernel(
        rows, layout=(tuple(col_offsets), validity_offset, row_size),
        kinds=tuple(dt.kind for dt in schema))
    cols = []
    for dt, data, mask in zip(schema, datas, masks):
        cols.append(Column(dtype=dt, length=n, data=data, validity=mask))
    return Table(cols)


# ---- kernel-registry wiring (ops/registry.py, docs/kernels.md) --------------
# the u32 word kernels are the universal lowering (TPU tiling: narrow u8
# slices pad to (32, 128) tiles); the byte-concat kernels register for the
# cpu backend, where the word kernel measured ~1.4x slower (round 5, CPU
# only; not measured on the chip)
from .registry import REGISTRY as _REGISTRY  # noqa: E402

_REGISTRY.register("row_conversion", "word", fallback=True)
_REGISTRY.register("row_conversion", "concat", backends=("cpu",))
