"""Arrow interchange — the JVM-facing binding surface.

The reference's consumers live in a JVM: its Java facade passes cudf column
handles over JNI (SURVEY.md §1 L5→L4; `CastStrings.java:155`). The TPU
engine's columns are already Arrow-layout (columnar/column.py), so the
equivalent binding surface is the Arrow **C Data Interface**: `export_to_c`
/ `import_from_c` move whole tables across an ABI boundary as
ArrowArray/ArrowSchema structs, which Arrow Java's C Data bridge (or any
other runtime) consumes zero-copy — the JNI-handle role without bespoke
glue. `to_arrow`/`from_arrow` are the in-process pyarrow conveniences the
tests and IO paths use.

Device note: export materializes device buffers on the host (device→host
DMA); import is host→device `device_put`. That matches the reference, where
JNI interop likewise crosses the device boundary explicitly.
"""
from __future__ import annotations


import numpy as np

import pyarrow as pa

from .. import dtypes
from ..columnar import Column, Table
from ..dtypes import Kind


def _col_to_arrow(col: Column) -> pa.Array:
    import jax.numpy as jnp  # noqa: F401

    n = col.length
    k = col.dtype.kind
    # nested types take a pyarrow is-null mask, not a packed bitmap: handle
    # them before the (otherwise wasted) packbits pass below
    if k in (Kind.STRUCT, Kind.LIST):
        mask = (pa.array(~np.asarray(col.validity))
                if col.validity is not None else None)
        if k == Kind.STRUCT:
            children = [_col_to_arrow(c) for c in col.children]
            names = list(col.dtype.field_names or
                         [str(i) for i in range(len(children))])
            if not children:
                # from_arrays([]) infers length 0 and would drop every row
                is_valid = (np.asarray(col.validity) if col.validity is not None
                            else np.ones(n, dtype=bool))
                return pa.array([{} if v else None for v in is_valid],
                                type=pa.struct([]))
            return pa.StructArray.from_arrays(children, names=names,
                                              mask=mask)
        child = _col_to_arrow(col.children[0])
        offsets = pa.array(np.asarray(col.offsets, dtype=np.int32),
                           type=pa.int32())
        # mask kwarg, NOT null offset slots: masking an offset slot erases a
        # row boundary and the preceding row absorbs the null row's extent
        return pa.ListArray.from_arrays(offsets, child, mask=mask)

    if col.validity is not None:
        is_valid = np.asarray(col.validity)
        null_count = int(n - is_valid.sum())
        vbuf = pa.py_buffer(np.packbits(is_valid, bitorder="little").tobytes())
    else:
        null_count = 0
        vbuf = None

    if k == Kind.STRING:
        chars = np.asarray(col.data, dtype=np.uint8)
        offsets = np.asarray(col.offsets, dtype=np.int32)
        return pa.Array.from_buffers(
            pa.utf8(), n,
            [vbuf, pa.py_buffer(offsets.tobytes()),
             pa.py_buffer(chars.tobytes())], null_count=null_count)
    if k == Kind.DECIMAL128:
        limbs = np.asarray(col.data, dtype=np.uint32)   # (n, 4) LE limbs
        return pa.Array.from_buffers(
            pa.decimal128(col.dtype.precision or 38, col.dtype.scale or 0), n,
            [vbuf, pa.py_buffer(limbs.tobytes())], null_count=null_count)
    pa_type = {
        Kind.BOOL: pa.bool_(), Kind.INT8: pa.int8(), Kind.UINT8: pa.uint8(),
        Kind.INT16: pa.int16(), Kind.INT32: pa.int32(), Kind.INT64: pa.int64(),
        Kind.UINT64: pa.uint64(),
        Kind.FLOAT32: pa.float32(), Kind.FLOAT64: pa.float64(),
        Kind.DATE32: pa.date32(), Kind.TIMESTAMP_US: pa.timestamp("us"),
        Kind.TIMESTAMP_MS: pa.timestamp("ms"), Kind.TIMESTAMP_S: pa.timestamp("s"),
        Kind.DECIMAL32: pa.decimal128(col.dtype.precision or 9,
                                      col.dtype.scale or 0),
        Kind.DECIMAL64: pa.decimal128(col.dtype.precision or 18,
                                      col.dtype.scale or 0),
    }.get(k)
    if pa_type is None:
        raise TypeError(f"arrow export unsupported for {col.dtype}")
    vals = np.asarray(col.data)
    if k == Kind.BOOL:
        data_buf = pa.py_buffer(np.packbits(vals.astype(bool),
                                            bitorder="little").tobytes())
        return pa.Array.from_buffers(pa_type, n, [vbuf, data_buf],
                                     null_count=null_count)
    if k in (Kind.DECIMAL32, Kind.DECIMAL64):
        # widen unscaled ints to arrow's 16-byte decimal storage
        wide = np.zeros((n, 2), np.int64)
        wide[:, 0] = vals.astype(np.int64)
        wide[:, 1] = np.where(vals.astype(np.int64) < 0, -1, 0)
        return pa.Array.from_buffers(pa_type, n, [vbuf, pa.py_buffer(
            wide.tobytes())], null_count=null_count)
    return pa.Array.from_buffers(pa_type, n,
                                 [vbuf, pa.py_buffer(vals.tobytes())],
                                 null_count=null_count)


def to_arrow(table: Table) -> pa.Table:
    """Engine Table → pyarrow Table (host materialization)."""
    # from_arrays, not a dict: Table allows duplicate column names (join
    # outputs commonly produce them) and a dict would silently drop columns
    return pa.Table.from_arrays([_col_to_arrow(c) for c in table.columns],
                                names=list(table.names))


def _col_from_arrow(arr: pa.ChunkedArray | pa.Array, name: str) -> Column:
    import jax.numpy as jnp

    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    t = arr.type
    n = len(arr)
    validity = None
    if arr.null_count:
        validity = jnp.asarray(np.asarray(arr.is_valid()))

    if pa.types.is_string(t) or pa.types.is_large_string(t):
        if pa.types.is_large_string(t):
            arr = arr.cast(pa.utf8())
        bufs = arr.buffers()
        off = np.frombuffer(bufs[1], np.int32,
                            count=n + 1 + arr.offset)[arr.offset:]
        chars = np.frombuffer(bufs[2], np.uint8) if bufs[2] else np.zeros(0, np.uint8)
        base = off[0]
        chars = chars[base:off[-1]]
        return Column(dtype=dtypes.STRING, length=n,
                      data=jnp.asarray(chars),
                      offsets=jnp.asarray((off - base).astype(np.int32)),
                      validity=validity)
    if pa.types.is_struct(t):
        names = [f.name for f in t]
        children = tuple(_col_from_arrow(arr.field(i), f.name)
                         for i, f in enumerate(t))
        # build the Column directly: make_struct's **fields kwargs would
        # collide with a field literally named "validity", and a zero-field
        # struct still carries its own row count
        dt = dtypes.DType(Kind.STRUCT, children=tuple(c.dtype for c in children),
                          field_names=tuple(names))
        return Column(dtype=dt, length=n, validity=validity,
                      children=children)
    if pa.types.is_list(t) or pa.types.is_large_list(t):
        if pa.types.is_large_list(t):
            arr = arr.cast(pa.list_(t.value_type))
            t = arr.type
        # normalize nulls/offset slicing: arrow allows null offset slots and
        # array offsets; rebuild dense offsets from flattened lengths
        lens = np.asarray(arr.value_lengths().fill_null(0))
        offsets = np.zeros(n + 1, np.int32)
        np.cumsum(lens, out=offsets[1:])
        child = _col_from_arrow(arr.flatten(), name + ".item")
        return Column.make_list(jnp.asarray(offsets), child, validity)
    if pa.types.is_decimal256(t):
        raise TypeError(f"decimal256 import unsupported for column {name!r}; "
                        "cast to decimal128 first")
    if pa.types.is_decimal(t):
        if t.precision <= dtypes.MAX_DEC32_PRECISION:
            kind, np_dt = Kind.DECIMAL32, np.int32
        elif t.precision <= dtypes.MAX_DEC64_PRECISION:
            kind, np_dt = Kind.DECIMAL64, np.int64
        else:
            kind, np_dt = Kind.DECIMAL128, None
        raw = np.frombuffer(arr.buffers()[1], np.uint8).reshape(-1, 16)
        raw = raw[arr.offset:arr.offset + n]
        if kind == Kind.DECIMAL128:
            data = jnp.asarray(raw.copy().view(np.uint32).reshape(n, 4))
        else:
            data = jnp.asarray(raw[:, :8].copy().view(np.int64)
                               .reshape(n).astype(np_dt))
        return Column(dtype=dtypes.DType(kind, precision=t.precision,
                                         scale=t.scale),
                      length=n, data=data, validity=validity)

    m = {pa.bool_(): dtypes.BOOL, pa.int8(): dtypes.INT8,
         pa.uint8(): dtypes.UINT8, pa.int16(): dtypes.INT16,
         pa.int32(): dtypes.INT32, pa.int64(): dtypes.INT64,
         pa.uint64(): dtypes.UINT64,
         pa.float32(): dtypes.FLOAT32, pa.float64(): dtypes.FLOAT64,
         pa.date32(): dtypes.DATE32, pa.timestamp("us"): dtypes.TIMESTAMP_US,
         pa.timestamp("ms"): dtypes.TIMESTAMP_MS,
         pa.timestamp("s"): dtypes.TIMESTAMP_S}
    dt = m.get(t)
    if dt is None:
        raise TypeError(f"arrow import unsupported for column {name!r}: {t}")
    fill = False if pa.types.is_boolean(t) else 0
    np_vals = np.asarray(arr.fill_null(fill) if arr.null_count else arr)
    return Column(dtype=dt, length=n,
                  data=jnp.asarray(np_vals.astype(dt.storage_dtype())),
                  validity=validity)


def from_arrow(table: pa.Table) -> Table:
    """pyarrow Table → engine Table (device placement on first use)."""
    cols = [_col_from_arrow(table.column(i), table.column_names[i])
            for i in range(table.num_columns)]
    return Table(cols, names=table.column_names)


# ---- C Data Interface (the actual ABI boundary for JVM consumers) -----------

def export_to_c(table: Table, array_ptr: int, schema_ptr: int) -> None:
    """Write the table into caller-allocated ArrowArray/ArrowSchema structs
    (as a struct array of its columns). A JVM consumer imports them with
    Arrow Java's `org.apache.arrow.c.Data.importVectorSchemaRoot`."""
    batch = to_arrow(table).combine_chunks()
    struct = batch.to_struct_array().combine_chunks()
    struct._export_to_c(array_ptr, schema_ptr)


def import_from_c(array_ptr: int, schema_ptr: int) -> Table:
    """Read an ArrowArray/ArrowSchema pair (struct array of columns) into an
    engine Table — the inverse ABI direction (JVM → engine)."""
    struct = pa.Array._import_from_c(array_ptr, schema_ptr)
    if not pa.types.is_struct(struct.type):
        raise TypeError("expected a struct array of columns")
    names = [f.name for f in struct.type]
    cols = [_col_from_arrow(struct.field(i), names[i])
            for i in range(len(names))]
    return Table(cols, names=names)
