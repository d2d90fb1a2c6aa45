"""Copying/reshaping ops: concatenate, slice/split, replace_nulls, if_else,
drop_duplicates — the cudf copying surface the Spark plugin leans on
(cudf::concatenate, cudf::split for GpuSplitAndRetryOOM batch splitting —
SURVEY.md §5 "SplitAndRetry ... data chunking", cudf::copy_if_else,
cudf::replace_nulls, cudf::distinct)."""
from __future__ import annotations

from typing import List, Sequence, Union

import jax.numpy as jnp

from ..columnar import Column, Table
from ..dtypes import Kind
from .gather import take_table


def concat_columns(cols: Sequence[Column]) -> Column:
    """Concatenate same-dtype columns (cudf::concatenate)."""
    cols = list(cols)
    if not cols:
        raise ValueError("concat requires at least one column")
    out = cols[0]
    for c in cols[1:]:
        out = _concat2(out, c)
    return out


def _concat2(a: Column, b: Column) -> Column:
    if a.dtype != b.dtype:
        raise TypeError(f"concat dtype mismatch: {a.dtype} vs {b.dtype}")
    n = a.length + b.length
    if a.validity is not None or b.validity is not None:
        va = a.validity if a.validity is not None else jnp.ones((a.length,), bool)
        vb = b.validity if b.validity is not None else jnp.ones((b.length,), bool)
        validity = jnp.concatenate([va, vb])
    else:
        validity = None
    if a.dtype.kind == Kind.STRING:
        chars = jnp.concatenate([a.data, b.data])
        off_b = b.offsets[1:] + a.data.shape[0]
        offsets = jnp.concatenate([a.offsets, off_b.astype(jnp.int32)])
        return Column(dtype=a.dtype, length=n, data=chars,
                      offsets=offsets, validity=validity)
    if a.dtype.kind == Kind.LIST:
        child = _concat2(a.children[0], b.children[0])
        off_b = b.offsets[1:] + a.offsets[-1]
        offsets = jnp.concatenate([a.offsets, off_b.astype(jnp.int32)])
        return Column(dtype=a.dtype, length=n, offsets=offsets,
                      children=(child,), validity=validity)
    if a.dtype.kind == Kind.STRUCT:
        children = tuple(_concat2(ca, cb)
                         for ca, cb in zip(a.children, b.children))
        return Column(dtype=a.dtype, length=n, children=children,
                      validity=validity)
    return Column(dtype=a.dtype, length=n,
                  data=jnp.concatenate([a.data, b.data]), validity=validity)


def concat_tables(tables: Sequence[Table]) -> Table:
    tables = list(tables)
    if not tables:
        raise ValueError("concat requires at least one table")
    names = tables[0].names
    for t in tables[1:]:
        if t.num_columns != tables[0].num_columns:
            raise ValueError("concat column-count mismatch")
    cols = [concat_columns([t.columns[i] for t in tables])
            for i in range(tables[0].num_columns)]
    return Table(cols, names=names)


def slice_table(table: Table, start: int, end: int) -> Table:
    """Rows [start, end) (cudf::slice, one span)."""
    n = table.num_rows
    start = max(0, min(start, n))
    end = max(start, min(end, n))
    idx = jnp.arange(start, end, dtype=jnp.int32)
    return take_table(table, idx, _has_negative=False)


def split_table(table: Table, splits: Sequence[int]) -> List[Table]:
    """Split at row indices (cudf::split): splits [s1, s2] → [0,s1), [s1,s2),
    [s2, n). This is the batch-splitting primitive the SplitAndRetryOOM
    recovery contract needs (RmmSpark.java:461-490: split the input and
    retry halves)."""
    n = table.num_rows
    points = [0] + [int(s) for s in splits] + [n]
    for a, b in zip(points, points[1:]):
        if a > b or b > n:
            raise ValueError(f"invalid split points {splits} for {n} rows")
    return [slice_table(table, a, b) for a, b in zip(points, points[1:])]


def halve_table(table: Table) -> List[Table]:
    """The default SplitAndRetry policy: split the batch in half."""
    return split_table(table, [table.num_rows // 2])


def replace_nulls(col: Column, value) -> Column:
    """Nulls → scalar (cudf::replace_nulls; Spark coalesce(col, lit))."""
    if col.validity is None:
        return col
    if col.dtype.kind == Kind.STRING:
        # rebuild via the padded path: null rows take the fill string
        fill = value.encode() if isinstance(value, str) else bytes(value)
        from ..columnar.column import strings_from_padded
        padded, lens = col.padded_chars()
        L = max(padded.shape[1], len(fill)) if col.length else len(fill)
        if padded.shape[1] < L:
            padded = jnp.pad(padded, ((0, 0), (0, L - padded.shape[1])))
        fill_row = jnp.zeros((L,), jnp.uint8).at[:len(fill)].set(
            jnp.asarray(bytearray(fill), jnp.uint8))
        padded = jnp.where(col.validity[:, None], padded, fill_row[None, :])
        lens = jnp.where(col.validity, lens, len(fill))
        return strings_from_padded(padded, lens, None)
    if col.dtype.kind in (Kind.LIST, Kind.STRUCT):
        raise TypeError("nested replace_nulls is not supported")
    if col.dtype.kind == Kind.DECIMAL128:
        v = jnp.asarray(value, jnp.uint32)
        data = jnp.where(col.validity[:, None], col.data, v)
    else:
        data = jnp.where(col.validity, col.data,
                         jnp.asarray(value, col.dtype.storage_dtype()))
    return Column(dtype=col.dtype, length=col.length, data=data, validity=None)


def if_else(mask: Column, lhs: Column, rhs: Column) -> Column:
    """Row-wise select (cudf::copy_if_else). Spark CASE WHEN semantics: a
    null predicate chooses the ELSE side."""
    if lhs.dtype != rhs.dtype:
        raise TypeError(f"if_else dtype mismatch: {lhs.dtype} vs {rhs.dtype}")
    if lhs.dtype.kind in (Kind.LIST, Kind.STRUCT):
        raise TypeError("nested if_else is not supported")
    sel = mask.data
    if mask.validity is not None:
        sel = sel & mask.validity
    n = lhs.length

    def side_valid(c):
        return c.validity if c.validity is not None else jnp.ones((n,), bool)

    validity = jnp.where(sel, side_valid(lhs), side_valid(rhs))
    if lhs.validity is None and rhs.validity is None:
        validity = None
    if lhs.dtype.kind == Kind.STRING:
        from ..columnar.column import strings_from_padded
        L = max(int(lhs.max_string_length()), int(rhs.max_string_length()), 1)
        pl, ll = lhs.padded_chars(pad_to=_bucket(L))
        pr, lr = rhs.padded_chars(pad_to=_bucket(L))
        padded = jnp.where(sel[:, None], pl, pr)
        lens = jnp.where(sel, ll, lr)
        return strings_from_padded(padded, lens, validity)
    if lhs.dtype.kind == Kind.DECIMAL128:
        data = jnp.where(sel[:, None], lhs.data, rhs.data)
    else:
        data = jnp.where(sel, lhs.data, rhs.data)
    return Column(dtype=lhs.dtype, length=n, data=data, validity=validity)


def _bucket(n: int) -> int:
    from ..columnar.column import _round_bucket
    return _round_bucket(max(n, 1))


def drop_duplicates(table: Table,
                    key_names: Union[None, Sequence] = None) -> Table:
    """Distinct rows, keeping the FIRST occurrence in original row order
    (cudf::distinct KEEP_FIRST; Spark dropDuplicates)."""
    from .sort import _key_operands
    import jax

    keys = (list(table.columns) if key_names is None
            else [table[k] for k in key_names])
    operands = []
    for c in keys:
        operands.extend(_key_operands(c, True, None))
    n = table.num_rows
    iota = jnp.arange(n, dtype=jnp.int32)
    out = jax.lax.sort([*operands, iota], num_keys=len(operands),
                       is_stable=True)
    sorted_ops, order = out[:-1], out[-1]
    neq = jnp.zeros((n,), bool)
    for o in sorted_ops:
        neq = neq | (o != jnp.roll(o, 1))
    first_of_group = neq.at[0].set(True) if n else neq  # guard: empty scatter
    rows = jnp.sort(jnp.where(first_of_group, order, jnp.int32(n)))
    g = int(jnp.sum(first_of_group.astype(jnp.int32))) if n else 0
    return take_table(table, rows[:g], _has_negative=False)