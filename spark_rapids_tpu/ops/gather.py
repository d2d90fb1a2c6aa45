"""Row gather (`take`) over columns/tables — the cudf::gather equivalent the
reference leans on everywhere (e.g. map_utils' substring gather,
map_utils.cu:539-647; join gather maps in the plugin). TPU-first: one fused
`jnp.take` per buffer; strings go through the padded measure→gather pattern
(SURVEY.md §7 step 1).

An index of -1 (OOB_NULL policy, like cudf's out-of-bounds-policy
NULLIFY) yields a null output row — hash joins use this for outer-join
non-matches.
"""
from __future__ import annotations

from typing import Union

import jax
import jax.numpy as jnp

from ..columnar import Column, Table
from ..columnar.column import strings_from_padded
from ..dtypes import Kind
from ..utils.tracing import span


def take(col: Column, idx: jnp.ndarray, check_bounds: bool = False,
         _has_negative: bool = None) -> Column:
    """New column with rows col[idx]. idx: (m,) int32/int64; -1 → null row.

    `_has_negative` lets table-level callers hoist the one device sync that
    decides whether a validity mask is needed; leave it None elsewhere.
    """
    idx = jnp.asarray(idx)
    if idx.ndim != 1:
        raise ValueError("gather map must be 1-D")
    m = int(idx.shape[0])
    if check_bounds and m:
        lo, hi = (int(x) for x in jax.device_get(
            (jnp.min(idx), jnp.max(idx))))        # one fused sync
        if hi >= col.length or lo < -1:
            raise IndexError(f"gather index out of bounds for {col.length} rows")
    if _has_negative is None:
        _has_negative = m > 0 and bool(jnp.any(idx < 0))
    nullify = idx < 0
    safe = jnp.where(nullify, 0, idx)

    if col.validity is not None:
        validity = jnp.take(col.validity, safe, axis=0) & ~nullify
    elif _has_negative:
        validity = ~nullify
    else:
        validity = None

    k = col.dtype.kind
    if k == Kind.STRING:
        padded, lens = col.padded_chars()
        out_padded = jnp.take(padded, safe, axis=0)
        out_lens = jnp.where(nullify, 0, jnp.take(lens, safe, axis=0))
        out = strings_from_padded(out_padded, out_lens, validity)
        return out
    if k == Kind.STRUCT:
        children = tuple(take(c, idx, _has_negative=_has_negative)
                         for c in col.children)
        return Column(dtype=col.dtype, length=m, validity=validity,
                      children=children)
    if k == Kind.LIST:
        # two-pass: gather per-row spans into a fresh dense child
        lens = col.offsets[1:] - col.offsets[:-1]
        out_lens = jnp.where(nullify, 0, jnp.take(lens, safe, axis=0))
        new_offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                       jnp.cumsum(out_lens).astype(jnp.int32)])
        with span("ops.host_sync", site="gather.list"):
            total = int(new_offsets[-1])
            L = int(jnp.max(lens)) if col.length else 0
        # child indexes: for output row i, element j -> old_start[idx[i]] + j
        starts = jnp.take(col.offsets[:-1], safe, axis=0)
        pos = jnp.arange(max(L, 1), dtype=jnp.int32)[None, :]
        child_idx = jnp.where(pos < out_lens[:, None], starts[:, None] + pos, -1)
        flat = child_idx.reshape(-1)
        keep_map = flat[flat >= 0] if total else jnp.zeros((0,), jnp.int32)
        # (host-synced total; facade-level op like the reference's JNI calls)
        child = take(col.children[0], keep_map.astype(jnp.int32),
                     _has_negative=False)
        return Column.make_list(new_offsets, child, validity)
    # fixed-width (incl. decimal128 limbs: take along axis 0 of (n,4))
    data = jnp.take(col.data, safe, axis=0)
    return Column(dtype=col.dtype, length=m, data=data, validity=validity)


def apply_boolean_mask(table_or_col, mask) -> Union[Table, Column]:
    """Keep rows where mask is True (cudf::apply_boolean_mask — the filter
    half of read → filter → project). Null mask entries drop the row, like
    Spark's WHERE over a nullable predicate."""
    if isinstance(mask, Column):
        m = mask.data
        if mask.validity is not None:
            m = m & mask.validity
    else:
        m = jnp.asarray(mask)
    n = (table_or_col.num_rows if isinstance(table_or_col, Table)
         else table_or_col.length)
    if m.shape != (n,):
        raise ValueError(f"mask length {m.shape} does not match {n} rows")
    keep = jnp.nonzero(m)[0].astype(jnp.int32)   # host sync: result size
    if isinstance(table_or_col, Table):
        return take_table(table_or_col, keep, _has_negative=False)
    return take(table_or_col, keep, _has_negative=False)


def take_table(table: Table, idx: jnp.ndarray,
               _has_negative: bool = None) -> Table:
    idx = jnp.asarray(idx)
    if _has_negative is None:
        _has_negative = int(idx.shape[0]) > 0 and bool(jnp.any(idx < 0))
    return Table([take(c, idx, _has_negative=_has_negative)
                  for c in table.columns], names=table.names)
