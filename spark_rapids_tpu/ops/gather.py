"""Row gather (`take`) over columns/tables — the cudf::gather equivalent the
reference leans on everywhere (e.g. map_utils' substring gather,
map_utils.cu:539-647; join gather maps in the plugin). TPU-first: one fused
`jnp.take` per buffer; strings go through the padded measure→gather pattern
(SURVEY.md §7 step 1).

An index of -1 (OOB_NULL policy, like cudf's out-of-bounds-policy
NULLIFY) yields a null output row — hash joins use this for outer-join
non-matches.

The capped tier's joins gather at a static cap of which a prefix is live:
`take_live` / `gather_live` do that gather in chunks over the live rows
only, the count read on the device (what a chunk and a slot cost on the
chip is in PERF.md, PR 31).
"""
from __future__ import annotations

import functools
from typing import Union

import jax
import jax.numpy as jnp

from ..columnar import Column, Table
from ..columnar.column import strings_from_padded
from ..dtypes import Kind
from ..utils.tracing import span
from .scans import running


def _any_negative(idx) -> bool:
    """Whether a gather map holds a -1 (a null row): one host sync."""
    with span("ops.host_sync", site="gather.has_negative"):
        return bool(jnp.any(idx < 0))


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
        with span("ops.host_sync", site="gather.bounds"):
            lo, hi = (int(x) for x in jax.device_get(
                (jnp.min(idx), jnp.max(idx))))    # one fused sync
        if hi >= col.length or lo < -1:
            raise IndexError(f"gather index out of bounds for {col.length} rows")
    if _has_negative is None:
        _has_negative = m > 0 and _any_negative(idx)
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


def live_chunk(m: int) -> int:
    """Slots one step of `gather_live` gathers over an `m`-slot frame: a
    sixty-fourth of the frame, rounded up to whole (8, 128) tiles of 32-bit
    words, and never more than the frame. Measured on the chip at 360,000
    slots (PERF.md, PR 31): a step costs about 4 us beside 8 ns a slot, so
    of a sixteenth, a thirty-second and a sixty-fourth the finest wastes
    least on the last chunk and loses nothing at a full frame."""
    return min(-(-m // (64 * 1024)) * 1024, m)


def live_slots(live: int, m: int) -> int:
    """Slots `gather_live` touches for `live` live rows of an `m`-slot
    frame (host arithmetic over counts already read back: the executor's
    `gather_slots`)."""
    c = live_chunk(m)
    return min(-(-min(live, m) // c) * c, m) if m else 0


def loop_zeros(shape, dtype, *like):
    """Zeros to carry through a `fori_loop` whose body writes values read
    off `like` into them: inside a `shard_map` a carry must enter the loop
    varying over the mesh axes it leaves varying over."""
    zeros = jnp.zeros(shape, dtype)
    axes = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.lax.pcast(zeros, tuple(axes), to="varying") if axes else zeros


def gather_live(planes, idx: jnp.ndarray, live) -> tuple:
    """`[jnp.take(p, idx, axis=0) for p in planes]` where only the prefix
    `idx[:live]` is wanted: the gather a capped join pays at its static
    cap, done in proportion to the rows that are live. `live` is a device
    scalar (a traced count, no host sync); the output keeps the static
    shape `(m, ...)`.

    A `fori_loop` over `ceil(live / C)` steps of `C = live_chunk(m)` slots:
    each step slices `C` indices, gathers every plane there and writes the
    chunk into the carried output. Slots of a touched chunk past `live`
    gather whatever `idx` holds there (a capped join leaves 0); slots past
    the last touched chunk are ZERO (False in a validity plane), never a
    row of the source. When `m` is not a multiple of `C` the last step's
    slice clamps back onto the frame's end (`dynamic_slice` and
    `dynamic_update_slice` clamp alike) and gathers a few slots twice.
    One loop per index vector: the compiler drops the planes nobody reads
    from the loop's carry as it drops a dead `take`
    (tests/test_chip_compile.py holds the count)."""
    m = int(idx.shape[0])
    planes = tuple(planes)
    if m == 0 or not planes:
        return tuple(jnp.take(p, idx, axis=0) for p in planes)
    c = live_chunk(m)
    steps = (jnp.clip(live, 0, m).astype(jnp.int32) + (c - 1)) // c

    def step(i, outs):
        at = i * jnp.int32(c)
        ix = jax.lax.dynamic_slice_in_dim(idx, at, c)
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(
                o, jnp.take(p, ix, axis=0), at, axis=0)
            for o, p in zip(outs, planes))

    init = tuple(loop_zeros((m,) + p.shape[1:], p.dtype, p, idx)
                 for p in planes)
    return jax.lax.fori_loop(jnp.int32(0), steps, step, init)


def take_live(cols, idx: jnp.ndarray, live) -> list:
    """`[take(c, idx) for c in cols]` for a capped join's output: `idx`
    is a (row_cap,) gather map without negatives whose live entries are
    the prefix `[0, live)`, `live` a device scalar. The fixed-width
    columns (decimal128's (n, 4) limbs too) go through ONE `gather_live`,
    data and validity planes together; slots past the last touched chunk
    come back zero and, in a nullable column, invalid. Strings, lists and
    structs have no plane to chunk and take the plain gather."""
    cols = list(cols)
    chunked = [c.dtype.kind not in (Kind.STRING, Kind.STRUCT, Kind.LIST)
               for c in cols]
    got = iter(gather_live(
        [p for c, ok in zip(cols, chunked) if ok
         for p in (c.data, c.validity) if p is not None], idx, live))
    return [Column(dtype=c.dtype, length=int(idx.shape[0]), data=next(got),
                   validity=None if c.validity is None else next(got))
            if ok else take(c, idx, _has_negative=False)
            for c, ok in zip(cols, chunked)]


@functools.partial(jax.jit, static_argnames=("total",))
def _pack_rows(mask, total: int):
    n = mask.shape[0]
    at = running(mask.astype(jnp.int32)) - 1
    # a row that is kept writes its index at its rank; the others write
    # nowhere
    return jnp.zeros((total,), jnp.int32).at[
        jnp.where(mask, at, total)].set(jnp.arange(n, dtype=jnp.int32),
                                        mode="drop")


def kept_rows(mask) -> jnp.ndarray:
    """The rows where the (n,) bool `mask` holds, ascending, as int32:
    `jnp.nonzero(mask)[0]`, whose own lowering adds a one for EVERY row
    into the bin of its rank (a scatter-add of n colliding updates: 87 ns
    a row where 677 of 15 M rows are kept, PERF.md, PR 34). One host sync
    for the result's size, as there."""
    mask = jnp.asarray(mask).astype(bool)
    with span("ops.host_sync", site="gather.kept_rows"):
        total = int(jnp.sum(mask))
    return _pack_rows(mask, total)


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
    keep = kept_rows(m)
    if isinstance(table_or_col, Table):
        return take_table(table_or_col, keep, _has_negative=False)
    return take(table_or_col, keep, _has_negative=False)


def take_table(table: Table, idx: jnp.ndarray,
               _has_negative: bool = None) -> Table:
    idx = jnp.asarray(idx)
    if _has_negative is None:
        _has_negative = int(idx.shape[0]) > 0 and _any_negative(idx)
    return Table([take(c, idx, _has_negative=_has_negative)
                  for c in table.columns], names=table.names)
