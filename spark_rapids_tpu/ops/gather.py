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

The eager tier moves rows by counts its callers have read: a filter's
compaction (`compaction_path`: few kept rows by their positions, the rest
riding one sort; PR 42) and an outer join's output columns
(`outer_join_paths`, `outer_join_columns`: a side whose map is the
identity is not gathered, a right side that hardly matches is a null
frame with the matched slots written in, a full join's lonely right rows
are a compaction; PR 44; a right side of which every row has one partner
at most is a PERMUTATION with null slots between, and rides one sort
keyed on the row's slot, `rows_by_slot`; PR 45), and a sorted group-by's
keys ride the compaction sort it runs anyway where `words_ride` says the
words cost less than the gathered slots (ops/aggregate.py; PR 48). A
frame-long `take` is what is left where no count says better.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from ..columnar import Column, Table
from ..columnar.column import strings_from_padded
from ..dtypes import Kind
from ..utils.tracing import Tally, span
from .scans import live_positions


# columns with no fixed-width plane a row: gathered whole by `take`
_RAGGED = (Kind.STRING, Kind.STRUCT, Kind.LIST)


def any_ragged(cols) -> bool:
    """Whether a string, list or struct column is among `cols`: such a
    column has no plane to ride a sort, and `take` gathers it whole."""
    return any(c.dtype.kind in _RAGGED for c in cols)


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
    chunked = [c.dtype.kind not in _RAGGED for c in cols]
    got = iter(gather_live(
        [p for c, ok in zip(cols, chunked) if ok
         for p in (c.data, c.validity) if p is not None], idx, live))
    return [Column(dtype=c.dtype, length=int(idx.shape[0]), data=next(got),
                   validity=None if c.validity is None else next(got))
            if ok else take(c, idx, _has_negative=False)
            for c, ok in zip(cols, chunked)]


# ---------------------------------------------------------------------------
# Row compaction: the eager tier's filter (`apply_boolean_mask`) and the
# semi / anti joins' row lists (`kept_rows`). The map of a compaction is
# strictly increasing, so nothing has to be gathered through it over the
# frame: the rows move by the count the caller has read (its one host sync).
# ---------------------------------------------------------------------------

# The constants below are set from a probe on the chip (one TPU v5 lite,
# 15 M rows; `tools/probe_compaction.py`, PERF.md section 6, PR 42):
#
#   the sort, a row:        1.34 ns with the key alone, 2.16 / 3.16 / 4.88 /
#                           8.73 / 12.37 ns carrying 1 / 2 / 4 / 8 / 12 32-bit
#                           words (0.93 ns a word), whatever share is kept;
#                           two int64 columns as they are 5.02, an int64 and
#                           a validity mask 3.64; a STABLE sort on the dropped
#                           flag with the same two columns 5.62 to 5.99
#   positions, a kept row:  153 to 176 ns (58,344 to 1,876,242 kept), 182 to
#                           219 with two int64 columns gathered; 1.7 ms for
#                           1,475 rows (the pass over the mask's words)
#   what ran before, a row: the rank scan and scatter 6.0 to 8.1 ns, then a
#                           gathered slot of an int64 column 15 ns
#
# "Few rows kept": at most one row in FEW_KEPT goes by positions. With four
# words riding the two ways cost the same at one row in 36 (with none at one
# in 114, with twelve at one in 19). The eager joins' small-side path
# (ops/join.py) stops at the same share.
FEW_KEPT = 32

# Fewer kept rows than this go by their positions whatever the frame holds:
# at most 2.9 ms, against a sort program's fixed costs, which the chip's
# compiler sets by the operands and not by the rows: 9.7 s of compile and
# 1.5 MB of code in HBM for the key alone, 8 s and 0.7 MB more a riding
# word (code sizes from compiles for a described v5e: 4.4 MB for two int64
# columns at 73,049 rows, where `q3.share`'s peak memory rose by 3.6 MB,
# 2.1%, until its date filter's 6,000 rows went by positions).
KEPT_FLOOR = 1 << 14

# 32-bit words of payload one sort carries: the widest the probe ran (one
# sort of 12 words 185.5 ms, sorts of 8 and 4 words 204 ms). A wider table
# goes in groups of columns, each sort carrying the key again.
RIDE_WORDS = 12


def few_kept(kept: int, n: int) -> bool:
    """Whether `kept` of `n` rows are few enough to go by their positions."""
    return kept * FEW_KEPT <= n


def words_ride(n: int, words: int, slots: int, planes: int) -> bool:
    """Whether `words` 32-bit words riding a sort of `n` rows that its
    caller runs anyway cost less than `planes` planes gathered over
    `slots` slots, at the probe's prices above: 0.93 ns a riding word a
    row, 15 ns a gathered slot a plane. Never at or under `KEPT_FLOOR`
    rows: a riding word's fixed costs (code in HBM, compile time) do not
    follow the rows. Arithmetic over what the caller holds before its
    kernel runs; nothing else chooses."""
    return n > KEPT_FLOOR and 0.93 * words * n < 15.0 * planes * slots


def compaction_path(n: int, kept: int, ragged: bool = False) -> str:
    """How a compaction of `n` rows to `kept` moves them: `none` (every row
    stays: the table as it is), `positions` (few kept, of the frame or in
    all), `sort` (the columns' planes ride one sort), `sort+gather` (a
    string, list or struct column has no plane to ride: it is gathered by
    the positions the same sort gives). Arithmetic over counts the caller
    holds; nothing else chooses."""
    if kept == n:
        return "none"
    if few_kept(kept, n) or kept <= KEPT_FLOOR:
        return "positions"
    return "sort+gather" if ragged else "sort"


def plane_words(arrays) -> Tuple[int, ...]:
    """32-bit words a row of each 1-D plane of `arrays`, in the order
    `_planes` gives them (an (n, k) array is k planes)."""
    return tuple(max(a.dtype.itemsize // 4, 1) for a in arrays
                 for _ in range(1 if a.ndim == 1 else a.shape[1]))


def ride_groups(words, limit: int = RIDE_WORDS) -> Tuple[Tuple[int, ...], ...]:
    """The planes (by index) each sort carries: in order, a group closed
    before it would pass `limit` words (a plane wider than the limit rides
    alone)."""
    groups, group, load = [], [], 0
    for i, w in enumerate(words):
        if group and load + w > limit:
            groups.append(tuple(group))
            group, load = [], 0
        group.append(i)
        load += w
    if group:
        groups.append(tuple(group))
    return tuple(groups)


def _planes(arrays):
    """The arrays as 1-D planes: an (n, k) array (DECIMAL128's limbs) as
    its k columns."""
    return [p for a in arrays
            for p in ([a] if a.ndim == 1
                      else [a[:, j] for j in range(a.shape[1])])]


def _unplane(planes, arrays):
    """`_planes` undone: `planes` back in the shapes of `arrays`."""
    got, out = iter(planes), []
    for a in arrays:
        out.append(next(got) if a.ndim == 1 else
                   jnp.stack([next(got) for _ in range(a.shape[1])], axis=1))
    return out


def _ride(key, planes, groups, keep: int):
    """`planes` riding a not-stable sort on `key` (unique under the rows
    that are kept), a sort for each of `groups` (`ride_groups`), cut to
    the first `keep` rows -> (the sorted key, the planes)."""
    out = [None] * len(planes)
    for group in groups or ((),):
        got = jax.lax.sort([key] + [planes[i] for i in group],
                           num_keys=1, is_stable=False)
        for i, p in zip(group, got[1:]):
            out[i] = p[:keep]
    return got[0][:keep], out


@functools.partial(jax.jit, static_argnames=("kept",))
def rows_by_position(mask, arrays, *, kept: int):
    """-> (the positions of the `kept` rows of `mask`, ascending, int32;
    each of `arrays` at those rows). One program: the positions by
    `live_positions`, the gathers over `kept` slots."""
    rows = live_positions(mask, kept)[0]
    return rows, [jnp.take(a, rows, axis=0) for a in arrays]


@functools.partial(jax.jit, static_argnames=("kept", "groups"))
def rows_by_sort(mask, arrays, *, kept: int, groups=()):
    """-> (the positions of the `kept` rows of `mask`, ascending, int32;
    each of `arrays` at those rows). ONE sort of the frame whose key is the
    row's rank in the result: a kept row's own number, a dropped row's
    number past the frame. Kept rows come first and in their order, as a
    stable sort on the dropped flag leaves them, and the keys are unique,
    so the sort need not be stable (for a stable one the chip's compiler
    adds the row numbers as one more operand and compares two keys); the
    key's own prefix is the kept rows' positions. The arrays' planes ride
    as payload operands, `groups` (from `ride_groups`) saying which planes
    share a sort; the result is cut to `kept` here, inside the program, so
    no second copy of the frame leaves it."""
    n = mask.shape[0]
    row = jnp.arange(n, dtype=jnp.uint32)
    rank = jnp.where(mask, row, row + jnp.uint32(n))
    rows, out = _ride(rank, _planes(arrays), groups, kept)
    return rows.astype(jnp.int32), _unplane(out, arrays)


def _count_kept(mask) -> int:
    with span("ops.host_sync", site="gather.kept_rows"):
        return int(jnp.sum(mask))


def kept_rows(mask, kept: int = None) -> jnp.ndarray:
    """The rows where the (n,) bool `mask` holds, ascending, as int32:
    `jnp.nonzero(mask)[0]`, whose own lowering adds a one for EVERY row
    into the bin of its rank (a scatter-add of n colliding updates: 87 ns
    a row where 677 of 15 M rows are kept, PERF.md, PR 34). One host sync
    for the result's size, unless the caller has read it (`kept`)."""
    mask = jnp.asarray(mask).astype(bool)
    if kept is None:
        kept = _count_kept(mask)
    return compact_rows_columns([], mask, kept)[0]


# what the compactions under a `with compactions.collect()` did:
# (path, rows in, rows kept) each (the executor's `compact=` and counters)
compactions = Tally()


def compact_columns(cols, mask, kept: int) -> list:
    """`cols` (all of `mask`'s length) at the `kept` rows where the bool
    `mask` holds, moved the way `compaction_path` says."""
    return compact_rows_columns(cols, mask, kept)[1]


def compact_rows_columns(cols, mask, kept: int):
    """-> (the positions of the `kept` rows where the bool `mask` holds,
    ascending, int32; `cols` at those rows), moved the way
    `compaction_path` says."""
    n = int(mask.shape[0])
    ragged = [c.dtype.kind in _RAGGED for c in cols]
    path = compaction_path(n, kept, any(ragged))
    compactions.note((path, n, kept))
    if path == "none":
        return jnp.arange(n, dtype=jnp.int32), list(cols)
    arrays = [p for c, r in zip(cols, ragged) if not r
              for p in (c.data, c.validity) if p is not None]
    if path == "positions":
        rows, got = rows_by_position(mask, arrays, kept=kept)
    else:
        rows, got = rows_by_sort(mask, arrays, kept=kept,
                                 groups=ride_groups(plane_words(arrays)))
    got = iter(got)
    return rows, [take(c, rows, _has_negative=False) if r else
                  Column(dtype=c.dtype, length=kept, data=next(got),
                         validity=None if c.validity is None else next(got))
                  for c, r in zip(cols, ragged)]


def apply_boolean_mask(table_or_col, mask) -> Union[Table, Column]:
    """Keep rows where mask is True (cudf::apply_boolean_mask — the filter
    half of read → filter → project). Null mask entries drop the row, like
    Spark's WHERE over a nullable predicate. One host sync (the count of
    kept rows), then one program that moves every fixed-width column."""
    if isinstance(mask, Column):
        m = mask.data
        if mask.validity is not None:
            m = m & mask.validity
    else:
        m = jnp.asarray(mask)
    is_table = isinstance(table_or_col, Table)
    n = table_or_col.num_rows if is_table else table_or_col.length
    if m.shape != (n,):
        raise ValueError(f"mask length {m.shape} does not match {n} rows")
    m = m.astype(bool)
    cols = table_or_col.columns if is_table else [table_or_col]
    out = compact_columns(cols, m, _count_kept(m))
    return Table(out, names=table_or_col.names) if is_table else out[0]


def take_table(table: Table, idx: jnp.ndarray,
               _has_negative: bool = None) -> Table:
    idx = jnp.asarray(idx)
    if _has_negative is None:
        _has_negative = int(idx.shape[0]) > 0 and _any_negative(idx)
    return Table([take(c, idx, _has_negative=_has_negative)
                  for c in table.columns], names=table.names)


# ---------------------------------------------------------------------------
# An eager outer join's output columns (`left_outer`, `full_outer`). The join
# has read, in its one host sync, how many pairs matched and how many rows
# of each side came out alone; those counts say what its gather maps hold,
# and a map that is the identity, a run of -1, a compaction, a -1 nearly
# everywhere, or a permutation is not gathered through over the frame.
# ---------------------------------------------------------------------------

class RightSlots(NamedTuple):
    """What an outer join hands back in place of its right map where its
    counts say that every right row has one partner at most (no two left
    rows that match share a key; `ops/join.py:_expand_slots`): the map's
    inverse. The right side of the output is then the right rows moved to
    their slots with a null slot for every left row without a match, and
    `rows_by_slot` moves them by one sort."""
    slot: jnp.ndarray    # (rows_right,) int32: a right row's output slot,
    #                      `matched + unmatched` where it has none
    alone: jnp.ndarray   # (rows_left,) int32: a left row's slot where it
    #                      matches nothing, `matched + unmatched` else


class OuterJoin(NamedTuple):
    """An eager outer join as the parts its one host sync tells apart
    (`ops/join.py:outer_join_parts`), and how its output columns are made
    of them: the one place that asks `outer_join_paths`, so that the join
    builds the map the assembly will read and `outer_join_columns` reads
    what the join built."""
    paths: Tuple[str, str, str]     # `outer_join_paths` of the counts below
    left_map: jnp.ndarray           # int32 over `matched + unmatched` slots
    right_map: Union[jnp.ndarray, RightSlots]   # the same; its inverse
    #                                 where `paths[1]` is `sort`
    lonely: Optional[jnp.ndarray]   # a full join's mask of the right rows
    #                                 without a match; None for a left join
    matched: int
    unmatched: int
    unmatched_right: int            # 0 for a left join


def outer_join_paths(how: str, rows_left: int, rows_right: int, matched: int,
                     unmatched: int, unmatched_right: int,
                     ragged: bool = False,
                     distinct: bool = False) -> Tuple[str, str, str]:
    """How an outer join's output columns are made -> (left, body, tail),
    by arithmetic over the join's own counts; nothing else chooses.

    `left`, the left side over the first `matched + unmatched` slots: every
    left row emits a slot or more, in row order, so when the slots are as
    many as the rows the map is `arange(rows_left)`: `as_is` (the columns
    as they stand), else `take`. `body`, the right side over those slots:
    `nulls` (nothing matched), `sparse` (`few_kept`, or at most `KEPT_FLOOR`
    matched: the matched slots' rows are gathered and written into a null
    frame; `sparse+gather` where a string, list or struct column has no
    plane to write: it takes the plain gather), `sort` (`distinct`: the
    join has read that no two left rows that match share a key, so every
    right row has one slot at most, and the columns ride one sort to their
    slots; a string, list or struct column cannot), else `take`. `tail`, a
    full join's last `unmatched_right` slots, the right rows without a
    match under a run of left nulls: `compaction_path`'s word for them,
    `empty` where there are none, "" for a join that has no tail."""
    slots = matched + unmatched
    left = "as_is" if slots == rows_left else "take"
    if matched == 0:
        body = "nulls"
    elif few_kept(matched, slots) or matched <= KEPT_FLOOR:
        body = "sparse+gather" if ragged else "sparse"
    else:
        body = "sort" if distinct and not ragged else "take"
    if how != "full_outer":
        tail = ""
    elif unmatched_right == 0:
        tail = "empty"
    else:
        tail = compaction_path(rows_right, unmatched_right, ragged)
    return left, body, tail


def null_rows(col: Column, n: int) -> Column:
    """`n` null rows of `col`'s type (the data under a null is zeros)."""
    no = jnp.zeros((n,), bool)
    k = col.dtype.kind
    if k == Kind.STRUCT:
        return Column(dtype=col.dtype, length=n, validity=no,
                      children=tuple(null_rows(c, n) for c in col.children))
    if k == Kind.LIST:
        return Column(dtype=col.dtype, length=n, validity=no,
                      offsets=jnp.zeros((n + 1,), jnp.int32),
                      children=(null_rows(col.children[0], 0),))
    if k == Kind.STRING:
        return Column(dtype=col.dtype, length=n, validity=no,
                      data=jnp.zeros((0,), col.data.dtype),
                      offsets=jnp.zeros((n + 1,), jnp.int32))
    return Column(dtype=col.dtype, length=n, validity=no,
                  data=jnp.zeros((n,) + col.data.shape[1:], col.data.dtype))


@functools.partial(jax.jit, static_argnames=("kept",))
def _write_rows(idx, arrays, *, kept: int):
    """-> (a bool plane, true at the `kept` slots where the (m,) map `idx`
    holds a row; per array of `arrays` a zero frame of `m` rows with the
    array's row at each of those slots). One program: the slots by
    `live_positions`, gathers over `kept` rows, and a scatter in which no
    two rows share a slot."""
    m = idx.shape[0]
    at = live_positions(idx >= 0, kept)[0]
    rows = jnp.take(idx, at, axis=0)

    def written(values):
        return jnp.zeros((m,) + values.shape[1:], values.dtype).at[at].set(
            values, indices_are_sorted=True, unique_indices=True)
    return (written(jnp.ones((kept,), bool)),
            [written(jnp.take(a, rows, axis=0)) for a in arrays])


def sparse_rows(cols, idx, kept: int) -> list:
    """`[take(c, idx) for c in cols]` for a gather map that holds a row at
    `kept` of its slots and -1 at every other, `kept` few: the rows are
    fetched for those slots alone. A string, list or struct column takes
    the plain gather."""
    m = int(idx.shape[0])
    ragged = [c.dtype.kind in _RAGGED for c in cols]
    hit, got = _write_rows(
        idx, [p for c, r in zip(cols, ragged) if not r
              for p in (c.data, c.validity) if p is not None], kept=kept)
    got = iter(got)
    return [take(c, idx, _has_negative=kept < m) if r else
            Column(dtype=c.dtype, length=m, data=next(got),
                   validity=next(got) if c.validity is not None
                   else hit if kept < m else None)
            for c, r in zip(cols, ragged)]


@functools.partial(jax.jit, static_argnames=("slots", "groups"))
def rows_by_slot(slot, alone, arrays, *, slots: int, groups=()):
    """-> (a bool plane over `slots` output rows, true where a row of
    `arrays` landed; each of `arrays` with row `r` at slot `slot[r]`, zeros
    at every slot of `alone`). No two entries of `slot` and `alone` under
    `slots` are alike, and together they are every slot; an entry of
    `slots` or more goes nowhere. ONE sort of the rows and a zero
    placeholder per entry of `alone`, keyed on twice the slot (a
    placeholder's key is odd, and says so after the sort): the keys under
    `2 * slots` are unique, so the sort need not be stable, and the
    arrays' planes ride as `rows_by_sort`'s do (`groups`). The result is
    cut to `slots` inside the program."""
    pad = alone.shape[0]
    key = jnp.concatenate([slot.astype(jnp.uint32) << 1,
                           (alone.astype(jnp.uint32) << 1) | 1])
    planes = [jnp.concatenate([p, jnp.zeros((pad,), p.dtype)])
              for p in _planes(arrays)]
    landed, out = _ride(key, planes, groups, slots)
    return (landed & 1) == 0, _unplane(out, arrays)


def outer_join_columns(left: Table, right: Table, parts: OuterJoin):
    """The output columns of an eager `left_outer` / `full_outer` join ->
    (left's columns ++ right's columns, over `matched + unmatched +
    unmatched_right` rows; what was done: `left_out`, `right_out`, and the
    `planes_gathered` / `slots_gathered` that still went through a
    frame-long `take`). Row for row, nulls included, what
    `take_table(left, lmap) ++ take_table(right, rmap)` gives over the
    join's whole maps, made the way `parts.paths` says (`outer_join_paths`
    of the join's counts, asked once, by the join:
    `ops/join.py:outer_join_parts` over these tables' keys, with `right`
    handed in): the maps are `left_join`'s over the first `matched +
    unmatched` slots, the right one its inverse (`RightSlots`) where the
    body is `sort`; `lonely` is a full join's mask of the right rows
    without a match."""
    from .copying import _concat2
    (left_out, body, tail), lmap, rmap, lonely, matched, unmatched, \
        unmatched_right = parts
    slots = matched + unmatched
    taken = []      # the columns that came out of a frame-long `take`
    lcols, rcols = list(left.columns), list(right.columns)
    if body == "nulls":
        rcols = [null_rows(c, slots) for c in rcols]
    elif body == "take":
        rcols = [take(c, rmap, _has_negative=unmatched > 0) for c in rcols]
        taken += rcols
    elif body == "sort":
        arrays = [p for c in rcols for p in (c.data, c.validity)
                  if p is not None]
        hit, got = rows_by_slot(rmap.slot, rmap.alone, arrays,
                                slots=slots,
                                groups=ride_groups(plane_words(arrays)))
        got = iter(got)
        rcols = [Column(dtype=c.dtype, length=slots, data=next(got),
                        validity=next(got) if c.validity is not None
                        else hit if unmatched else None) for c in rcols]
    else:
        rcols = sparse_rows(rcols, rmap, matched)
        taken += [c for c in rcols if c.dtype.kind in _RAGGED]
    # (the left side last: a sort's operands and results are not held
    # beside the left side's gathered columns)
    if left_out == "take":
        lcols = [take(c, lmap, _has_negative=False) for c in lcols]
        taken += lcols
    planes = [1 + (c.validity is not None) for c in taken]
    if unmatched_right:
        lcols = [_concat2(c, null_rows(c, unmatched_right)) for c in lcols]
        rcols = [_concat2(c, t) for c, t in zip(rcols, compact_columns(
            right.columns, lonely, unmatched_right))]
    return lcols + rcols, {
        "left_out": left_out, "right_out": body + "/" * bool(tail) + tail,
        "planes_gathered": sum(planes),
        "slots_gathered": sum(planes) * slots}
