"""Hash-join equivalent: equi-join gather maps with Spark null semantics
(BASELINE.json configs[2]: "hash inner-join on two int64-keyed tables,
10M×1M"; the reference stack gets joins from cudf's hash join, returning
gather maps the plugin applies — the same contract here).

TPU-first design: device hash tables fight the hardware (scatter-heavy,
dynamic occupancy); XLA's sorter and scans are native. Every join here is
ONE union sort, then a tail that reads the matches off the sorted frame.
What each step costs on the chip is in PERF.md (section 5, by source
line), and nowhere else.

1. union sort (`_union_sort`): concatenate left+right key columns, ONE
   multi-operand `lax.sort` over their orderable operands (shared with
   ops/sort.py, so cross-type normalization — NaN, -0.0, decimal limbs,
   string words — is consistent), carrying two payloads: the row iota and
   a per-row flag (a payload rides the sort far cheaper than a gather
   after it). Equal keys form runs; in a run the left rows come first.

2a. the general tail (`_span_tail`, then `_expand`): a left row may match
   any number of right rows.
   - spans: a cumsum of the "matchable right row" flag gives, at each
     sorted position, the count of matchable right rows at or before it.
     Every row's match span in "matchable-right union order" is then
         lo = exclusive count at its run START (a running maximum)
         hi = inclusive count at its run END   (a reverse running minimum)
     — two more prefix scans, since the counts only grow.
   - routing sorts: `lo`/`hi` ride ONE inverse-permutation sort (keyed by
     the iota payload) back to original row order. The right-side gather
     map targets come from one boundary-compaction sort that packs
     matchable right rows (in union order) to the front.
   - expand (`expand_rows`): exclusive-scan the counts; every left row
     that emits writes its index at its first output slot, and a running
     maximum carries it over the row's slots: (left row, k-th match) for
     every output slot. The writes run in chunks over the left rows up to
     the last that emits, the two gathers that follow (`lo - starts` at the
     slot's row, `rorder` at the match) in chunks over the live slots
     (`gather_live`): both counts are read on the device. The capped
     inner join's routing sort brings the rows that emit to the front as
     it routes, so there the writes visit those rows and no other. Both
     sides come back as gather maps; -1 marks outer-join non-matches
     (take() turns them into null rows).
   Every join but the capped inner join always takes it.

2b. the many-to-one tail (`_capped_inner_kernel`, the capped inner join
   only): when no key of the right side has two matchable rows — every
   dimension join on a primary key — a left row has at most one match, and
   nothing needs expanding. One reverse `cummin` over the sorted frame
   hands every left row its run's matchable right row, ONE 32-bit sort
   packs the emitting left rows to the front in left-row order, and one
   gather over the emitting rows (`gather_live`: whole chunks of the live
   prefix, not the output cap) reads the right row ids. The same pass decides
   `unique` on the device, and a `lax.cond` picks the tail: no plan
   annotation, argument or switch says which join is which. Both tails
   return the same arrays, slot for slot.

2c. both sides' rows (`_full_join_kernel`, the eager outer joins: the full
   join needs both answers, the left join the count of 2d): the
   same union sort and general tail, read for both sides at once. The flag
   payload marks a left row that may match (2) as it marks a right one
   (1); a running count of the first, less its value at the run's start,
   is at every right row the left rows that match it, and rides home in
   the right row's slot of the routing sort, which nobody else reads. So
   one sort of the union where `left_join` and a swapped anti join ran
   two. Nulls match nothing here, so a nullable key brings no null-rank
   operand (the match masks keep a null-key row out whatever run it lies
   in), and the three sorts take the row number as their last key, or a
   key that is unique already, and are not stable: the order is the same,
   and XLA compiles such a sort in half the time (PERF.md section 6, PR
   43; the other joins keep the stable form their cells were measured
   with: ROADMAP S3). `full_join_parts` hands back the left join's
   maps, the mask of the right rows no left row matches and the three
   counts of the one read: the join's last rows are those right rows,
   ascending, a compaction by a count in hand, so the eager executor
   builds its output columns from the parts
   (`ops/gather.py:outer_join_columns`) and only `full_join_counted`,
   the public map contract, writes the tail out as gather maps.

2d. a right side that is a permutation (`_expand_slots`, the eager outer
   joins for a caller that builds the output columns,
   `outer_join_parts`): when no two left rows that
   match share a key, every right row has one partner at most, and the
   right side of the output is the right rows moved to their slots with a
   null slot for each left row alone: a sort on the slot with the columns
   riding, not a gather through a map (every primary-key to foreign-key
   outer join: a dimension LEFT OUTER its fact table). The kernel of 2c
   has counted it already: the pairs are as many as the right rows that
   have a partner (`matched + unmatched_right == rows_right`) exactly
   when each has one, so the eager left join runs that kernel too, and
   no second read is made. Where that holds and the
   right side's body would be a frame-long `take`
   (`ops/gather.py:outer_join_paths`) the join hands back, in place of
   the right map, its inverse (`RightSlots`): per right row its slot, per
   left row alone its own. The matchable right row at packed rank `q` in
   the span of left row `l` lands at `starts[l] + q - lo[l]`, so over the
   ranks each left row that matches adds its span's own number where the
   span starts and takes it back where it ends, a 32-bit running sum
   holds it along the span, and ONE two-word sort keyed on `rorder`
   brings the slots to right-row order. What that saves: the right map's
   own three gathered planes (`first`, `matches`, `rorder`) and a
   frame-long gather for every plane of the right side's columns. A
   repeated matching key, or a caller whose columns cannot ride a sort
   or who wants the maps themselves, gets the maps as before.

3. the small-side path (eager `inner_join`, `left_semi_join`,
   `left_anti_join` only, which hold both sides' row counts on the host):
   with one side of at most `LOOKUP_SMALL` rows and the other of at least
   `LOOKUP_LARGE`, integer keys, the large side is not sorted, whatever
   share of it passes. Its rows
   that carry a key of the small side are found by comparison in one pass
   (ops/join_lookup.py), moved the way `ops/gather.py:compaction_path`
   says of their count (few by their positions, many riding one sort),
   and the sort join above runs over the small side and
   those survivors. Survivors keep their row order, so the maps come out
   pair for pair in the order the sort join of the whole sides gives.
   Where more than a row in `ops/gather.py:FEW_KEPT` survives an inner
   join whose small side is the right one and has distinct keys (a fact
   table against a filtered dimension), a second pass leaves every row's
   one match, which rides the compaction: no sort join follows.

Null keys never match (Spark equi-join); null-safe equality (<=>) is the
`null_equal` flag, like cudf's null_equality::EQUAL — null rows get their
own leading rank operand (ops/sort.py), so they form their own runs and
match each other exactly when the validity masks say they may.
"""
from __future__ import annotations

from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import dtypes
from ..columnar import Column, Table
from ..utils.tracing import span
from .gather import (OuterJoin, RightSlots, any_ragged, compact_rows_columns,
                     few_kept, gather_live, kept_rows, live_chunk,
                     loop_zeros, outer_join_paths)
from .join_lookup import lookup_side, match_rows, member_mask, note_lookup
from .scans import running
from .sort import _key_operands

__all__ = ["inner_join", "inner_join_carrying", "left_join", "left_join_counted", "full_join",
           "full_join_counted", "full_join_parts", "outer_join_parts",
           "left_semi_join",
           "left_anti_join",
           "inner_join_capped", "inner_join_capped_tail", "left_join_capped",
           "semi_join_mask",
           "join_spans", "expand_spans"]


def _concat_columns(a: Column, b: Column) -> Column:
    """Concatenate two same-dtype key columns. Full dtype equality is
    required: decimal keys with different scale/precision would otherwise be
    compared on raw unscaled values (cudf also rejects)."""
    from .copying import _concat2
    try:
        return _concat2(a, b)
    except TypeError as e:
        raise TypeError(f"join key {e}") from None


def _union_sort(operands, iota, flags, *, n_ops: int, by_row: bool = False):
    """The union sort every join starts from: ONE stable multi-operand sort
    of the concatenated keys carrying two payloads, the row `iota` and a
    per-row int32 `flags`. Returns (boundary, order, flags_sorted): where a
    run of equal keys starts, each sorted position's original row, and its
    flag. Within a run the left rows come first (lower iota), then the
    right rows, each side in its original order. `by_row`: the row number
    is the sort's last key and the sort is not a stable one; no two rows
    tie, so the order is the stable sort's, and the program compiles in
    half the time (for a stable sort the chip's compiler adds the row
    numbers as one more operand and key: PERF.md, PR 43)."""
    n = operands[0].shape[0]
    # a marginal sort operand is cheaper on-chip than a post-sort gather
    out = jax.lax.sort([*operands, iota, flags], num_keys=n_ops + int(by_row),
                       is_stable=not by_row)
    sorted_ops, order, f_s = out[:-2], out[-2], out[-1]
    neq = jnp.zeros((n,), bool)
    for o in sorted_ops:
        neq = neq | (o != jnp.roll(o, 1))
    boundary = neq.at[0].set(True) if n else neq   # guard: empty scatter OOB
    return boundary, order, f_s


def _run_ends(boundary):
    """Where a run of equal keys ends: the position before the next start."""
    n = boundary.shape[0]
    return jnp.roll(boundary, -1).at[-1].set(True) if n else boundary


def _sorted_spans(boundary, m_s):
    """Every sorted position's match span in "matchable-right union order"
    (`m_s`: 1 at a matchable right row): (lo, hi), the exclusive count of
    matchable right rows at its run's start and the inclusive count at its
    run's end."""
    n = boundary.shape[0]
    ends = _run_ends(boundary)
    rcnt = jnp.cumsum(m_s)                       # inclusive matchable count
    excl = rcnt - m_s
    # the counts only grow along the frame, so "the value at my run's
    # start" is a running maximum over the starts, and "at my run's end" a
    # reverse running minimum over the ends: two prefix scans (an unrolled
    # `associative_scan` of the same copies was 120 MB of a join's code)
    lo_pos = jax.lax.cummax(jnp.where(boundary, excl, 0))
    hi_pos = jax.lax.cummin(jnp.where(ends, rcnt, jnp.int32(n)),
                            reverse=True)
    return lo_pos, hi_pos


def _matchable_rows(order, m_s, *, nl: int, by_row: bool = False):
    """Matchable right-row ids packed to the front, in union-sorted order.
    `by_row`: keyed by the sorted position itself (every other row holds
    the same key and the same id `n`), so the sort need not be stable."""
    n = order.shape[0]
    if by_row:
        at = jnp.where(m_s == 1, jnp.arange(n, dtype=jnp.int32), jnp.int32(n))
        rid = jnp.where(m_s == 1, order - nl, jnp.int32(n))
        return jax.lax.sort([at, rid], num_keys=1, is_stable=False)[1]
    flag = jnp.where(m_s == 1, jnp.int32(0), jnp.int32(1))
    rid = jnp.where(m_s == 1, order - nl, jnp.int32(n))
    return jax.lax.sort([flag, rid], num_keys=1, is_stable=True)[1]


def _span_tail(boundary, order, m_s, lvalid, *, nl: int, need_rorder: bool,
               l_s=None):
    """The general tail over the union sort (`m_s`: 1 at a matchable right
    row): every left row's match span, for `_expand`. See _join_kernel.
    With `l_s` (1 at a left row that may match; the frame was sorted
    `by_row`) a fourth result: per right row, the left rows that may match
    in its run."""
    lo_pos, hi_pos = _sorted_spans(boundary, m_s)
    if l_s is not None:
        # in a run the left rows come first, so at a right row the count so
        # far less the count at the run's start (a running maximum, as for
        # `lo`) is the run's; nobody reads a right row's span, and its slot
        # of the routing sort carries the count home
        seen = jnp.cumsum(l_s)
        hits = seen - jax.lax.cummax(jnp.where(boundary, seen - l_s, 0))
        lo_pos = jnp.where(order < nl, lo_pos, hits)
    # route lo/hi back to original row order: ONE 3-operand sort keyed by
    # the iota payload (order is a permutation, so this inverts it, stable
    # or not: the `by_row` frame's is not)
    routed = jax.lax.sort([order, lo_pos, hi_pos], num_keys=1,
                          is_stable=l_s is None)
    lo_orig, hi_orig = routed[1][:nl], routed[2][:nl]
    counts = jnp.where(lvalid, hi_orig - lo_orig, 0)
    rorder = _matchable_rows(order, m_s, nl=nl, by_row=l_s is not None) \
        if need_rorder else jnp.zeros((0,), jnp.int32)
    if l_s is not None:
        return counts, lo_orig, rorder, routed[1][nl:]
    return counts, lo_orig, rorder


@partial(jax.jit, static_argnames=("n_ops", "nl", "need_rorder"))
def _join_kernel(operands, lvalid, rvalid, *, n_ops: int, nl: int,
                 need_rorder: bool):
    """Scatter-free span computation over the union sort.

    Returns (counts, lo, rorder) in ORIGINAL left-row order:
      counts[i] — number of matching (valid) right rows for left row i
      lo[i]     — first match position in `rorder`
      rorder    — matchable right-row ids packed to the front, union-sorted
                  (length n union frame; entries past the matchable count
                  are n and never addressed: hi <= matchable count)
    """
    iota = jnp.arange(operands[0].shape[0], dtype=jnp.int32)
    # matchable = valid right row, the union sort's flag payload
    matchable = jnp.concatenate([jnp.zeros((nl,), jnp.int32),
                                 rvalid.astype(jnp.int32)])
    boundary, order, m_s = _union_sort(operands, iota, matchable,
                                       n_ops=n_ops)
    return _span_tail(boundary, order, m_s, lvalid, nl=nl,
                      need_rorder=need_rorder)


@partial(jax.jit, static_argnames=("n_ops", "nl"))
def _full_join_kernel(operands, lvalid, rvalid, *, n_ops: int, nl: int):
    """`_join_kernel` for the join that keeps both sides' rows: ONE union
    sort serves both. -> (counts, lo, rorder, rmiss): `_join_kernel`'s
    three, and per right row whether no left row matches it (a row that
    may not match among them). The flag payload says of a left row that it
    may match (2) as it says of a right row (1)."""
    iota = jnp.arange(operands[0].shape[0], dtype=jnp.int32)
    may = jnp.concatenate([lvalid.astype(jnp.int32) * 2,
                           rvalid.astype(jnp.int32)])
    boundary, order, f_s = _union_sort(operands, iota, may, n_ops=n_ops,
                                       by_row=True)
    counts, lo, rorder, hits = _span_tail(
        boundary, order, f_s & 1, lvalid, nl=nl, need_rorder=True,
        l_s=f_s >> 1)
    return counts, lo, rorder, ~(rvalid & (hits > 0))


def expand_rows(eff, total: int):
    """Which left row owns each of `total` output slots, when row `i`
    emits `eff[i]` of them in row order: `numpy.repeat(arange(nl),
    eff)[:total]` over the live slots, in proportion to the rows that emit. -> (lsel, starts, live): the owner of every slot, each
    row's first slot (the exclusive scan of `eff`), and the live slots
    `min(sum(eff), total)`, a device scalar. `nl` is at least 1.

    An emitting row owns the slots from its `starts` on, and `starts`
    strictly grows over the emitting rows. So every emitting row writes
    its own index at its first slot (a row that emits nothing, or starts
    at or past `total`, writes nowhere), and a running maximum carries
    the index to the next write. The writes run in a `fori_loop` over
    whole chunks of the left frame's prefix that ends at the last emitting
    row (`gather_live`'s chunking; the bound is read on the device), so a
    frame whose emitting rows are a short prefix pays for the prefix. A
    slot past the live ones holds the last owner; callers mask it."""
    nl = eff.shape[0]
    starts = jnp.cumsum(eff) - eff            # exclusive scan
    live = jnp.minimum(jnp.sum(eff.astype(jnp.int64)), total)
    iota = jnp.arange(nl, dtype=jnp.int32)
    emits = eff > 0
    c = live_chunk(nl)
    steps = (jnp.max(jnp.where(emits, iota, -1)) + c) // c
    at_slot = jnp.where(emits, starts, total).astype(jnp.int32)

    def step(i, owner):
        at = jnp.minimum(i * jnp.int32(c), nl - c)  # the last chunk clamps
        return owner.at[jax.lax.dynamic_slice_in_dim(at_slot, at, c)].set(
            at + jnp.arange(c, dtype=jnp.int32), mode="drop")

    owner = jax.lax.fori_loop(jnp.int32(0), steps, step,
                              loop_zeros((total,), jnp.int32, eff))
    return jax.lax.cummax(owner), starts, live


def expansion_slots(lmap, live, nl: int, planes: int, packed: bool):
    """What a capped join's expansion touched, and its frames' size, as
    device scalars read off the join's own outputs (the executor's
    `expand_slots` / `expand_cap_slots`): the left rows `expand_rows`'
    scatter visited plus the slots `planes` gathers over the `live` slots
    touched, each in whole chunks, against `nl + planes * row_cap`. The
    live prefix of `lmap` is in left-row order: the scatter visited the
    rows up to its last entry or, where the join `packed` the rows that
    emit to the front, as many rows as it names (a join that overflowed
    is run again)."""
    row_cap = lmap.shape[0]
    if nl == 0 or row_cap == 0:
        return jnp.int64(0), jnp.int64(0)
    live = live.astype(jnp.int32)

    def touched(rows, m: int):      # ops/gather.py:live_slots, on the device
        c = jnp.int32(live_chunk(m))
        return jnp.minimum((rows + c - 1) // c * c, m).astype(jnp.int64)
    if packed:
        slot = jnp.arange(row_cap, dtype=jnp.int32)
        emitting = jnp.sum(((slot == 0) | (lmap != jnp.roll(lmap, 1)))
                           & (slot < live), dtype=jnp.int32)
    else:
        emitting = jnp.where(live > 0,
                             lmap[jnp.maximum(live - 1, 0)] + 1, 0)
    return (touched(emitting, nl) + planes * touched(live, row_cap),
            jnp.int64(nl + planes * row_cap))


@partial(jax.jit, static_argnames=("total", "outer"))
def _expand(counts, lo, rorder, *, total: int, outer: bool, eff=None,
            rows=None):
    """`eff`, if given, is the per-row EMIT count (overrides the default
    outer rule of max(counts, 1)): rows with eff 0 produce no output slot,
    so a caller excluding rows (an alive mask) gets a live-slot prefix with
    no permute — output slots are allocated to emitting rows in row order
    by the exclusive scan. `rows`, if given, names the left row behind each
    entry of the spans (a caller that packed them), and the left map reads
    it. Slots past `min(sum(eff), total)` hold whatever the chunked
    gathers leave there (ops/gather.py:gather_live)."""
    nl = counts.shape[0]
    if nl == 0 or total == 0:   # static: nothing expands to all-dead slots
        return (jnp.zeros((total,), jnp.int32),
                jnp.full((total,), -1, jnp.int32))
    if eff is None:
        eff = jnp.maximum(counts, 1) if outer else counts
    lsel, starts, live = expand_rows(eff, total)
    # slot j is its row's k-th match, k = j - starts[lsel]: rorder's entry
    # lo[lsel] + k, so ONE gather of (lo - starts) over the live slots
    planes = {"first": lo - starts}
    if outer:
        planes["matches"] = counts
    if rows is not None:
        planes["rows"] = rows
    got = dict(zip(planes, gather_live(planes.values(), lsel, live)))
    lmap = got.get("rows", lsel)
    if rorder.shape[0] == 0:                  # static shape: empty right side
        return lmap, jnp.full((total,), -1, jnp.int32)
    rpos = jnp.arange(total, dtype=jnp.int32) + got["first"]
    (rmap,) = gather_live(
        [rorder], jnp.clip(rpos, 0, rorder.shape[0] - 1), live)
    if outer:
        rmap = jnp.where(got["matches"] > 0, rmap, -1)
    return lmap, rmap


def join_spans(operands, lvalid, rvalid, *, nl: int, need_rorder: bool = True):
    """PUBLIC span kernel — the cross-module contract consumed by
    parallel/relational.py's shard-local join tails (imported at module top
    there, so a refactor here fails at collection time, not at runtime).

    operands: orderable sort operands of the CONCATENATED left+right keys
    (raw key words work: the kernel sorts whatever it is given). lvalid
    (nl,) / rvalid (n-nl,) are the MATCH masks — masked-out left rows get
    count 0, masked-out right rows are never matched. Returns
    (counts, lo, rorder) in original left-row order; see _join_kernel."""
    operands = tuple(operands)
    return _join_kernel(operands, lvalid, rvalid, n_ops=len(operands),
                        nl=nl, need_rorder=need_rorder)


def expand_spans(counts, lo, rorder, *, total: int, outer: bool = False,
                 eff=None):
    """PUBLIC padded span expansion (companion to join_spans): materialize
    (left row, right row) gather maps into a fixed `total` slots; under
    `outer` every left row emits >=1 slot and unmatched rows get right -1.
    `eff` overrides the per-row emit count (rows with eff 0 emit nothing —
    the alive-mask idiom; see _expand)."""
    return _expand(counts, lo, rorder, total=total, outer=outer, eff=eff)


def _union_operands(left_keys, right_keys, null_equal: bool, lalive, ralive,
                    ranked: bool = True):
    """-> (sort operands of the concatenated keys, lvalid, rvalid, nl): the
    match masks hold null keys (unless `null_equal`) and the alive masks.
    Not `ranked`: a nullable key brings no null-rank operand and its data
    goes as it lies; right where nulls match nothing, since the masks keep
    a null-key row out of every match whatever run it sorts into."""
    lcols, rcols = list(left_keys), list(right_keys)
    if len(lcols) != len(rcols) or not lcols:
        raise ValueError("join requires equal, nonzero key column counts")
    union_ops: List[jnp.ndarray] = []
    for a, b in zip(lcols, rcols):
        # operands are built on the CONCATENATED keys: for strings the
        # operand count depends on the padded width, so building them on the
        # union guarantees both sides agree on the encoding
        u = _concat_columns(a, b)
        union_ops.extend(_key_operands(
            u if ranked else u.with_validity(None), True, None))
    nl = lcols[0].length

    def side_valid(cols, n):
        v = jnp.ones((n,), bool)
        any_mask = False
        for c in cols:
            if c.validity is not None:
                v = v & c.validity
                any_mask = True
        return v if (any_mask and not null_equal) else jnp.ones((n,), bool)

    lvalid = side_valid(lcols, nl)
    rvalid = side_valid(rcols, rcols[0].length)
    # alive masks exclude rows ENTIRELY (padded rows of a capped upstream
    # op, filters-as-masks) — unlike null keys they bind even under <=>
    if lalive is not None:
        lvalid = lvalid & lalive
    if ralive is not None:
        rvalid = rvalid & ralive
    return tuple(union_ops), lvalid, rvalid, nl


def _prep(left_keys, right_keys, null_equal: bool, need_rorder: bool = True,
          lalive=None, ralive=None):
    operands, lvalid, rvalid, nl = _union_operands(
        left_keys, right_keys, null_equal, lalive, ralive)
    return _join_kernel(operands, lvalid, rvalid, n_ops=len(operands),
                        nl=nl, need_rorder=need_rorder)


def _cols(keys) -> Sequence[Column]:
    if isinstance(keys, Column):
        return [keys]
    if isinstance(keys, Table):
        return list(keys.columns)
    return list(keys)


def _sort_inner_join(lcols, rcols, null_equal: bool):
    counts, lo, rorder = _prep(lcols, rcols, null_equal)
    with span("ops.host_sync", site="join.inner"):
        total = int(jnp.sum(counts))          # the one host sync
    lmap, rmap = _expand(counts, lo, rorder, total=total, outer=False)
    return lmap, rmap, total


def _survivors(small, large, mask, count: int):
    """Step 2 of the small-side path: the rows of `large` under
    `member_mask`'s `mask`, ascending, and their keys as columns (no row
    of them is null or dead), moved by the count step 1 read."""
    return compact_rows_columns([c.with_validity(None) for c in large],
                                mask, count)


@jax.jit
def _map_back(rows, idx):
    return jnp.take(rows, idx, axis=0)


def _lookup_inner_join(lcols, rcols, null_equal: bool, carry=()):
    """The inner join by the small-side path -> (lmap, rmap, total,
    carried), or None where it declines. The large side is read once
    whatever share of it passes. Few rows (`few_kept`): the sort join runs
    over the small side and the survivors. Many, the small side on the
    right with distinct keys (a fact table against a filtered dimension,
    Spark's broadcast hash join): a second pass leaves every row's match,
    which rides the survivors' compaction, and no sort join follows; the
    columns of `carry` (of the left side's length) ride it too, and come
    back as `carried`: the left map's rows of them, which no gather has to
    fetch. Else the sort join over the survivors, as with few, and
    `carried` is None."""
    side = lookup_side(lcols, rcols, null_equal)
    if side is None:
        return None
    small, large = (lcols, rcols) if side == "left" else (rcols, lcols)
    mask, count = member_mask(small, large)
    n = large[0].length
    note_lookup(small[0].length, n)
    if side == "right" and not few_kept(count, n):
        match = match_rows(small, large, mask)
        if match is not None:
            lmap, moved = compact_rows_columns(
                [*carry, Column(dtype=dtypes.INT32, length=n, data=match)],
                mask, count)
            return lmap, moved[-1].data, count, moved[:-1]
    rows, keys = _survivors(small, large, mask, count)
    if side == "left":
        lmap, rmap, total = _sort_inner_join(lcols, keys, null_equal)
        return lmap, _map_back(rows, rmap), total, None
    lmap, rmap, total = _sort_inner_join(keys, rcols, null_equal)
    return _map_back(rows, lmap), rmap, total, None


def inner_join(left_keys, right_keys,
               null_equal: bool = False) -> Tuple[Column, Column]:
    """Gather maps (left_map, right_map) of the inner equi-join, ordered by
    (left row, right row). With one side of a few hundred rows and the
    other large, the large side is not sorted: its rows that carry a key
    of the small side are found by comparison, and the sort join runs over
    the small side and those survivors, which keep their row order, so the
    pairs come out as the sort join of the whole sides gives them."""
    return inner_join_carrying(left_keys, right_keys, (), null_equal)[:2]


def inner_join_carrying(left_keys, right_keys, carry,
                        null_equal: bool = False):
    """`inner_join`, and the columns of `carry` (of the left side's length:
    its output columns) at the left map's rows where the join moved them
    itself: -> (left_map, right_map, carried). Where many rows of a large
    left side pass a small right side of distinct keys, the survivors'
    columns ride the one sort that compacts them and `carried` holds them;
    else it is None and the caller gathers by the left map."""
    lcols, rcols = _cols(left_keys), _cols(right_keys)
    lmap, rmap, total, carried = \
        _lookup_inner_join(lcols, rcols, null_equal, carry) \
        or (*_sort_inner_join(lcols, rcols, null_equal), None)
    return (Column(dtype=dtypes.INT32, length=total, data=lmap),
            Column(dtype=dtypes.INT32, length=total, data=rmap), carried)


@jax.jit
def _outer_totals(counts):
    """-> (matched pairs, left rows without a match), in 64 bits."""
    return (jnp.sum(counts.astype(jnp.int64)),
            jnp.sum((counts == 0).astype(jnp.int64)))


@partial(jax.jit, static_argnames=("total", "holes"))
def _expand_slots(counts, lo, rorder, rvalid, *, total: int, holes: bool):
    """`_expand(outer=True)` for a join in which every right row has one
    partner at most, read the other way: the right map's inverse says all
    the map says. -> (the left map; per right row its output slot, `total`
    where it has none; per left row its slot where it matches nothing,
    else `total`). No frame-long gather: the right map, the planes `first`
    and `matches` and the gather of `rorder` that make it are not built.

    The matchable right row at packed rank `q` in the span of left row `l`
    lands at `starts[l] + q - lo[l]` (`_expand`'s `rpos`, solved for the
    slot): the rank plus a number that is its span's own. The spans are
    disjoint, so over the ranks every left row that matches adds that
    number, less `total`, where its span starts and takes it back where
    it ends (in chunks over the left rows, as `expand_rows` writes), and a
    running sum, in 32 bits that may wrap, holds at every rank its span's
    number or, between the spans, `total`: a rank no left row matches
    reads `total` or more. `rorder` names the row at each rank, so ONE
    two-word sort keyed on it brings the slots to right-row order; where
    the right side `holes` rows that may not match (a null key, which
    `rorder` leaves out), they join that sort under their own numbers."""
    nl = counts.shape[0]
    n = rorder.shape[0]
    nr = n - nl
    lmap, starts, _ = expand_rows(jnp.maximum(counts, 1), total)
    c = live_chunk(nl)
    pad = (-nl) % c
    # a row that matches nothing writes past the ranks: nowhere
    first = jnp.pad(jnp.where(counts > 0, lo, nr), (0, pad),
                    constant_values=nr)
    last = jnp.pad(jnp.where(counts > 0, lo + counts, nr), (0, pad),
                   constant_values=nr)
    own = jnp.pad(jax.lax.bitcast_convert_type(starts - lo, jnp.uint32)
                  - jnp.uint32(total), (0, pad))

    def step(i, frame):
        at = i * jnp.int32(c)
        add = jax.lax.dynamic_slice_in_dim(own, at, c)
        return (frame
                .at[jax.lax.dynamic_slice_in_dim(first, at, c)].add(
                    add, mode="drop")
                .at[jax.lax.dynamic_slice_in_dim(last, at, c)].add(
                    -add, mode="drop"))

    rank = jnp.arange(nr, dtype=jnp.int32)
    slot = jnp.minimum(
        running(jax.lax.fori_loop(
            jnp.int32(0), jnp.int32((nl + pad) // c), step,
            loop_zeros((nr,), jnp.uint32, counts)))
        + jnp.uint32(total) + rank.astype(jnp.uint32),
        jnp.uint32(total)).astype(jnp.int32)
    rows = rorder[:nr]              # every rank lies under the right rows
    if holes:
        rows = jnp.concatenate([rows, jnp.where(rvalid, n, rank)])
        slot = jnp.concatenate([slot, jnp.full((nr,), total, jnp.int32)])
    slot = jax.lax.sort([rows, slot], num_keys=1, is_stable=False)[1][:nr]
    return lmap, slot, jnp.where(counts == 0, starts, total)


def outer_join_parts(how: str, left_keys, right_keys, right: Table = None,
                     null_equal: bool = False) -> OuterJoin:
    """An eager `left_outer` / `full_outer` join for the caller that builds
    its output columns (`ops/gather.py:outer_join_columns(left, right,
    parts)`): both sides' answers off ONE union sort (`_full_join_kernel`),
    one read of its three counts, and `outer_join_paths` asked of them
    ONCE, here, so that the join builds the map the assembly reads.
    `right` is the table whose columns the caller will place (None: the
    caller wants the maps themselves, `left_join_counted`).

    The three counts say whether any right row has two partners: the
    pairs are as many as the right rows that have a partner exactly when
    each has one (`matched + unmatched_right == rows_right`: no two left
    rows that match share a key). Where that holds and the right side's
    body would be a frame-long `take` over columns that can ride a sort,
    the body is `sort` and the right map is its inverse, `RightSlots`;
    the map itself is never built. A left join has no tail: its `lonely`
    is None and its `unmatched_right` 0."""
    lcols, rcols = _cols(left_keys), _cols(right_keys)
    operands, lvalid, rvalid, nl = _union_operands(
        lcols, rcols, null_equal, None, None, ranked=null_equal)
    counts, lo, rorder, lonely = _full_join_kernel(
        operands, lvalid, rvalid, n_ops=len(operands), nl=nl)
    full = how == "full_outer"
    with span("ops.host_sync", site="join.full" if full else "join.left"):
        matched, unmatched, unmatched_right = (int(x) for x in jax.device_get(
            _full_totals(counts, lonely)))    # the one host sync
    total, nr = matched + unmatched, rcols[0].length
    paths = outer_join_paths(
        how, nl, nr, matched, unmatched, unmatched_right if full else 0,
        ragged=right is None or any_ragged(right.columns),
        distinct=matched + unmatched_right == nr)
    if paths[1] == "sort":
        lmap, slot, alone = _expand_slots(
            counts, lo, rorder, rvalid, total=total,
            holes=not null_equal and any(c.validity is not None
                                         for c in rcols))
        rmap = RightSlots(slot, alone)
    else:
        lmap, rmap = _expand(counts, lo, rorder, total=total, outer=True)
    return OuterJoin(paths, lmap, rmap, lonely if full else None, matched,
                     unmatched, unmatched_right if full else 0)


def _map_columns(parts: OuterJoin) -> Tuple[Column, Column]:
    """The parts' two maps as the int32 columns the map contracts return."""
    return tuple(Column(dtype=dtypes.INT32, length=m.shape[0], data=m)
                 for m in (parts.left_map, parts.right_map))


def left_join_counted(left_keys, right_keys, null_equal: bool = False):
    """`left_join` and what its one host sync read: (left_map, right_map,
    matched, unmatched), the pairs that matched and the left rows that
    came out null-extended (a null key among them). The caller that
    gathers the right side's columns knows from `unmatched` that the map
    holds a -1 (take(_has_negative=...)) and need not ask the device."""
    parts = outer_join_parts("left_outer", left_keys, right_keys,
                             null_equal=null_equal)
    return (*_map_columns(parts), parts.matched, parts.unmatched)


def left_join(left_keys, right_keys,
              null_equal: bool = False) -> Tuple[Column, Column]:
    """Left outer join: every left row appears; non-matches get right -1
    (take() nullifies)."""
    return left_join_counted(left_keys, right_keys, null_equal)[:2]


def _require_x64(op_name: str) -> None:
    """The capped joins' total-match guard sums counts in int64; with
    jax_enable_x64 off, `astype(jnp.int64)` silently degrades to int32 and
    the overflow flag wraps at 2^31 total matches. The flag is enabled at
    package import, but a host app embedding this engine can flip it back —
    fail loudly instead of corrupting the guard."""
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            f"{op_name} requires jax_enable_x64 (enabled at spark_rapids_tpu "
            "import): its match-count overflow guard sums in int64 and would "
            "silently wrap at 2^31 matches under 32-bit mode")


def inner_join_capped(left_keys, right_keys, row_cap: int, *,
                      lalive=None, ralive=None, null_equal: bool = False):
    """Jit-traceable inner equi-join: a static `row_cap` output instead of
    the match-count host sync, so whole pipelines (join → join → groupby)
    fuse into ONE XLA program — the single-chip analogue of
    parallel.relational's shard-local join tail, sharing its SplitAndRetry
    contract (overflow True ⇒ retry with a bigger row_cap).

    `lalive`/`ralive` exclude rows entirely (padded rows from a capped
    upstream op, or dim-table filters applied as masks — the jit tier's
    filter idiom: a predicate costs one mask AND, not a compaction).

    Returns (lmap, rmap, valid, overflow): (row_cap,) int32 gather maps into
    the original frames (dead slots hold 0 and are masked by `valid`), a
    (row_cap,) bool row mask whose live slots are the prefix `[0, total)`
    (so `take_live` gathers the output columns over that prefix alone),
    and a scalar overflow flag."""
    return inner_join_capped_tail(left_keys, right_keys, row_cap,
                                  lalive=lalive, ralive=ralive,
                                  null_equal=null_equal)[:4]


def inner_join_capped_tail(left_keys, right_keys, row_cap: int, *,
                           lalive=None, ralive=None,
                           null_equal: bool = False):
    """`inner_join_capped` and which tail answered: (lmap, rmap, valid,
    overflow, unique). `unique` (a scalar bool, decided on the device from
    the union sort) says that no key of the right side had two matchable
    rows, so the many-to-one tail ran; False: the expansion did. Both
    return the same arrays (see _capped_inner_kernel)."""
    _require_x64("inner_join_capped")
    operands, lvalid, rvalid, nl = _union_operands(
        _cols(left_keys), _cols(right_keys), null_equal, lalive, ralive)
    return _capped_inner_kernel(operands, lvalid, rvalid,
                                n_ops=len(operands), nl=nl, row_cap=row_cap)


def _fit(x, length: int):
    """The first `length` entries of `x`, zero-padded where it is shorter
    (the padding lies past every live slot)."""
    if x.shape[0] >= length:
        return x[:length]
    return jnp.pad(x, (0, length - x.shape[0]))


@partial(jax.jit, static_argnames=("n_ops", "nl", "row_cap"))
def _capped_inner_kernel(operands, lvalid, rvalid, *, n_ops: int, nl: int,
                         row_cap: int):
    """One union sort, then one of two tails, chosen on the device.

    The sort's flag payload is the match mask of BOTH sides, so a left
    row's `lvalid` arrives in sorted order without a gather. One reverse
    `cummin` over the sorted frame then gives every position its nearest
    EVENT at or after it, an event being a matchable right row (word 2p)
    or the last row of a run (2p + 1; the matchable row wins where a row is
    both). Right rows close their run, so a left row has a match iff its
    event is even, and that event is the match when the run holds one
    matchable row. `unique`: no matchable right row is followed, inside its
    run, by another (dead and null-keyed right rows between them are no
    events, so they hide nothing).

    unique  -> the many-to-one tail: ONE 32-bit sort packs the emitting left
               rows to the front in left-row order with their event as
               payload, cut to `row_cap`, and one gather over the live
               prefix (ops/gather.py:gather_live) reads the right row id
               off the union sort's iota.
    else    -> the general tail: spans, routing sorts (the one that routes
               the spans also packs the rows that emit to the front) and
               `_expand`.
    Pair for pair the same (lmap, rmap, valid, overflow)."""
    n = operands[0].shape[0]
    nr = n - nl
    if nl == 0 or nr == 0:      # static: nothing can match, nothing to sort
        z = jnp.zeros((row_cap,), jnp.int32)
        return z, z, jnp.zeros((row_cap,), bool), jnp.bool_(False), \
            jnp.bool_(True)
    flags = jnp.concatenate([lvalid, rvalid]).astype(jnp.int32)
    iota = jnp.arange(n, dtype=jnp.int32)
    boundary, order, f_s = _union_sort(operands, iota, flags, n_ops=n_ops)
    right = order >= nl
    m_s = jnp.where(right, f_s, 0)           # matchable right rows
    ends = _run_ends(boundary)
    pos2 = jnp.arange(n, dtype=jnp.uint32) * 2
    none = jnp.uint32(2 * n + 1)             # odd: matches nothing
    event = jax.lax.cummin(
        jnp.where(m_s == 1, pos2, jnp.where(ends, pos2 + 1, none)),
        reverse=True)
    after = jnp.concatenate([event[1:], none[None]])
    unique = ~jnp.any((m_s == 1) & ~ends & (after % 2 == 0))

    def many_to_one(_):
        emit = ~right & (f_s == 1) & (event % 2 == 0)
        key = jnp.where(emit, order, jnp.int32(n))     # left row, or past all
        lrow, hit = jax.lax.sort([key, event], num_keys=1)
        total = jnp.sum(emit.astype(jnp.int64))
        # the emitting rows are the frame's prefix: read their right row
        # ids, not the cap's (a dead slot's event may lie past the frame)
        at = jnp.minimum(_fit(hit, row_cap) >> 1, n - 1).astype(jnp.int32)
        (rrow,) = gather_live([order], at, total)
        return _fit(lrow, row_cap), rrow - nl, total

    def general(_):
        lo_pos, hi_pos = _sorted_spans(boundary, m_s)
        # the routing sort packs as it routes: keyed by the left row where
        # a row emits and past every row where it does not, it brings the
        # spans of the rows that emit to the front in left-row order, so
        # the expansion's scatter visits those rows and no other
        emit = ~right & (f_s == 1) & (hi_pos > lo_pos)
        rows, lo, hi = (x[:nl] for x in jax.lax.sort(
            [jnp.where(emit, order, jnp.int32(n)), lo_pos, hi_pos],
            num_keys=1))
        counts = jnp.where(rows < n, hi - lo, 0)
        total = jnp.sum(counts.astype(jnp.int64))  # i32 sum could wrap at 10M×
        lmap, rmap = _expand(counts, lo, _matchable_rows(order, m_s, nl=nl),
                             total=row_cap, outer=False, rows=rows)
        return lmap, rmap, total

    lmap, rmap, total = jax.lax.cond(unique, many_to_one, general, None)
    valid = jnp.arange(row_cap, dtype=jnp.int32) < total
    # valid slots carry genuine in-range matches; dead slots are clamped to
    # row 0 so downstream gathers never need a host sync or a fill value
    lmap = jnp.where(valid, lmap, 0)
    rmap = jnp.where(valid, jnp.clip(rmap, 0, nr - 1), 0)
    return lmap, rmap, valid, total > row_cap, unique


def left_join_capped(left_keys, right_keys, row_cap: int, *,
                     lalive=None, ralive=None, null_equal: bool = False):
    """Jit-traceable left-outer equi-join (the outer sibling of
    inner_join_capped): every ALIVE left row emits at least one output
    slot; unmatched rows get right -1, surfaced as `rvalid` False. Rows
    excluded by `lalive` emit nothing — a zero per-row emit count drops
    them from the expansion entirely, so live output slots stay a prefix
    under the static cap with no permute (see _expand's `eff`).

    Returns (lmap, rmap, rvalid, valid, overflow): (row_cap,) int32 gather
    maps (dead/unmatched slots clamped to 0), rvalid marking slots whose
    right side is real, valid marking live slots, and the overflow flag."""
    _require_x64("left_join_capped")
    counts, lo, rorder = _prep(_cols(left_keys), _cols(right_keys),
                               null_equal, lalive=lalive, ralive=ralive)
    eff = jnp.maximum(counts, 1)
    if lalive is not None:
        eff = jnp.where(lalive, eff, 0)   # excluded rows emit nothing
    total = jnp.sum(eff.astype(jnp.int64))
    lmap, rmap = _expand(counts, lo, rorder, total=row_cap, outer=True,
                         eff=eff)
    valid = jnp.arange(row_cap, dtype=jnp.int32) < total
    rvalid = valid & (rmap >= 0)
    nr = _cols(right_keys)[0].length
    lmap = jnp.where(valid, lmap, 0)
    rmap = jnp.where(rvalid, jnp.clip(rmap, 0, max(nr - 1, 0)), 0)
    return lmap, rmap, rvalid, valid, total > row_cap


def semi_join_mask(left_keys, right_keys, *, lalive=None, ralive=None,
                   null_equal: bool = False) -> jnp.ndarray:
    """Jit-traceable semi-join as a MASK: True for (alive) left rows with at
    least one (alive) right match. The left frame never moves — a semi/anti
    join inside a jitted pipeline is a mask AND, not a compaction
    (left_semi_join's nonzero() host sync is the eager-tier form). Anti is
    the caller's `lalive & ~mask`."""
    counts, _, _ = _prep(_cols(left_keys), _cols(right_keys), null_equal,
                         need_rorder=False, lalive=lalive, ralive=ralive)
    return counts > 0


@jax.jit
def _full_totals(counts, rmiss):
    """-> `_outer_totals`, and the right rows without a match."""
    return (*_outer_totals(counts), jnp.sum(rmiss.astype(jnp.int64)))


def full_join_parts(left_keys, right_keys, null_equal: bool = False):
    """A full outer join as the parts its one host sync tells apart ->
    (left_map, right_map, lonely, matched, unmatched, unmatched_right):
    `left_join_counted`'s maps over the first `matched + unmatched` slots,
    and `lonely`, a bool per right row: no left row matches it (a null key
    among them). The join's last `unmatched_right` slots are those rows,
    ascending, under a -1 in the left map: a compaction of the right side
    under `lonely` by a count in hand, which a caller that builds the
    output columns moves by that count (`outer_join_parts`,
    `ops/gather.py:outer_join_columns`) and `full_join_counted` writes out
    as maps. Both sides' answers come off ONE union sort
    (`_full_join_kernel`)."""
    parts = outer_join_parts("full_outer", left_keys, right_keys,
                             null_equal=null_equal)
    return (*_map_columns(parts), *parts[3:])


def full_join_counted(left_keys, right_keys, null_equal: bool = False):
    """`full_join` and what its one host sync read: (left_map, right_map,
    matched, unmatched, unmatched_right): `left_join_counted`'s output,
    then one (-1, j) row per right row j without a match (a null key
    among them), ascending. `unmatched_right` says whether the left map
    holds a -1, as `unmatched` says of the right map: a caller's gathers
    need not ask the device."""
    lm, rm, lonely, matched, unmatched, unmatched_right = full_join_parts(
        left_keys, right_keys, null_equal)
    if unmatched_right:
        lmap, rmap = _append_right(lm.data, rm.data,
                                   kept_rows(lonely, unmatched_right))
        total = lm.length + unmatched_right
        lm = Column(dtype=dtypes.INT32, length=total, data=lmap)
        rm = Column(dtype=dtypes.INT32, length=total, data=rmap)
    return lm, rm, matched, unmatched, unmatched_right


@jax.jit
def _append_right(lmap, rmap, extra):
    return (jnp.concatenate([lmap, jnp.full(extra.shape, -1, jnp.int32)]),
            jnp.concatenate([rmap, extra]))


def full_join(left_keys, right_keys,
              null_equal: bool = False) -> Tuple[Column, Column]:
    """Full outer join: left_join's output plus one (-1, j) row per
    UNMATCHED right row j (cudf::full_join's gather-map contract; take()
    turns the -1s into null rows on either side)."""
    return full_join_counted(left_keys, right_keys, null_equal)[:2]


def _sort_semi_anti(lcols, rcols, null_equal: bool, semi: bool):
    counts, _, _ = _prep(lcols, rcols, null_equal, need_rorder=False)
    return kept_rows(counts > 0 if semi else counts == 0)


def _lookup_semi_anti(lcols, rcols, null_equal: bool, semi: bool):
    """The semi or anti join's rows by the small-side path, or None."""
    side = lookup_side(lcols, rcols, null_equal)
    if side is None:
        return None
    if side == "left":
        mask, count = member_mask(lcols, rcols)
        note_lookup(lcols[0].length, rcols[0].length)
        return _sort_semi_anti(
            lcols, _survivors(lcols, rcols, mask, count)[1], null_equal,
            semi)
    # the membership mask IS the semi join; the anti join keeps the rest
    mask, count = member_mask(rcols, lcols)
    note_lookup(rcols[0].length, lcols[0].length)
    return kept_rows(mask, count) if semi \
        else kept_rows(~mask, lcols[0].length - count)


def _semi_anti(left_keys, right_keys, null_equal: bool, semi: bool) -> Column:
    lcols, rcols = _cols(left_keys), _cols(right_keys)
    keep = _lookup_semi_anti(lcols, rcols, null_equal, semi)
    if keep is None:
        keep = _sort_semi_anti(lcols, rcols, null_equal, semi)
    return Column(dtype=dtypes.INT32, length=int(keep.shape[0]), data=keep)


def left_semi_join(left_keys, right_keys,
                   null_equal: bool = False) -> Column:
    """Left rows having >=1 match (gather map into the left table)."""
    return _semi_anti(left_keys, right_keys, null_equal, True)


def left_anti_join(left_keys, right_keys,
                   null_equal: bool = False) -> Column:
    """Left rows having no match — Spark NOT IN/anti join. NB: rows with a
    null key have no match, so they ARE returned (cudf behavior; Spark's
    NOT IN null semantics are built on top by the plugin)."""
    return _semi_anti(left_keys, right_keys, null_equal, False)
