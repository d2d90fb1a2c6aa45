"""Membership by comparison: which rows of a LARGE join side carry a key of
a SMALL one (a few hundred to a few thousand rows), found without sorting
the large side. Steps 1 and 2 of the eager joins' small-side path
(ops/join.py does step 3, the join of the survivors, with the sort join).

1. `_member`: every large-side row against every live small-side key,
   in plain XLA: a loop over chunks of 64 small keys up to the live ones,
   each chunk's compares unrolled in ONE elementwise fusion over the large
   side's 32-bit key words, so a row's words are read once a chunk and
   the compares run in registers. Dead small slots (padding, null keys)
   are packed past the loop's bound, so no value has to "match nothing".
   (A Pallas kernel with the keys in SMEM was 1.6 times slower on the
   chip: PERF.md section 6, PR 37.)
2. `ops/gather.py:compact_rows_columns`: the positions of the rows that
   passed and their keys, moved the way `compaction_path` says of the
   count step 1 read: few rows by `ops/scans.py:live_positions` (a price
   that follows what is kept), many riding one sort, all as they lie.
2'. `_match` (a broadcast join in which MANY rows pass, the small side on
   the right with distinct keys: a fact table against a filtered
   dimension): a second pass of the same compares that leaves, at every
   large-side row, the small-side row it matched. That index rides step
   2's compaction and IS the join's right map: no sort join follows.

Exact: integer words compared bit for bit. A null key, on either side,
matches nothing.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..columnar import Column
from ..dtypes import Kind
from ..utils.tracing import Tally, span

# The largest small side, in rows, and the smallest large side the path
# takes, each with the reading it was set from (PERF.md section 6, PR 37:
# my chip runs, one TPU v5 lite, an int64 key).
#
# Membership costs 0.8 to 1.0 ps a compare: 677 keys x 59,998,501 rows
# 38.5 ms, 4,096 x 59,998,501 196.0 ms, 677 x 15 M 10.7 ms. At 4,096 keys
# that is 3.3 ns a large-side row against the sort join's 11 to 21 (17 to
# 25 ms over 1.5 M rows, 1.265 s over 60 M). No small side above 4,096 was
# timed, and the next doubling would take `q3.share`'s date join.
LOOKUP_SMALL = 4096
# With 677 small rows the whole path takes 6.6 to 7.0 ms whatever the
# large side holds up to 262,144 rows (the sort join of the small side and
# the survivors 5.4 ms, the rest membership, positions and one more host
# sync), 8.3 at 1.5 M. The sort join of the whole sides takes 5.4 ms at
# 16,384 rows, 5.9 to 6.4 at 65,536, 6.8 to 8.5 at 262,144, 17 to 25 at
# 1.5 M: an inner join breaks even at 262,144 (a semi join with the large
# side left already at 16,384: 2.1 against 5.4 ms).
LOOKUP_LARGE = 1 << 18

_LOOKUP_KINDS = frozenset({
    Kind.BOOL, Kind.INT8, Kind.INT16, Kind.INT32, Kind.DATE32, Kind.INT64,
    Kind.TIMESTAMP_US, Kind.TIMESTAMP_S, Kind.TIMESTAMP_MS, Kind.DECIMAL32,
    Kind.DECIMAL64})

# 32-bit key words a row at most (two int64 columns). 677 keys x
# 59,998,501 rows take 24.5 ms with one word a key, 38.5 with two, 84.4
# with three; no wider key was timed.
_LOOKUP_WORDS = 4

# Small keys compared in one pass over the large side: 677 x 59,998,501
# take 38.5 ms in chunks of 32 or 64, 86.4 in chunks of 128.
_CHUNK = 64


def lookup_side(lcols: Sequence[Column], rcols: Sequence[Column],
                null_equal: bool) -> Optional[str]:
    """Which side ("left" or "right") is the small one of a join the path
    takes, from the two sides' row counts and the key columns' kinds; None
    where the sort join runs: a key that is no integer word (string, float,
    decimal128), more than `_LOOKUP_WORDS` words a key, sides whose types
    differ (the sort join says so), a null-safe join over nullable keys, or
    sizes outside the two bounds."""
    if len(lcols) != len(rcols) or not lcols:
        return None
    words = 0
    for a, b in zip(lcols, rcols):
        if a.dtype != b.dtype or a.dtype.kind not in _LOOKUP_KINDS:
            return None
        if null_equal and (a.validity is not None or b.validity is not None):
            return None
        words += 2 if a.data.dtype.itemsize > 4 else 1
    if words > _LOOKUP_WORDS:
        return None
    nl, nr = lcols[0].length, rcols[0].length
    small, large = min(nl, nr), max(nl, nr)
    if small > LOOKUP_SMALL or large < LOOKUP_LARGE:
        return None
    return "left" if nl <= nr else "right"


def _words(data):
    """An integer key column as 32-bit word planes: one, or low and high."""
    if data.dtype.itemsize <= 4:
        return [data.astype(jnp.int32)]
    wide = data.astype(jnp.int64)
    return [wide.astype(jnp.int32), (wide >> 32).astype(jnp.int32)]


def _member_rows(small, count, large):
    """(n,) bool: the large-side rows whose words equal those of one of the
    first `count` small keys; `small` (n_planes, S), `large` n_planes arrays
    of (n,), all int32. A loop over chunks of `_CHUNK` small keys, up to
    the live ones; a step compares every large-side row with
    the chunk's keys, unrolled in one elementwise fusion (the row's words
    are read once a chunk, not once a key)."""
    n = large[0].shape[0]
    lanes = jnp.arange(_CHUNK, dtype=jnp.int32)

    def chunk(i, acc):
        at = i * jnp.int32(_CHUNK)
        keys = jax.lax.dynamic_slice_in_dim(small, at, _CHUNK, axis=1)
        # a dead slot of the last chunk repeats the chunk's first key,
        # which is live: it matches nothing new
        keys = jnp.where(at + lanes < count, keys, keys[:, :1])
        for j in range(_CHUNK):
            eq = large[0] == keys[0, j]
            for p in range(1, len(large)):
                eq = eq & (large[p] == keys[p, j])
            acc = acc | eq
        return acc

    steps = (count + jnp.int32(_CHUNK - 1)) // jnp.int32(_CHUNK)
    return jax.lax.fori_loop(jnp.int32(0), steps, chunk,
                             jnp.zeros((n,), bool))


@jax.jit
def _member(small_data, small_live, large_data, large_validity):
    """-> (mask over the large side, how many rows it holds).
    `small_data` and `small_live` are padded to LOOKUP_SMALL rows;
    `large_validity` holds the validity masks the large keys have."""
    # live small keys first: the compares stop at their count
    order = jnp.argsort(~small_live, stable=True)
    small = jnp.stack([w[order] for d in small_data for w in _words(d)])
    count = jnp.sum(small_live, dtype=jnp.int32)
    mask = _member_rows(small, count,
                        [w for d in large_data for w in _words(d)])
    for v in large_validity:
        mask = mask & v
    return mask, jnp.sum(mask, dtype=jnp.int32)


def _match_rows(small, rows, count, large):
    """(n,) int32: at every large-side row the entry of `rows` that goes
    with the small key it equals (the last of them, among the first
    `count`), -1 where it equals none. `_member_rows` with a select where
    that has an OR."""
    n = large[0].shape[0]
    lanes = jnp.arange(_CHUNK, dtype=jnp.int32)

    def chunk(i, idx):
        at = i * jnp.int32(_CHUNK)
        live = at + lanes < count
        keys = jax.lax.dynamic_slice_in_dim(small, at, _CHUNK, axis=1)
        keys = jnp.where(live, keys, keys[:, :1])
        ids = jax.lax.dynamic_slice_in_dim(rows, at, _CHUNK)
        ids = jnp.where(live, ids, ids[:1])
        for j in range(_CHUNK):
            eq = large[0] == keys[0, j]
            for p in range(1, len(large)):
                eq = eq & (large[p] == keys[p, j])
            idx = jnp.where(eq, ids[j], idx)
        return idx

    steps = (count + jnp.int32(_CHUNK - 1)) // jnp.int32(_CHUNK)
    return jax.lax.fori_loop(jnp.int32(0), steps, chunk,
                             jnp.full((n,), -1, jnp.int32))


@jax.jit
def _match(small_data, small_live, large_data, mask):
    """-> (for every large-side row of `mask` the small-side row whose key
    it carries, -1 outside the mask; whether the live small keys are
    distinct, so that the row is the only one). Inputs as `_member`'s."""
    order = jnp.argsort(~small_live, stable=True)
    words = [w for d in small_data for w in _words(d)]
    count = jnp.sum(small_live, dtype=jnp.int32)
    idx = _match_rows(jnp.stack([w[order] for w in words]),
                      order.astype(jnp.int32), count,
                      [w for d in large_data for w in _words(d)])
    # dead keys last; two equal live keys then lie side by side
    dead, *srt = jax.lax.sort([(~small_live).astype(jnp.int32), *words],
                              num_keys=1 + len(words))
    same = dead[1:] == 0
    for w in srt:
        same = same & (w[1:] == w[:-1])
    return jnp.where(mask, idx, -1), ~jnp.any(same)


@jax.jit
def _pad_small(data, validity):
    """The small side's key data and match mask, padded to LOOKUP_SMALL
    rows (dead, as a null key is): every small side shares one `_member`
    program a large side, and the compares stop at the live keys."""
    n = data[0].shape[0]
    live = jnp.ones((n,), bool)
    for v in validity:
        live = live & v
    pad = (0, LOOKUP_SMALL - n)
    return [jnp.pad(d, pad) for d in data], jnp.pad(live, pad)


def _validity(cols):
    return [c.validity for c in cols if c.validity is not None]


# (small rows, large rows) of every join under a `with lookup_counts()`
# that took the path: the `lookup_joins` / `lookup_compares` of
# `plan.execute`
_lookups = Tally()
lookup_counts = _lookups.collect


def note_lookup(small: int, large: int) -> None:
    _lookups.note((small, large))


# what `OperatorMetrics.kernel` reads for a join that took the path
KERNEL_LABEL = "xla:lookup"


def member_mask(small_cols: Sequence[Column], large_cols: Sequence[Column]):
    """-> (mask, count): the large side's rows that carry a key of the
    small side (a device bool array), and how many (one host sync)."""
    small_data, small_live = _pad_small([c.data for c in small_cols],
                                        _validity(small_cols))
    mask, count = _member(small_data, small_live,
                          [c.data for c in large_cols], _validity(large_cols))
    with span("ops.host_sync", site="join.lookup"):
        return mask, int(count)


def match_rows(small_cols: Sequence[Column], large_cols: Sequence[Column],
               mask):
    """At every large-side row of `member_mask`'s `mask` the row of the
    small side whose key it carries ((n,) int32, -1 outside the mask), or
    None where two live small keys are equal and a large-side row has
    more matches than one (one host sync says which)."""
    small_data, small_live = _pad_small([c.data for c in small_cols],
                                        _validity(small_cols))
    match, distinct = _match(small_data, small_live,
                             [c.data for c in large_cols], mask)
    with span("ops.host_sync", site="join.lookup_distinct"):
        return match if bool(distinct) else None
