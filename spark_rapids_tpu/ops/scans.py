"""Running sums and maxima of long vectors, in two levels.

The chip's compiler takes a 64-bit scan of a long vector as one emulated
reduce-window, and its compile time grows with the length. Compiled for a
described v5e (no chip; PERF.md, PR 32), an int64 `cumsum` of 65,535
elements takes 6.6 s flat, of 65,536 16.7 s flat and 2.0 s in two levels,
of 131,072 28.3 s and 3.5 s, of 425,984 121.6 s and 1.9 s; of 60,000,000
7.4 s in two levels (PR 34; a 32-bit one compiles flat in 9.7 s). On the
chip the count of 39.6 M rows runs in 10.1 ms flat and 5.2 ms in two
levels. The block is the one size tried.

`running` is the one definition: the SPMD walk's sorted group-by
(`parallel/relational.py`) and the `scan` group-by kernel
(`ops/aggregate.py`) both call it, and `running_in_runs`, the scans of
the window kernel (`ops/window.py`) that restart at every partition, is
built from it: no flat `associative_scan` over a frame, whose unrolled
program is what takes minutes to compile. `live_positions` (PR 32, moved here
unchanged in PR 37) is the one definition too: the SPMD walk's compaction
and the eager joins' small-side path (`ops/join_lookup.py`) call it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SCAN_BLOCK = 4096


def running(x: jnp.ndarray, op: str = "sum") -> jnp.ndarray:
    """Inclusive running sum (or maximum) of a 1-D array, in two levels
    from 16 blocks on: a scan inside blocks of 4,096, a scan over the
    blocks' totals, one elementwise merge."""
    scan = jnp.cumsum if op == "sum" else jax.lax.cummax
    n = x.shape[0]
    if n < 16 * SCAN_BLOCK:
        return scan(x, axis=0)
    pad = (-n) % SCAN_BLOCK
    if pad:
        fill = 0 if op == "sum" else jnp.iinfo(x.dtype).min
        x = jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])
    inner = scan(x.reshape(-1, SCAN_BLOCK), axis=1)
    totals = inner[:, -1]
    if op == "sum":
        before = jnp.cumsum(totals) - totals
        out = inner + before[:, None]
    else:
        lowest = jnp.full((1,), jnp.iinfo(x.dtype).min, x.dtype)
        before = jnp.concatenate([lowest, jax.lax.cummax(totals)[:-1]])
        out = jnp.maximum(inner, before[:, None])
    return out.reshape(-1)[:n]


def _carried_word(rank, word):
    """The running maximum of the pair (rank, 32-bit word) as one int64,
    its low word back as uint32. `rank` never falls, so the maximum at a
    row is taken over the rows of its own rank alone."""
    packed = (rank.astype(jnp.int64) << 32) \
        | word.astype(jnp.uint32).astype(jnp.int64)
    return running(packed, "max").astype(jnp.uint32)


def _join_words(hi, lo):
    return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)


def running_in_runs(x: jnp.ndarray, head: jnp.ndarray, rank: jnp.ndarray,
                    op: str = "sum") -> jnp.ndarray:
    """Inclusive running sum (or maximum) of an int64 vector that restarts
    at every row `head` flags: the forward segmented scan of a window's
    partitions. `rank` is the run's number, `running(head) - 1` (a caller
    with several vectors computes it once).

    A sum is the frame-long running sum less its value before the run's
    head, and that value reaches the run's rows as the running maximum of
    (rank, word), a word at a time: no gather through the head's position
    (15 ns a slot, PERF.md PR 42) and no segmented `associative_scan`.
    A maximum is the lexicographic one of (rank, high word, low word): the
    running maximum of (rank, high word) gives the high word; it never
    falls, so the rows over which it stays the same are runs themselves,
    and inside one the low word is the running maximum over the rows that
    hold that high word.

    A 32-bit `x` is a COUNT (never negative, `op` "sum"): its running
    total never falls, so the value before the run's head is carried by
    one 32-bit running maximum, and the program holds no 64-bit scan (18.5
    MB of code each on the chip against 1.7, PERF.md PR 45)."""
    sign = jnp.uint32(1 << 31)
    if x.dtype == jnp.int32:
        csum = running(x)
        return csum - running(jnp.where(head, csum - x, jnp.int32(0)), "max")
    if op == "sum":
        csum = running(x)
        before = jnp.where(head, csum - x, jnp.int64(0))
        return csum - _join_words(_carried_word(rank, before >> 32),
                                  _carried_word(rank, before))
    hi = (x >> 32).astype(jnp.uint32) ^ sign         # order as unsigned
    packed = (rank.astype(jnp.int64) << 32) | hi.astype(jnp.int64)
    top = running(packed, "max")
    n = x.shape[0]
    turn = top != jnp.roll(top, 1)
    turn = turn.at[0].set(True) if n else turn
    sub = running(turn.astype(jnp.int32)) - 1
    lo = _carried_word(sub, jnp.where(packed == top, x.astype(jnp.uint32),
                                      jnp.uint32(0)))
    return _join_words(top.astype(jnp.uint32) ^ sign, lo)


_MASK_WORD = 32


def live_positions(live, cap: int):
    """(idx, keep): the positions of the first `cap` live rows of a mask,
    in their order, and which of the `cap` slots hold one. The mask is
    read as 32-row words: a running count of the words' populations, a
    binary search per OUTPUT slot over that count (a table a 32nd of the
    frame: the 21 steps over 39.6 M rows gather from 5 MB, not from the
    158 MB a per-row count takes), one gather of the word, and the slot's
    bit found in it by arithmetic. Third: whether more than `cap` rows
    are live (the rest would be lost)."""
    n = live.shape[0]
    pad = (-n) % _MASK_WORD
    if pad:
        live = jnp.concatenate([live, jnp.zeros((pad,), live.dtype)])
    lanes = jnp.arange(_MASK_WORD, dtype=jnp.uint32)
    words = jnp.sum(live.reshape(-1, _MASK_WORD).astype(jnp.uint32) << lanes,
                    axis=1, dtype=jnp.uint32)
    cum = running(jax.lax.population_count(words).astype(jnp.int32))
    slot = jnp.arange(cap, dtype=jnp.int32)
    at = jnp.searchsorted(cum, slot, side="right", method="scan")
    at = jnp.minimum(at, cum.shape[0] - 1).astype(jnp.int32)
    word = jnp.take(words, at, axis=0)
    rank = slot - (jnp.take(cum, at, axis=0)
                   - jax.lax.population_count(word).astype(jnp.int32))
    bit = jnp.zeros_like(slot)
    for width in (16, 8, 4, 2, 1):      # the rank-th set bit of the word
        low = word & jnp.uint32((1 << width) - 1)
        below = jax.lax.population_count(low).astype(jnp.int32)
        high = rank >= below
        rank = jnp.where(high, rank - below, rank)
        word = jnp.where(high, word >> width, low)
        bit = bit + jnp.where(high, width, 0)
    keep = slot < cum[-1]
    idx = jnp.minimum(at * _MASK_WORD + bit, n - 1)
    return jnp.where(keep, idx, 0).astype(jnp.int32), keep, cum[-1] > cap
