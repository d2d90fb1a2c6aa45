"""Scatter-free, sort-free bucket partitioning for the shuffle hot path.

Round-1 measurements on the chip (docs/architecture.md): at 10M rows,
`jnp.searchsorted` ≈ 2 s (≈log₂n whole-array gather passes) and scatter-add
under x64 emulation ≈ 930 ms, while the ops the VPU loves — compares,
cumsum, block reduces — are tens of ms. `build_partition_map`
(parallel/shuffle.py) pays one stable sort + two searchsorted calls per
exchange; the functions here produce the same information from a single
streaming pass:

    histogram:  counts[b] = Σ rows (part == b)      — compare-reduce blocks
    ranks:      rank[r]   = #prior rows in r's bucket — running-count scan

Both are `lax.scan` over row blocks carrying a (P,) running count: no sort,
no searchsorted, no scatter. Memory is O(block × P) for the transient
one-hot, streamed block by block. `build_partition_map_scan` is a drop-in
replacement for `build_partition_map` (one int32 set-scatter builds the
(P, capacity) gather map from the ranks — a *set* scatter of row ids, not
the emulated-u64 add-scatter the measurement flagged).

The Pallas explicit-kernel tier of the same histogram lives in
parallel/partition_pallas.py; tests/test_partition.py holds all three equal.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

_DEFAULT_BLOCK = 65536


def _pad_blocks(part: jnp.ndarray, num_partitions: int, block_rows: int):
    n = part.shape[0]
    m = max(1, math.ceil(n / block_rows))
    pad = m * block_rows - n
    # out-of-range id: matches no bucket, so padding never counts
    padded = jnp.concatenate(
        [part.astype(jnp.int32),
         jnp.full((pad,), num_partitions, jnp.int32)]) if pad else \
        part.astype(jnp.int32)
    return padded.reshape(m, block_rows), n


def partition_histogram(part: jnp.ndarray, num_partitions: int,
                        block_rows: int = _DEFAULT_BLOCK) -> jnp.ndarray:
    """(P,) int32 bucket counts via blocked compare-reduce (no scatter)."""
    blocks, _ = _pad_blocks(part, num_partitions, block_rows)
    buckets = jnp.arange(num_partitions, dtype=jnp.int32)

    def body(acc, blk):
        onehot = (blk[:, None] == buckets[None, :])
        return acc + jnp.sum(onehot, axis=0, dtype=jnp.int32), None

    counts, _ = jax.lax.scan(body, jnp.zeros((num_partitions,), jnp.int32),
                             blocks)
    return counts


def partition_ranks(part: jnp.ndarray, num_partitions: int,
                    block_rows: int = _DEFAULT_BLOCK
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stable intra-bucket rank per row + (P,) counts, one streaming pass.

    rank[r] = number of earlier rows with the same partition id — exactly
    the slot a stable radix partition assigns. Scan blocks carry the (P,)
    running counts; within a block the rank is an exclusive cumsum of the
    one-hot matrix gathered back through the same one-hot (a multiply-sum,
    not an indexed gather)."""
    blocks, n = _pad_blocks(part, num_partitions, block_rows)
    buckets = jnp.arange(num_partitions, dtype=jnp.int32)

    def body(running, blk):
        onehot = (blk[:, None] == buckets[None, :]).astype(jnp.int32)
        csum = jnp.cumsum(onehot, axis=0)
        excl = csum - onehot
        rank = jnp.sum(onehot * (excl + running[None, :]), axis=1)
        return running + csum[-1], rank

    counts, ranks = jax.lax.scan(
        body, jnp.zeros((num_partitions,), jnp.int32), blocks)
    return ranks.reshape(-1)[:n], counts


def build_partition_map_scan(part: jnp.ndarray, num_partitions: int,
                             capacity: int):
    """Same contract as shuffle.build_partition_map — (gather_idx (P, cap),
    valid (P, cap), counts (P,)) — built from the streaming ranks instead
    of sort + searchsorted. Rows past a bucket's capacity are dropped and
    reported via counts > capacity (the SplitAndRetry overflow signal)."""
    n = part.shape[0]
    ranks, counts = partition_ranks(part, num_partitions)
    dest = jnp.where(ranks < capacity,
                     part.astype(jnp.int32) * capacity + ranks,
                     jnp.int32(num_partitions * capacity))
    flat = jnp.zeros((num_partitions * capacity,), jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    gather_idx = flat.reshape(num_partitions, capacity)
    slot = jnp.arange(capacity, dtype=jnp.int32)[None, :]
    valid = slot < counts[:, None]
    return gather_idx, valid, counts
