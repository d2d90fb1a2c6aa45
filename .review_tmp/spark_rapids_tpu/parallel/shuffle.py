"""ICI all-to-all partition exchange — the TPU-native shuffle slot.

The reference repo has no in-repo shuffle (SURVEY.md §2.4): partition exchange
lives one level up in spark-rapids' UCX shuffle manager, and the JNI layer only
models shuffle *threads* as a priority class. On TPU the equivalent first-class
component (BASELINE.json north star) keeps partition exchange on-device: rows
are hash-partitioned with Spark's murmur3 pmod, bucketed to a fixed per-peer
capacity, and exchanged over ICI with `jax.lax.all_to_all` inside `shard_map`.

Design notes (TPU-first):
- XLA needs static shapes, so the exchange uses fixed-capacity buckets
  (capacity = ceil(rows_per_shard / P) * slack). Overflowing rows would be
  dropped; callers size slack for their skew, and `exchange` returns per-bucket
  counts so overflow is detectable (the moral equivalent of the reference's
  SplitAndRetry contract: detect, then retry with a bigger capacity).
- The bucketing sort is a single stable `argsort` on partition id — this is
  the radix-partition step of a shuffle, fused by XLA with the gathers.
- Works identically on a CPU-host virtual mesh (tests) and a real slice: only
  the Mesh construction differs.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def make_mesh(n_devices: Optional[int] = None, axis: str = "data",
              cpu_fallback: bool = False) -> Mesh:
    """Build a 1-D device mesh. `cpu_fallback=True` is for validation runs on
    underprovisioned machines only (it substitutes virtual CPU devices, whose
    count is configurable even when the default backend is a TPU); production
    callers must leave it False so a short slice fails fast instead of
    silently running on host."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices and cpu_fallback:
            try:
                devs = jax.devices("cpu")
            except RuntimeError:
                pass
        if len(devs) < n_devices:
            raise ValueError(
                f"requested a {n_devices}-device mesh but only {len(devs)} "
                f"devices are visible")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (axis,))


def partition_ids(hashes: jnp.ndarray, num_partitions: int) -> jnp.ndarray:
    """Spark's `pmod(hash, numPartitions)` partitioner (non-negative mod)."""
    h = hashes.astype(jnp.int32)
    m = jnp.int32(num_partitions)
    r = jax.lax.rem(h, m)
    return jnp.where(r < 0, r + m, r).astype(jnp.int32)


def build_partition_map(part: jnp.ndarray, num_partitions: int,
                        capacity: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Bucket local rows by target partition into fixed-capacity slots.

    Returns (gather_idx (P, capacity) int32 row indices into the local shard,
             valid (P, capacity) bool, counts (P,) int32). Rows beyond
    `capacity` for a bucket are dropped (reported via counts > capacity).
    """
    n = part.shape[0]
    order = jnp.argsort(part, stable=True)            # radix-partition step
    sorted_part = part[order]
    # start offset of each partition in the sorted order
    starts = jnp.searchsorted(sorted_part, jnp.arange(num_partitions, dtype=part.dtype))
    ends = jnp.searchsorted(sorted_part, jnp.arange(num_partitions, dtype=part.dtype),
                            side="right")
    counts = (ends - starts).astype(jnp.int32)
    slot = jnp.arange(capacity, dtype=jnp.int32)[None, :]           # (P, cap)
    src = starts[:, None].astype(jnp.int32) + slot
    valid = slot < counts[:, None]
    src = jnp.clip(src, 0, max(n - 1, 0))
    gather_idx = order[src].astype(jnp.int32)
    return gather_idx, valid, counts


def _exchange_local(axis: str, num_partitions: int, capacity: int,
                    part: jnp.ndarray, *payloads: jnp.ndarray):
    """Per-shard body: bucket rows, all_to_all the buckets over `axis`."""
    gather_idx, valid, counts = build_partition_map(part, num_partitions, capacity)
    out = []
    for p in payloads:
        bucketed = jnp.take(p, gather_idx, axis=0)        # (P, cap, ...)
        zero = jnp.zeros((), dtype=p.dtype)
        mask = valid.reshape(valid.shape + (1,) * (bucketed.ndim - 2))
        bucketed = jnp.where(mask, bucketed, zero)
        # (P, cap, ...) -> exchange bucket p to peer p
        recv = jax.lax.all_to_all(bucketed, axis, split_axis=0, concat_axis=0,
                                  tiled=True)              # (P, cap, ...) one bucket/peer
        out.append(recv.reshape((-1,) + recv.shape[2:]))   # (P*cap, ...) rows for me
    # exchange only the (P,) sent counts and rebuild the mask receiver-side —
    # capacity× less ICI traffic than shipping the full bool mask
    sent = jnp.minimum(counts, capacity)
    sent_recv = jax.lax.all_to_all(sent, axis, split_axis=0, concat_axis=0,
                                   tiled=True)
    slot = jnp.arange(capacity, dtype=jnp.int32)[None, :]
    recv_valid = slot < sent_recv[:, None]
    return tuple(out), recv_valid.reshape(-1), counts, sent


def exchange(mesh: Mesh, part: jnp.ndarray, payloads: Sequence[jnp.ndarray],
             capacity: int, axis: str = "data"):
    """All-to-all repartition: rows of `payloads` move to the shard given by
    `part` (values in [0, n_shards)). All arrays are sharded on axis 0.

    Returns (payloads_out, valid, counts): payloads_out rows are grouped by
    source shard with `valid` marking live slots; counts is the (global-view)
    per-source bucket histogram for overflow detection.
    """
    num_partitions = mesh.shape[axis]
    body = partial(_exchange_local, axis, num_partitions, capacity)
    specs = P(axis)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(specs,) + tuple(specs for _ in payloads),
        out_specs=(tuple(specs for _ in payloads), specs, specs, specs))
    return fn(part, *payloads)


def repartition_table(mesh: Mesh, hashes: jnp.ndarray,
                      columns: Dict[str, jnp.ndarray],
                      slack: float = 2.0, axis: str = "data"):
    """Hash-repartition named fixed-width columns across the mesh.

    The host-facing wrapper: picks capacity from the row count and `slack`,
    computes Spark pmod partition ids from `hashes`, and runs the exchange.
    Returns (columns_out, valid, counts, capacity); any counts > capacity
    means rows were dropped — retry with larger slack.
    """
    n = hashes.shape[0]
    p = mesh.shape[axis]
    capacity = max(1, math.ceil(n / p / p * slack))
    part = partition_ids(hashes, p)
    names = list(columns)
    outs, valid, counts, _ = exchange(mesh, part, [columns[k] for k in names],
                                      capacity, axis)
    return {k: v for k, v in zip(names, outs)}, valid, counts, capacity
