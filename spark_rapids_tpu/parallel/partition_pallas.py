"""Pallas TPU kernel for the shuffle bucket histogram.

The explicit-kernel tier of parallel/partition.py's compare-reduce
histogram (the reference computes this with an atomic-add CUDA kernel; TPU
has no atomics, so the kernel streams row blocks through VMEM and keeps the
(P,) accumulator resident across grid steps — the output block is revisited
by every step, so each input byte crosses HBM exactly once and the counts
never round-trip).

Layout: rows arrive as (TM, 128) int32 planes (natural tiling). Buckets are
capped at 128 (one lane plane); a real shuffle's peer count fits. Each grid
step unrolls a per-bucket compare+reduce — P block-wide reduces on the VPU,
~P ops/row total, vs the 930 ms emulated scatter-add the round-1
measurement flagged at 10M rows.

A/B status: CPU-validated (interpret mode) against partition_histogram and
compiled for v5e by tests/test_chip_compile.py; not measured on the chip.
No plan reaches this kernel and no pair of it against the sort-based or
scan design has run on hardware (ROADMAP D4a).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128


def _hist_kernel(P: int, TM: int):
    def kernel(part_ref, counts_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            counts_ref[...] = jnp.zeros_like(counts_ref)

        blk = part_ref[...]                                  # (TM, 128) i32
        sub = jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 1)
        acc = counts_ref[...]                                # (8, 128) i32
        # bucket b lives at (sublane 0, lane b); P block-reduces, unrolled
        for b in range(P):
            # summed in f32: Mosaic's integer sum widens to i64 under x64
            # ("64-bit types are not supported"); a block holds TM*128
            # <= 2^24 rows, so the f32 count is exact
            c = jnp.sum(jnp.where(blk == b, jnp.float32(1), jnp.float32(0))
                        ).astype(jnp.int32)
            acc = acc + jnp.where((sub == 0) & (lane == b), c, jnp.int32(0))
        counts_ref[...] = acc

    return kernel


def histogram_pallas(part: jnp.ndarray, num_partitions: int,
                     block_rows: int = 4096,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """(P,) int32 bucket counts; P <= 128 (one lane plane)."""
    if num_partitions > _LANES:
        raise ValueError(f"histogram_pallas supports up to {_LANES} buckets")
    if block_rows > 1 << 24:
        raise ValueError("histogram_pallas counts a block in f32: "
                         "block_rows must not exceed 2^24")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = part.shape[0]
    TM = max(8, block_rows // _LANES)
    per_block = TM * _LANES
    m = max(1, math.ceil(n / per_block))
    pad = m * per_block - n
    p32 = part.astype(jnp.int32)
    if pad:
        # out-of-range id: never matches a bucket
        p32 = jnp.concatenate(
            [p32, jnp.full((pad,), num_partitions, jnp.int32)])
    planes = p32.reshape(m * TM, _LANES)

    counts = pl.pallas_call(
        _hist_kernel(num_partitions, TM),
        out_shape=jax.ShapeDtypeStruct((8, _LANES), jnp.int32),
        # index-map constants written `i - i` (not 0): under x64 a literal
        # 0 traces as i64 and Mosaic refuses 64-bit types
        in_specs=[pl.BlockSpec((TM, _LANES), lambda i: (i, i - i))],
        out_specs=pl.BlockSpec((8, _LANES), lambda i: (i - i, i - i)),
        grid=(m,), interpret=interpret,
        name="pallas_partition_counts")(planes)
    return counts[0, :num_partitions]
