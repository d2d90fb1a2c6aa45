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
(`ops/aggregate.py`) both call it.
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
