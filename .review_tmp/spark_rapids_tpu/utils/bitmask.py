"""Arrow packed-validity interop + bitmask combination.

The columnar substrate keeps validity as an unpacked bool vector (VPU-friendly);
these helpers convert to/from Arrow's LSB-first packed bitmask for wire parity,
and OR many packed masks together — the capability the reference exposes as
`bitmask_bitwise_or` (utilities.hpp:36, utilities.cu:32, used by the bloom
filter merge, bloom_filter.cu:277).
"""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp


def pack_validity(valid: jnp.ndarray) -> jnp.ndarray:
    """(n,) bool -> ceil(n/8) uint8, Arrow LSB-first bit order."""
    n = valid.shape[0]
    pad = (-n) % 8
    v = jnp.concatenate([valid.astype(jnp.uint8),
                         jnp.zeros((pad,), jnp.uint8)]) if pad else valid.astype(jnp.uint8)
    v = v.reshape(-1, 8)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    return jnp.sum(v * weights[None, :], axis=1, dtype=jnp.uint32).astype(jnp.uint8)


def unpack_validity(packed: jnp.ndarray, n: int) -> jnp.ndarray:
    """ceil(n/8) uint8 -> (n,) bool, Arrow LSB-first bit order."""
    bits = (packed[:, None] >> jnp.arange(8, dtype=jnp.uint8)[None, :]) & jnp.uint8(1)
    return bits.reshape(-1)[:n].astype(jnp.bool_)


def bitmask_bitwise_or(masks: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """OR N equal-length packed (or word) mask buffers (utilities.cu:32)."""
    if not masks:
        raise ValueError("requires at least one mask")
    out = masks[0]
    for m in masks[1:]:
        if m.shape != out.shape:
            raise ValueError("all masks must be the same length")
        out = out | m
    return out
