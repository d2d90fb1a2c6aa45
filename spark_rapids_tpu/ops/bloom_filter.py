"""Spark-wire-compatible bloom filter: create / put / merge / probe.

TPU-native re-design of the reference's bloom filter
(src/main/cpp/src/bloom_filter.cu, BloomFilter.java:42-97). Spark semantics
(org.apache.spark.util.sketch.BloomFilterImpl):

- item hash: h1 = murmur3_32(long, seed=0), h2 = murmur3_32(long, seed=h1);
  k probes combined = h1 + i*h2 (i = 1..k, int32 wraparound); negative
  combined is bit-flipped (~); bit index = combined % num_bits
  (bloom_filter.cu:75-87).
- wire format: 12-byte big-endian header {version=1, num_hashes, num_longs}
  followed by num_longs big-endian int64 words; bit j of the filter lives in
  long j>>6 at position j&63 from the LSB (bloom_filter.cu:46-60 encodes the
  same layout via word/byte swizzles on the raw BE buffer).

Where the reference mutates the serialized buffer in place with atomicOr and
reads it through an index-swizzle, here the device-resident form is an
*unpacked* bit vector (one uint8 lane per bit — scatter-max for put, gather
for probe, both single fused XLA ops), and the BE swizzle happens only in
serialize()/deserialize(). The wire bytes are identical.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes
from ..columnar import Column
from ..dtypes import Kind
from .hash import _mm_fixed, _words_u32

SPARK_BLOOM_FILTER_VERSION = 1
HEADER_SIZE = 12


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BloomFilter:
    """Device-resident bloom filter: unpacked bits + static header fields."""
    bits: jnp.ndarray          # (num_longs*64,) uint8, 0/1
    num_hashes: int
    num_longs: int

    def tree_flatten(self):
        return (self.bits,), (self.num_hashes, self.num_longs)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(bits=leaves[0], num_hashes=aux[0], num_longs=aux[1])

    @property
    def num_bits(self) -> int:
        return self.num_longs * 64


def bloom_filter_create(num_hashes: int, num_longs: int) -> BloomFilter:
    """New empty filter (bloom_filter.cu:225-253)."""
    if num_hashes <= 0 or num_longs <= 0:
        raise ValueError("num_hashes and num_longs must be positive")
    return BloomFilter(bits=jnp.zeros((num_longs * 64,), jnp.uint8),
                       num_hashes=num_hashes, num_longs=num_longs)


def _spark_bit_indexes(values: jnp.ndarray, num_hashes: int, num_bits: int):
    """(n,) int64 -> (n, k) int32 bit indexes per Spark BloomFilterImpl."""
    u64 = values.astype(jnp.uint64)
    words = _words_u32(u64, 8)                       # (n, 2) LE words
    h1 = _mm_fixed(jnp.zeros(values.shape, jnp.uint32), words, 8)
    h2 = _mm_fixed(h1, words, 8)
    i = jnp.arange(1, num_hashes + 1, dtype=jnp.uint32)[None, :]
    combined = h1[:, None] + i * h2[:, None]          # uint32 wraparound
    neg = (combined >> jnp.uint32(31)) != 0
    combined = jnp.where(neg, ~combined, combined)    # bit-flip negatives
    return (combined.astype(jnp.int64) % jnp.int64(num_bits)).astype(jnp.int32)


def bloom_filter_put(bf: BloomFilter, col: Column,
                     sort_indices: bool = False) -> BloomFilter:
    """Insert a LONG column's valid rows; returns the updated filter
    (bloom_filter.cu:255-275). Functional: the input filter is unchanged.

    The reference's build kernel is an atomicOr scatter; XLA has no atomics,
    so this is a scatter-max over the unpacked bit vector. `sort_indices=True`
    sorts the bit positions first and passes `indices_are_sorted` to the
    scatter — one extra sort buys XLA's much cheaper sorted-scatter lowering
    on TPU; pick per batch size (the bench sweeps both).

    Pallas finding (round-2 mandate): an explicit TPU kernel does not have
    a path that beats this. TPU Pallas has no atomics either, so a kernel
    must serialize bit-sets; the two candidate shapes both lose —
    (a) one-hot OR accumulation compares every row block against every
    bits word: O(rows x num_bits/128) VPU ops, ~500x more work than the
    hash itself for Spark's 1-8 MiB filters; (b) per-row scalar stores
    into a VMEM-resident bits buffer is exactly what XLA's sorted-scatter
    lowering already emits, minus its run-length coalescing of duplicate
    words. The sort+scatter formulation IS the TPU-native atomicOr
    (tests/test_bloom_filter.py holds both scatter modes to the same
    bits; neither is measured on the chip)."""
    if col.dtype.kind != Kind.INT64:
        raise TypeError("bloom filter input must be INT64")
    idx = _spark_bit_indexes(col.data, bf.num_hashes, bf.num_bits)
    if col.validity is not None:
        # route null rows' probes to a dummy slot past the end (dropped)
        idx = jnp.where(col.validity[:, None], idx, jnp.int32(bf.num_bits))
    flat = idx.reshape(-1)
    if sort_indices:
        flat = jnp.sort(flat)
        bits = bf.bits.at[flat].max(jnp.uint8(1), mode="drop",
                                    indices_are_sorted=True)
    else:
        bits = bf.bits.at[flat].max(jnp.uint8(1), mode="drop")
    return BloomFilter(bits=bits, num_hashes=bf.num_hashes, num_longs=bf.num_longs)


def bloom_filter_merge(filters: list) -> BloomFilter:
    """OR filters with identical parameters (bloom_filter.cu:277-337)."""
    if not filters:
        raise ValueError("requires at least one bloom filter")
    f0 = filters[0]
    for f in filters[1:]:
        if f.num_hashes != f0.num_hashes or f.num_longs != f0.num_longs:
            raise ValueError("Mismatch of bloom filter parameters")
    bits = f0.bits
    for f in filters[1:]:
        bits = bits | f.bits
    return BloomFilter(bits=bits, num_hashes=f0.num_hashes, num_longs=f0.num_longs)


def bloom_filter_probe(col: Column, bf: BloomFilter) -> Column:
    """BOOL column: True where the row might be in the filter; nulls pass
    through (bloom_filter.cu:339-366)."""
    if col.dtype.kind != Kind.INT64:
        raise TypeError("bloom filter input must be INT64")
    idx = _spark_bit_indexes(col.data, bf.num_hashes, bf.num_bits)
    hit = jnp.take(bf.bits, idx, axis=0) != 0         # (n, k)
    found = jnp.all(hit, axis=1)
    return Column(dtype=dtypes.BOOL, length=col.length, data=found,
                  validity=col.validity)


# ---------------------------------------------------------------------------
# Spark wire format (big-endian; BloomFilterImpl.writeTo)
# ---------------------------------------------------------------------------

def bloom_filter_serialize(bf: BloomFilter) -> jnp.ndarray:
    """(12 + num_longs*8,) uint8 buffer in Spark's serialized form."""
    header = np.array([SPARK_BLOOM_FILTER_VERSION, bf.num_hashes, bf.num_longs],
                      dtype=">i4").tobytes()
    # pack bits LSB-first into longs, then emit each long big-endian
    b = bf.bits.reshape(bf.num_longs, 8, 8)           # (longs, byte, bitpos)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    byts = jnp.sum(b.astype(jnp.uint32) * weights[None, None, :].astype(jnp.uint32),
                   axis=2).astype(jnp.uint8)          # (longs, 8) LSB-first bytes
    be = byts[:, ::-1].reshape(-1)                    # big-endian byte order
    return jnp.concatenate([jnp.asarray(np.frombuffer(header, np.uint8)), be])


def bloom_filter_deserialize(buf) -> BloomFilter:
    """Parse a Spark-serialized filter buffer (uint8 array or bytes)."""
    raw = np.asarray(buf, dtype=np.uint8)
    if raw.size < HEADER_SIZE:
        raise ValueError("Encountered truncated bloom filter")
    version, num_hashes, num_longs = np.frombuffer(raw[:HEADER_SIZE].tobytes(), ">i4")
    if version != SPARK_BLOOM_FILTER_VERSION:
        raise ValueError("Unexpected bloom filter version")
    if num_longs <= 0:
        raise ValueError("Invalid empty bloom filter size")
    if raw.size != HEADER_SIZE + num_longs * 8:
        raise ValueError("Encountered invalid/mismatched bloom filter buffer data")
    be = jnp.asarray(raw[HEADER_SIZE:]).reshape(num_longs, 8)
    byts = be[:, ::-1]                                # back to LSB-first bytes
    bits = ((byts[:, :, None] >> jnp.arange(8, dtype=jnp.uint8)[None, None, :])
            & jnp.uint8(1)).reshape(-1)
    return BloomFilter(bits=bits.astype(jnp.uint8),
                       num_hashes=int(num_hashes), num_longs=int(num_longs))
