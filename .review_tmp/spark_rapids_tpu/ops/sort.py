"""Table sort: sorted_order / sort_by_key with Spark null/NaN semantics.

The reference stack gets its sorts from cudf (radix/merge sorts on device);
BASELINE.json's north star calls for the same capability TPU-side. TPU-first
design: ONE `jax.lax.sort` call with multiple key operands — XLA lowers
multi-operand sort to its native on-device sorter, so a k-key lexicographic
sort is a single fused device op, not k passes. Each logical key column is
transformed into 1+ orderable unsigned/int operands:

- null rank first (BEFORE/AFTER per key, Spark: asc→nulls first,
  desc→nulls last)
- signed ints: bitwise-NOT for descending (order-reversing, overflow-free)
- floats: IEEE-754 bits mapped to total-order ints (NaN greatest, like
  Spark; -0.0 normalized to 0.0 per Spark comparison semantics)
- DECIMAL128: 4 limb operands, top limb signed, rest unsigned
- strings: padded chars viewed as big-endian uint32 word operands +
  length tiebreak (byte-lexicographic, like Spark's UTF8String.compareTo)

Stability comes from `is_stable=True`, matching cudf::stable_sorted_order.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

from .. import dtypes
from ..columnar import Column, Table
from ..dtypes import Kind
from .gather import take_table

NULLS_FIRST = "first"
NULLS_LAST = "last"


def _float_total_order(x: jnp.ndarray) -> jnp.ndarray:
    """IEEE bits → monotone signed int; NaN sorts greatest (Spark)."""
    bits_t = jnp.int32 if x.dtype == jnp.float32 else jnp.int64
    # Spark: -0.0 == 0.0; canonicalize NaNs so all NaN payloads tie
    x = jnp.where(x == 0, jnp.zeros_like(x), x)
    x = jnp.where(jnp.isnan(x), jnp.full_like(x, jnp.nan), x)
    b = jax.lax.bitcast_convert_type(x, bits_t)
    # monotone map to signed order: positives keep their bits (already
    # increasing), negatives flip magnitude bits and land below zero
    sign_bit = jnp.asarray(jnp.iinfo(bits_t).min, bits_t)
    return jnp.where(b < 0, ~b ^ sign_bit, b)


def _descending(op: jnp.ndarray) -> jnp.ndarray:
    """Order-reversing transform (signed domain): x -> ~x."""
    return ~op


def _key_operands(col: Column, ascending: bool, null_precedence: Optional[str]):
    """Orderable operand list for one key column (ascending transforms)."""
    ops = []
    k = col.dtype.kind
    if k in (Kind.BOOL,):
        ops.append(col.data.astype(jnp.int32))
    elif col.dtype.is_integer or k in (Kind.DATE32, Kind.TIMESTAMP_US,
                                       Kind.TIMESTAMP_S, Kind.TIMESTAMP_MS,
                                       Kind.DECIMAL32, Kind.DECIMAL64):
        ops.append(col.data)
    elif col.dtype.is_floating:
        ops.append(_float_total_order(col.data))
    elif k == Kind.DECIMAL128:
        limbs = col.data  # (n, 4) uint32 little-endian
        ops.append(jax.lax.bitcast_convert_type(limbs[:, 3], jnp.int32))
        for i in (2, 1, 0):
            # unsigned limbs: bias to signed order by flipping the sign bit
            ops.append(jax.lax.bitcast_convert_type(limbs[:, i], jnp.int32)
                       ^ jnp.int32(-2**31))
    elif k == Kind.STRING:
        padded, lens = col.padded_chars()
        n, L = padded.shape
        pad4 = (-L) % 4
        if pad4:
            padded = jnp.pad(padded, ((0, 0), (0, pad4)))
        # explicit word count, not -1: reshape(-1) divides by zero on n == 0
        words = padded.reshape(n, (L + pad4) // 4, 4).astype(jnp.uint32)
        # big-endian packing: first byte most significant
        w = ((words[:, :, 0] << 24) | (words[:, :, 1] << 16)
             | (words[:, :, 2] << 8) | words[:, :, 3])
        for i in range(w.shape[1]):
            ops.append(jax.lax.bitcast_convert_type(w[:, i], jnp.int32)
                       ^ jnp.int32(-2**31))
        ops.append(lens)          # prefix-equal tiebreak: shorter first
    else:
        raise TypeError(f"unsupported sort key dtype {col.dtype}")

    if not ascending:
        ops = [_descending(o) for o in ops]

    # payload bytes under null slots are undefined — zero them so nulls
    # compare equal to each other and keep stable original order
    if col.validity is not None:
        ops = [jnp.where(col.validity, o, jnp.zeros((), o.dtype)) for o in ops]

    # null rank leads: Spark defaults asc→nulls first, desc→nulls last
    if col.validity is not None:
        if null_precedence is None:
            null_precedence = NULLS_FIRST if ascending else NULLS_LAST
        if null_precedence == NULLS_FIRST:
            rank = jnp.where(col.validity, jnp.int32(1), jnp.int32(0))
        else:
            rank = jnp.where(col.validity, jnp.int32(0), jnp.int32(1))
        ops.insert(0, rank)
    return ops


def sorted_order(keys: Union[Table, Sequence[Column], Column],
                 ascending: Union[bool, Sequence[bool]] = True,
                 null_precedence: Union[None, str, Sequence[Optional[str]]] = None,
                 stable: bool = True,
                 alive: Optional[jnp.ndarray] = None) -> Column:
    """INT32 gather map that sorts `keys` lexicographically
    (cudf::sorted_order / cudf::stable_sorted_order equivalent).

    `alive`, if given, is a (n,) bool excluding padded rows (the capped
    jit-pipeline contract): dead rows sink to the END regardless of their
    key bytes, so live output rows stay a prefix selected by the caller's
    `iota < live_count` mask."""
    if isinstance(keys, Column):
        cols = [keys]
    elif isinstance(keys, Table):
        cols = list(keys.columns)
    else:
        cols = list(keys)
    if not cols:
        raise ValueError("sort requires at least one key column")
    nk = len(cols)
    asc = [ascending] * nk if isinstance(ascending, bool) else list(ascending)
    if null_precedence is None or isinstance(null_precedence, str):
        nulls = [null_precedence] * nk
    else:
        nulls = list(null_precedence)
    if len(asc) != nk or len(nulls) != nk:
        raise ValueError("per-key option lists must match the key count")

    operands = []
    for c, a, npred in zip(cols, asc, nulls):
        operands.extend(_key_operands(c, a, npred))
    if alive is not None:
        operands = [jnp.where(alive, jnp.int32(0), jnp.int32(1))] + operands
    n = cols[0].length
    iota = jnp.arange(n, dtype=jnp.int32)
    out = jax.lax.sort([*operands, iota], num_keys=len(operands),
                       is_stable=stable)
    return Column(dtype=dtypes.INT32, length=n, data=out[-1])


def sort_table(table: Table,
               key_names: Optional[Sequence[Union[int, str]]] = None,
               ascending: Union[bool, Sequence[bool]] = True,
               null_precedence: Union[None, str, Sequence[Optional[str]]] = None,
               stable: bool = True) -> Table:
    """Sort whole rows by the given key columns (cudf::sort_by_key)."""
    if key_names is None:
        keys = list(table.columns)
    else:
        keys = [table[k] for k in key_names]
    order = sorted_order(keys, ascending, null_precedence, stable)
    # a permutation is never negative: skip take_table's any<0 sync
    return take_table(table, order.data, _has_negative=False)


def sort_table_capped(table: Table,
                      key_names: Optional[Sequence[Union[int, str]]] = None,
                      ascending: Union[bool, Sequence[bool]] = True,
                      null_precedence: Union[None, str,
                                             Sequence[Optional[str]]] = None,
                      stable: bool = True,
                      alive: Optional[jnp.ndarray] = None):
    """sort_table for the capped jit tier (the *_capped sibling of
    groupby_aggregate_capped / inner_join_capped): dead rows sink to the
    END regardless of key bytes. Returns (sorted Table, sorted alive mask)
    — live rows are a prefix."""
    if key_names is None:
        keys = list(table.columns)
    else:
        keys = [table[k] for k in key_names]
    order = sorted_order(keys, ascending, null_precedence, stable, alive)
    out = take_table(table, order.data, _has_negative=False)
    if alive is None:
        alive = jnp.ones((table.num_rows,), bool)
    return out, jnp.take(alive, order.data, axis=0)
