"""Pallas TPU TopK kernel: blockwise local top-k in VMEM, one cross-block
merge — replacing the full global sort the generic lowering pays.

The optimizer's `limit_pushdown` rule produces TopK nodes (Sort+Limit) and
both executor tiers lower them through `ops.sort_table` — an O(n log n)
global sort that materializes the WHOLE sorted relation to keep `n` rows.
This kernel crosses HBM once: each block of rows computes its local top-k
entirely in VMEM (k lexicographic-min selection passes over the block — a
handful of VPU reductions each, no sort), emitting k candidate tuples per
block; one tiny XLA merge over the `blocks x k` candidates (thousands of
rows, not millions) picks the global top-k. Registered with the kernel
registry (ops/registry.py) as `topk`/"pallas" for the TPU backend; the
sort-based lowering stays the universal fallback.

Exactness contract (the registry parity suite pins it): candidate tuples
are the SAME orderable operands `ops.sort_table` sorts — built by
`ops.sort._key_operands`, so null rank, NaN total order, -0.0
normalization and per-key descending transforms match Spark comparison
semantics bit for bit — mapped to unsigned u32 words, with the row index
appended as the final word so ties resolve exactly like the stable sort.
Unsupported signatures (string/decimal128 keys, k > 128) decline at
registry-lookup time and the fallback runs.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..columnar import Column, Table
from ..dtypes import Kind
from .gather import take
from .hash_pallas import _to_tiles
from .sort import _key_operands

_LANES = 128
_U32 = jnp.uint32
_SENTINEL = jnp.uint32(0xFFFFFFFF)

# key dtypes whose _key_operands output is i32/i64 words this kernel can
# map to unsigned planes (strings explode into per-word operands of data-
# dependent count; decimal128 needs 4 limbs — both decline to the fallback)
_SUPPORTED_KINDS = frozenset(k.value for k in (
    Kind.BOOL, Kind.INT8, Kind.INT16, Kind.INT32, Kind.INT64, Kind.DATE32,
    Kind.TIMESTAMP_US, Kind.TIMESTAMP_S, Kind.TIMESTAMP_MS,
    Kind.DECIMAL32, Kind.DECIMAL64, Kind.FLOAT32, Kind.FLOAT64))

MAX_K = 128     # one lane row of selections per block; larger limits fall
#                 back to the global sort (k selection passes stop paying)


def _signed_to_u32_words(op: jnp.ndarray) -> List[jnp.ndarray]:
    """One signed sort operand -> 1-2 u32 words whose unsigned lexicographic
    order equals the operand's signed order (bias the sign bit; 64-bit
    operands split hi/lo, hi compared first)."""
    if op.dtype in (jnp.int8, jnp.int16, jnp.int32, jnp.bool_):
        w = jax.lax.bitcast_convert_type(op.astype(jnp.int32), _U32)
        return [w ^ jnp.uint32(0x80000000)]
    if op.dtype == jnp.int64:
        u = jax.lax.bitcast_convert_type(op, jnp.uint64) \
            ^ jnp.uint64(0x8000000000000000)
        return [(u >> jnp.uint64(32)).astype(_U32),
                (u & jnp.uint64(0xFFFFFFFF)).astype(_U32)]
    raise TypeError(f"topk pallas: unexpected operand dtype {op.dtype}")


def _order_words(table: Table, keys: Sequence[str],
                 ascending: Sequence[bool],
                 alive: Optional[jnp.ndarray]) -> List[jnp.ndarray]:
    """The candidate tuple, most-significant word first: [alive rank,]
    per-key orderable words (exactly _key_operands' operands, unsigned-
    mapped), row iota last (stable-sort tiebreak)."""
    n = table.num_rows
    words: List[jnp.ndarray] = []
    if alive is not None:
        # dead rows sort behind every live row, like sort_table_capped
        words.append(jnp.where(alive, jnp.uint32(0), jnp.uint32(1)))
    for name, asc in zip(keys, ascending):
        for op in _key_operands(table[name], bool(asc), None):
            words.extend(_signed_to_u32_words(op))
    words.append(jnp.arange(n, dtype=_U32))
    return words


def _topk_kernel_body(k: int, n_words: int, refs):
    in_refs, out_ref = refs[:n_words], refs[n_words]
    # Mosaic has no reductions over unsigned integers: the min-extraction
    # runs on the order-preserving int32 image of each u32 word (flip the
    # top bit, reinterpret), mapped back on the way out. Constants are
    # built in-kernel: a module-level jnp constant would be a captured
    # array.
    flip = jnp.uint32(0x80000000)
    snt = jnp.int32(0x7FFFFFFF)    # image of the u32 sentinel 0xFFFFFFFF
    words = [jax.lax.bitcast_convert_type(r[...] ^ flip, jnp.int32)
             for r in in_refs]
    # the live-candidate mask is carried as int32: Mosaic cannot legalize
    # an scf.for whose carry is a boolean vector
    mask = jnp.ones(words[0].shape, jnp.int32)
    k128 = out_ref.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, k128), 1)
    init = tuple(jnp.full((1, k128), snt) for _ in range(n_words))

    def body(i, carry):
        mask, sels = carry
        # lexicographic min of the masked tuples: narrow the candidate set
        # word by word (each step is one VPU reduction + one compare)
        m = mask != 0
        cur = []
        for w in words:
            mv = jnp.min(jnp.where(m, w, snt))
            m = m & (w == mv)
            cur.append(mv)
        # the iota word is unique, so m now holds at most one row; an
        # exhausted mask leaves the all-sentinel tuple (merged away later)
        mask = jnp.where(m, jnp.int32(0), mask)
        sels = tuple(jnp.where(lane == i, c, s) for c, s in zip(cur, sels))
        return mask, sels

    # int32 bounds: under x64 python-int bounds make the index an i64
    _, sels = jax.lax.fori_loop(jnp.int32(0), jnp.int32(k), body,
                                (mask, init))
    for wi in range(n_words):
        out_ref[wi:wi + 1, :] = \
            jax.lax.bitcast_convert_type(sels[wi], _U32) ^ flip


def _topk_words(words: List[jnp.ndarray], k: int, n: int,
                block_rows: int, interpret: Optional[bool]):
    """Run the blockwise kernel + merge; returns the k smallest candidate
    tuples as sorted word arrays (each (k,) u32)."""
    if block_rows < _LANES or block_rows % _LANES:
        raise ValueError(f"block_rows must be a multiple of {_LANES}, "
                         f"got {block_rows}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_pad = max(block_rows, ((n + block_rows - 1) // block_rows) * block_rows)
    M = n_pad // _LANES
    TM = block_rows // _LANES
    k128 = ((k + _LANES - 1) // _LANES) * _LANES
    B = M // TM
    n_words = len(words)
    tiles = [_to_tiles(w, n_pad, fill=_SENTINEL) for w in words]

    def kernel(*refs):
        _topk_kernel_body(k, n_words, refs)

    # index_map constants written `i - i` (not 0): under x64 a literal 0
    # traces as i64 and Mosaic rejects the mixed index tuple (the same
    # guard as ops/hash_pallas.py)
    in_specs = [pl.BlockSpec((TM, _LANES), lambda i: (i, i - i),
                             memory_space=pltpu.VMEM) for _ in tiles]
    # block-major output: the block's last two dims equal the array's (the
    # (8, 128) divisibility rule); the leading block axis is squeezed
    out_spec = pl.BlockSpec((None, n_words, k128),
                            lambda i: (i, i - i, i - i),
                            memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((B, n_words, k128), _U32)],
        in_specs=in_specs, out_specs=[out_spec],
        grid=(B,), interpret=interpret, name="pallas_topk")(*tiles)[0]
    # cross-block merge: B*k128 candidates (tiny) through one XLA sort
    cands = [out[:, wi, :].reshape(-1) for wi in range(n_words)]
    merged = jax.lax.sort(cands, num_keys=n_words, is_stable=False)
    return [m[:k] for m in merged]


# The eager tier's entry: one program a (k, rows). Called outside a jit,
# `pl.pallas_call` wraps a fresh closure and is lowered again on every
# request (ROADMAP S4; `q18.batch` compiled once a request, PR 34).
_topk_words_once = jax.jit(_topk_words, static_argnums=(1, 2, 3, 4))


def topk_table(table: Table, keys: Sequence[str],
               ascending: Sequence[bool], n: int,
               block_rows: int = 128 * 128,
               interpret: Optional[bool] = None) -> Table:
    """Eager-tier TopK: the first `n` rows of the sorted relation, exactly
    `ops.sort_table(...)` then `slice_table(0, n)` (stability included)."""
    rows = table.num_rows
    m = min(n, rows)
    if m == 0:
        empty = jnp.zeros((0,), jnp.int32)
        return Table([take(c, empty, _has_negative=False)
                      for c in table.columns], names=table.names)
    words = _order_words(table, keys, ascending, alive=None)
    merged = _topk_words_once(words, m, rows, block_rows, interpret)
    idx = merged[-1].astype(jnp.int32)      # iota word; no sentinels in the
    #                                         first m entries: real rows
    #                                         always precede padding
    return Table([take(c, idx, _has_negative=False) for c in table.columns],
                 names=table.names)


def topk_capped(table: Table, keys: Sequence[str],
                ascending: Sequence[bool], n: int,
                alive: jnp.ndarray,
                block_rows: int = 128 * 128,
                interpret: Optional[bool] = None):
    """Capped-tier TopK: returns (table of n rows, alive mask) — the top-n
    LIVE rows in sorted order (dead slots masked), jit-traceable. The
    fallback keeps the padded frame at full length; downstream capped
    operators accept any row count, so the narrower frame is free."""
    rows = table.num_rows
    k = min(n, rows) if rows else 0
    if k == 0 or rows == 0:
        empty = jnp.zeros((0,), jnp.int32)
        t = Table([take(c, empty, _has_negative=False)
                   for c in table.columns], names=table.names)
        return t, jnp.zeros((0,), bool)
    words = _order_words(table, keys, ascending, alive=alive)
    merged = _topk_words(words, k, rows, block_rows, interpret)
    live_total = jnp.sum(alive.astype(jnp.int32))
    n_live = jnp.minimum(jnp.int32(k), live_total)
    out_alive = jnp.arange(k, dtype=jnp.int32) < n_live
    idx = merged[-1]
    idx = jnp.where(out_alive, idx, jnp.uint32(0)).astype(jnp.int32)
    t = Table([take(c, idx, _has_negative=False) for c in table.columns],
              names=table.names)
    return t, out_alive


# ---- registry wiring --------------------------------------------------------

def make_signature(table: Table, keys: Sequence[str],
                   ascending: Sequence[bool], n: int, tier: str):
    from .registry import Signature
    return Signature.of([table[k] for k in keys], limit=n, tier=tier)


def _supports(sig) -> bool:
    if not (1 <= (sig.extra("limit") or 0) <= MAX_K):
        return False
    if sig.extra("tier") not in ("eager", "capped"):
        return False
    return all(k in _SUPPORTED_KINDS for k in sig.kinds)


from .registry import REGISTRY as _REGISTRY  # noqa: E402

_REGISTRY.register("topk", "xla", fallback=True)
_REGISTRY.register("topk", "pallas", fn=topk_table, backends=("tpu",),
                   supports=_supports)
