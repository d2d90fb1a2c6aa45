"""Pallas TPU hash-join build/probe over fixed-width key columns.

The engine's generic join (`ops/join.py`) is one union sort over both
sides' key operands — O((nl+nr) log) over the CONCATENATED relation, paid
even when the build side is a few hundred dimension rows. This module is
the classic build/probe split for that case, reusing `ops/hash_pallas`'s
u32 word-plane layout so the hash planes are split once and consumed
IN-KERNEL (the exact "planes already split" reuse case hash_pallas's
module docstring identifies as its win condition — no standalone hash
materialization pass):

- **build** (one `pallas_call`, `pallas_hash_join_build`): murmur3 bucket
  hashes of the build keys computed from the word planes on the VPU, then
  a VMEM-resident open-addressing table (capacity = 2x rows rounded to a
  power of two, linear probing) filled by PARALLEL insertion rounds: every
  unplaced row proposes `(h + probe_distance) & (C-1)`, the winner per free
  slot is the minimum row id (a masked sublane reduction), winners' key
  words land in the table via one-hot matrix products on the MXU (u16
  halves, one term per slot — bit-exact in f32), losers advance their
  probe distance. Insertion therefore lands equal keys in ascending-row
  chain order, which is what makes probe emission order match the
  sort-based fallback exactly.
- **probe** (two `pallas_call`s, both `pallas_hash_join_probe`): per
  128-row block, bucket hashes from the probe planes in-kernel, then a
  vectorized chain walk — each round gathers 128 slots in one one-hot
  matmul against the table matrix and compares raw key words; counting
  stops per-lane at the first empty slot (the linear-probing invariant). A
  count pass sizes the output exactly like the fallback's span kernel; an
  emit pass re-walks to the k-th match per output slot.

The three `pallas_call`s never run as programs of their own. The capped
entry (`inner_join_capped_pallas`) is traced inside its caller's program
(`jit_capped_plan`). The eager entry (`inner_join_pallas`) is TWO jitted
programs with the match count's read between them: `_count_matches` (both
sides' validity, the build call, the probe planes, the count call and the
sum of the counts) and, `total` known, `_emit_matches` (the expansion and
the emit call). Called outside a jit, `pl.pallas_call` wraps a fresh
`kernel` closure: the kernel bodies were traced to jaxprs and lowered again
on every request, with some dozens of single-op programs dispatched around
them (225 of `q3.share`'s 333 ms a request until PR 40, PERF.md).

Nulls never match (Spark equi-join): invalid build rows are never
inserted, invalid probe rows count zero — the same lvalid/rvalid masks the
fallback applies. Registered as `hash_join`/"pallas" for the TPU backend;
declines (strings/decimal128/floats, build side > MAX_BUILD rows — the
table must fit VMEM) run the union-sort fallback. Parity is asserted
pair-for-pair IN ORDER against `ops.inner_join` / `inner_join_capped` by
the registry parity suite.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import dtypes
from ..columnar import Column, Table
from ..dtypes import Kind
from ..utils.tracing import span
from .hash_pallas import (_exact_dot, _mm_fmix, _mm_round, _planes,
                          _to_tiles, _u32c, _u16_halves as _halves)
from .gather import gather_live
from .join import _require_x64, expand_rows

_LANES = 128
_U32 = jnp.uint32
_SEED = 42          # any fixed seed: build and probe share the chain

MAX_BUILD = 512     # table capacity tops out at 1024 slots; the (rows x
#                     capacity) insertion matrices and the per-block
#                     (128 x capacity) probe one-hots stay comfortably in
#                     VMEM. Bigger build sides decline to the union sort,
#                     which scales; this kernel is the small-dimension-
#                     table shape (the broadcast-join regime).

_SUPPORTED_KINDS = frozenset(k.value for k in (
    Kind.BOOL, Kind.INT8, Kind.INT16, Kind.INT32, Kind.DATE32,
    Kind.INT64, Kind.TIMESTAMP_US, Kind.DECIMAL32, Kind.DECIMAL64))


def _capacity(n_build: int) -> int:
    c = 256
    while c < 2 * n_build:
        c *= 2
    return c


def _layout_of(cols: Sequence[Column]) -> List[int]:
    """Per-key byte widths (the murmur chain's static layout). Static dtype
    facts only — no plane arrays are built (those are computed exactly once
    per side and threaded through the build/count/emit passes). MUST match
    hash_pallas._planes' nbytes per kind: decimals hash as longs (Spark),
    so DECIMAL32 is 8-byte despite its 4-byte storage."""
    eight = (Kind.INT64, Kind.TIMESTAMP_US, Kind.DECIMAL32, Kind.DECIMAL64)
    return [8 if c.dtype.kind in eight else 4 for c in cols]


def _key_planes(cols: Sequence[Column], n_pad: int):
    """Word planes of the key columns, shaped (1, n_pad) — the hash_pallas
    u32 words, minus validity planes (validity is an insert/probe mask
    here, not hashed). The build side keeps all rows in the LANE dimension
    (n_pad <= 2*MAX_BUILD, a multiple of 128) because the kernel transposes
    rows against the table's capacity axis; flattening (rows/128, 128)
    tiles in-kernel would be the minor-dim reshape Mosaic rejects
    (hash_pallas module docstring), so the host does it here in XLA."""
    planes, layout = [], []
    for c in cols:
        lo, hi, nbytes = _planes(c, normalize_zero=False)
        ws = [lo] if hi is None else [lo, hi]
        planes.extend(_to_tiles(w, n_pad, lanes=n_pad) for w in ws)
        layout.append(nbytes)
    return planes, layout


def _mm_hash(layout: List[int], words: List[jnp.ndarray]) -> jnp.ndarray:
    """Murmur3 bucket hash over per-column word tiles, in-kernel (the
    hash_pallas round/fmix chain, no validity selects — null exclusion is
    the caller's mask)."""
    h = jnp.full(words[0].shape, _u32c(_SEED))
    p = 0
    for nbytes in layout:
        nh = _mm_round(h, words[p])
        if nbytes == 8:
            nh = _mm_round(nh, words[p + 1])
        p += 2 if nbytes == 8 else 1
        h = _mm_fmix(nh ^ _U32(nbytes))
    return h


def _any(mask) -> jnp.ndarray:
    """In-kernel `jnp.any`: Mosaic's boolean reduce goes through f64 under
    x64 ("Only arrays with 32-bit element types can be converted to
    scalars"), so reduce the f32 image instead."""
    return jnp.max(mask.astype(jnp.float32)) > 0


# ---- build kernel ------------------------------------------------------------

def _build_kernel_body(layout, C: int, n_pad: int, refs):
    n_words = sum(2 if b == 8 else 1 for b in layout)
    in_refs = refs[:n_words + 1]
    out_refs = refs[n_words + 1:]          # occ, rowid, 2*n_words halves
    words = [r[...] for r in in_refs[:n_words]]      # (1, n_pad) blocks
    valid = in_refs[n_words][...] != _U32(0)
    h = _mm_hash(layout, words)
    halves = [hh for w in words for hh in _halves(w)]

    # integer iota, then cast: tpu.iota yields integer vectors only
    r_col = jax.lax.broadcasted_iota(jnp.int32, (n_pad, C), 0) \
        .astype(jnp.float32)
    c_ids = jax.lax.broadcasted_iota(jnp.int32, (n_pad, C), 1)
    big = jnp.float32(1e9)

    occ = jnp.zeros((1, C), jnp.float32)
    rowid = jnp.zeros((1, C), jnp.float32)
    tbl = tuple(jnp.zeros((1, C), jnp.float32) for _ in range(2 * n_words))
    p = jnp.zeros((1, n_pad), jnp.int32)
    # loop-carried masks travel as int32: Mosaic cannot legalize a loop
    # whose carry is a boolean vector
    placed = (~valid).astype(jnp.int32)    # invalid rows never insert

    def cond(st):
        d, p, placed, occ, rowid, tbl = st
        return _any(placed == 0) & (d < 2 * C + 2)

    def body(st):
        d, p, placed, occ, rowid, tbl = st
        placed = placed != 0
        slot = (h + p.astype(_U32)) & _u32c(C - 1)
        slot_col = jnp.transpose(slot.astype(jnp.int32))       # (n_pad, 1)
        unplaced_col = jnp.transpose((~placed).astype(jnp.float32)) > 0
        proposes = (slot_col == c_ids) & unplaced_col          # (n_pad, C)
        winner = jnp.min(jnp.where(proposes, r_col, big), axis=0,
                         keepdims=True)                        # (1, C)
        free = occ == 0
        won = free & (winner < big)
        onehot = (proposes & (r_col == winner) &
                  jnp.broadcast_to(free, proposes.shape)) \
            .astype(jnp.float32)                               # (n_pad, C)
        placed_now = jnp.transpose(
            jnp.sum(onehot, axis=1, keepdims=True)) > 0        # (1, n_pad)
        rowid = jnp.where(won, winner, rowid)
        tbl = tuple(
            jnp.where(won,
                      _exact_dot(half, onehot), t)
            for half, t in zip(halves, tbl))
        occ = jnp.where(won, jnp.float32(1), occ)
        placed = placed | placed_now
        p = p + jnp.where(placed, jnp.int32(0), jnp.int32(1))
        return d + 1, p, placed.astype(jnp.int32), occ, rowid, tbl

    _, _, _, occ, rowid, tbl = jax.lax.while_loop(
        cond, body, (jnp.int32(0), p, placed, occ, rowid, tbl))
    out_refs[0][...] = occ
    out_refs[1][...] = rowid
    for i, t in enumerate(tbl):
        out_refs[2 + i][...] = t


def _build_table(rcols: Sequence[Column], rvalid: jnp.ndarray, C: int,
                 interpret: bool) -> jnp.ndarray:
    """-> (C, 2 + 2*n_words) f32 table matrix: [occ, rowid, u16 halves of
    every key word]. Assembled from the build kernel's outputs; consumed by
    the probe kernels through one-hot matmul gathers."""
    n = rcols[0].length
    n_pad = max(_LANES, ((n + _LANES - 1) // _LANES) * _LANES)
    planes, layout = _key_planes(rcols, n_pad)
    n_words = len(planes)
    vplane = _to_tiles(rvalid.astype(_U32), n_pad, lanes=n_pad)

    def kernel(*refs):
        _build_kernel_body(layout, C, n_pad, refs)

    in_specs = [pl.BlockSpec((1, n_pad), lambda: (0, 0),
                             memory_space=pltpu.VMEM)
                for _ in range(n_words + 1)]
    out_shape = [jax.ShapeDtypeStruct((1, C), jnp.float32)
                 for _ in range(2 + 2 * n_words)]
    out_specs = [pl.BlockSpec((1, C), lambda: (0, 0),
                              memory_space=pltpu.VMEM)
                 for _ in range(2 + 2 * n_words)]
    outs = pl.pallas_call(
        kernel, out_shape=out_shape, in_specs=in_specs, out_specs=out_specs,
        interpret=interpret, name="pallas_hash_join_build")(*planes, vplane)
    return jnp.stack([o.reshape(-1) for o in outs], axis=1)


# ---- probe kernels -----------------------------------------------------------

def _count_kernel_body(layout, C: int, refs):
    n_words = sum(2 if b == 8 else 1 for b in layout)
    words = [refs[i][...] for i in range(n_words)]
    valid = refs[n_words][...] != _U32(0)
    tbl = refs[n_words + 1][...]
    out = refs[n_words + 2]

    h = _mm_hash(layout, words)
    h_col = jnp.transpose(h.astype(jnp.int32) & jnp.int32(C - 1))
    halves = [jnp.transpose(hh) for w in words for hh in _halves(w)]
    c_ids = jax.lax.broadcasted_iota(jnp.int32, (_LANES, C), 1)
    # int32 image: Mosaic cannot transpose a boolean tile
    active0 = jnp.transpose(valid.astype(jnp.int32))
    counts0 = jnp.zeros((_LANES, 1), jnp.int32)

    # loop-carried masks travel as int32 (see the build kernel)
    def cond(st):
        d, active, _ = st
        return _any(active != 0) & (d < C + 1)

    def body(st):
        d, active, counts = st
        active = active != 0
        slot = (h_col + d) & jnp.int32(C - 1)
        onehot = (slot == c_ids).astype(jnp.float32)
        g = _exact_dot(onehot, tbl)
        occ = g[:, 0:1] > 0
        eq = jnp.ones((_LANES, 1), bool)
        for j, ph in enumerate(halves):
            eq = eq & (g[:, 2 + j:3 + j] == ph)
        counts = counts + (active & occ & eq).astype(jnp.int32)
        return d + 1, (active & occ).astype(jnp.int32), counts

    _, _, counts = jax.lax.while_loop(cond, body,
                                      (jnp.int32(0), active0, counts0))
    out[...] = jnp.transpose(counts)


def _emit_kernel_body(layout, C: int, refs):
    n_words = sum(2 if b == 8 else 1 for b in layout)
    words = [refs[i][...] for i in range(n_words)]
    ktgt = jnp.transpose(
        jax.lax.bitcast_convert_type(refs[n_words][...], jnp.int32))
    tbl = refs[n_words + 1][...]
    out = refs[n_words + 2]

    h = _mm_hash(layout, words)
    h_col = jnp.transpose(h.astype(jnp.int32) & jnp.int32(C - 1))
    halves = [jnp.transpose(hh) for w in words for hh in _halves(w)]
    c_ids = jax.lax.broadcasted_iota(jnp.int32, (_LANES, C), 1)
    active0 = jnp.ones((_LANES, 1), jnp.int32)
    seen0 = jnp.zeros((_LANES, 1), jnp.int32)
    rmap0 = jnp.zeros((_LANES, 1), jnp.int32)

    def cond(st):
        d, active, seen, rmap, resolved = st
        return _any((active != 0) & (resolved == 0)) & (d < C + 1)

    def body(st):
        d, active, seen, rmap, resolved = st
        active, resolved = active != 0, resolved != 0
        slot = (h_col + d) & jnp.int32(C - 1)
        onehot = (slot == c_ids).astype(jnp.float32)
        g = _exact_dot(onehot, tbl)
        occ = g[:, 0:1] > 0
        rowid = g[:, 1:2].astype(jnp.int32)
        eq = jnp.ones((_LANES, 1), bool)
        for j, ph in enumerate(halves):
            eq = eq & (g[:, 2 + j:3 + j] == ph)
        match = active & occ & eq
        hit = match & ~resolved & (seen == ktgt)
        rmap = jnp.where(hit, rowid, rmap)
        resolved = resolved | hit
        seen = seen + match.astype(jnp.int32)
        return (d + 1, (active & occ).astype(jnp.int32), seen, rmap,
                resolved.astype(jnp.int32))

    _, _, _, rmap, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), active0, seen0, rmap0,
                     jnp.zeros((_LANES, 1), jnp.int32)))
    out[...] = jnp.transpose(rmap)


def _run_probe(body_fn, layout, C, planes, extra_plane, tbl, out_dtype,
               n_pad, interpret):
    n_words = len(planes)
    B = n_pad // _LANES

    def kernel(*refs):
        body_fn(layout, C, refs)

    # (B, 1, 128) planes with the leading grid axis squeezed: each block's
    # last two dims equal the array's (Mosaic's (8, 128) block rule)
    def row_spec():
        return pl.BlockSpec((None, 1, _LANES), lambda i: (i, i - i, i - i),
                            memory_space=pltpu.VMEM)

    in_specs = [row_spec() for _ in range(n_words + 1)]
    in_specs.append(pl.BlockSpec(tbl.shape, lambda i: (i - i, i - i),
                                 memory_space=pltpu.VMEM))
    out = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((B, 1, _LANES), out_dtype)],
        in_specs=in_specs, out_specs=[row_spec()],
        grid=(B,), interpret=interpret, name="pallas_hash_join_probe")(
            *[p.reshape(B, 1, _LANES) for p in planes],
            extra_plane.reshape(B, 1, _LANES), tbl)[0]
    return out.reshape(-1)


def _probe_counts(flat_planes, n: int, lvalid, layout, C, tbl, interpret):
    n_pad = max(_LANES, ((n + _LANES - 1) // _LANES) * _LANES)
    planes = [_to_tiles(p, n_pad) for p in flat_planes]
    vplane = _to_tiles(lvalid.astype(_U32), n_pad)
    counts = _run_probe(_count_kernel_body, layout, C, planes, vplane, tbl,
                        jnp.int32, n_pad, interpret)
    return counts[:n]


def _probe_emit(sel_planes, ktgt, layout, C, tbl, interpret):
    total = ktgt.shape[0]
    n_pad = max(_LANES, ((total + _LANES - 1) // _LANES) * _LANES)
    planes = [_to_tiles(p, n_pad) for p in sel_planes]
    kplane = _to_tiles(jax.lax.bitcast_convert_type(ktgt.astype(jnp.int32),
                                                _U32), n_pad)
    rmap = _run_probe(_emit_kernel_body, layout, C, planes, kplane, tbl,
                      jnp.int32, n_pad, interpret)
    return rmap[:total]


# ---- public entry points -----------------------------------------------------

def _side_valid(cols, n, alive=None):
    v = jnp.ones((n,), bool)
    for c in cols:
        if c.validity is not None:
            v = v & c.validity
    if alive is not None:
        v = v & alive
    return v


def _flat_planes(cols):
    out = []
    for c in cols:
        lo, hi, _ = _planes(c, normalize_zero=False)
        out.append(lo)
        if hi is not None:
            out.append(hi)
    return out


def _prep_probe(lcols, rcols, lvalid, rvalid, interpret):
    """-> (counts, probe planes, layout, C, tbl, interpret). The probe-side
    word planes are built ONCE here and reused by the emit pass (gathered
    at lsel) — the same planes-split-once economics the build side gets
    from consuming them in-kernel."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    C = _capacity(rcols[0].length)
    tbl = _build_table(rcols, rvalid, C, interpret)
    layout = _layout_of(rcols)
    lplanes = _flat_planes(lcols)
    counts = _probe_counts(lplanes, lcols[0].length, lvalid, layout, C,
                           tbl, interpret)
    return counts, lplanes, layout, C, tbl, interpret


def emit_planes(lcols: Sequence[Column]) -> int:
    """Planes `_emit_rows` gathers at every live slot: `starts` and the
    probe side's key words."""
    return 1 + sum(_layout_of(lcols)) // 4


@partial(jax.jit, static_argnames=("total",))
def _emit_rows(counts, lplanes, total: int):
    """-> (lsel, ktgt, sel_planes) for the emit pass's `total` slots: the
    slot's left row, which of that row's matches it is, and the row's
    probe planes, gathered over the live slots (ops/join.py:expand_rows,
    ops/gather.py:gather_live). Its jit names a scope (`jit(_emit_rows)`)
    in its callers' programs and is no program of its own: the eager entry
    reaches it inside `_emit_matches`, the capped one inside its caller's."""
    lsel, starts, live = expand_rows(counts, total)
    first, *sel_planes = gather_live([starts, *lplanes], lsel, live)
    return lsel, jnp.arange(total, dtype=jnp.int32) - first, sel_planes


# The eager entry's two programs (module docstring): what depends on shapes
# and dtypes alone is done once per shape, and `jax.jit`'s cache is the cache.

@partial(jax.jit, static_argnames=("interpret",))
def _count_matches(lcols, rcols, interpret: bool):
    """Stage A, from the key columns to the match count: both sides'
    validity, the build table, the probe planes, the count pass.
    -> (counts, probe planes, table matrix, sum of counts). One program a
    (key layout, left rows, right rows)."""
    lvalid = _side_valid(lcols, lcols[0].length)
    rvalid = _side_valid(rcols, rcols[0].length)
    counts, lplanes, _, _, tbl, _ = _prep_probe(lcols, rcols, lvalid, rvalid,
                                                interpret)
    return counts, lplanes, tbl, jnp.sum(counts)


@partial(jax.jit, static_argnames=("total", "layout", "interpret"))
def _emit_matches(counts, lplanes, tbl, total: int, layout: Tuple[int, ...],
                  interpret: bool):
    """Stage B, the expansion and the emit pass -> (lsel, rmap) of `total`
    pairs. One program a (key layout, `total`, table capacity): called
    eagerly, the expansion's loops and the emit kernel are fresh closures
    and would be lowered again on every call."""
    lsel, ktgt, sel_planes = _emit_rows(counts, lplanes, total)
    rmap = _probe_emit(sel_planes, ktgt, layout, tbl.shape[0], tbl, interpret)
    return lsel, rmap


def inner_join_pallas(left_keys, right_keys,
                      interpret: Optional[bool] = None):
    """Eager inner equi-join via hash build/probe: gather maps
    (left_map, right_map), pair-for-pair identical to `ops.inner_join`.
    Two cached programs with the match count's read between them."""
    from .join import _cols
    lcols, rcols = _cols(left_keys), _cols(right_keys)
    nl, nr = lcols[0].length, rcols[0].length
    if nl == 0 or nr == 0:
        e = jnp.zeros((0,), jnp.int32)
        return (Column(dtype=dtypes.INT32, length=0, data=e),
                Column(dtype=dtypes.INT32, length=0, data=e))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    counts, lplanes, tbl, matches = _count_matches(lcols, rcols, interpret)
    with span("ops.host_sync", site="join_pallas.inner"):
        total = int(jax.device_get(matches))    # the one host sync (same
        #                                         as the fallback's)
    if total == 0:
        e = jnp.zeros((0,), jnp.int32)
        return (Column(dtype=dtypes.INT32, length=0, data=e),
                Column(dtype=dtypes.INT32, length=0, data=e))
    lsel, rmap = _emit_matches(counts, lplanes, tbl, total,
                               tuple(_layout_of(rcols)), interpret)
    return (Column(dtype=dtypes.INT32, length=total, data=lsel),
            Column(dtype=dtypes.INT32, length=total, data=rmap))


def inner_join_capped_pallas(left_keys, right_keys, row_cap: int, *,
                             lalive=None, ralive=None,
                             interpret: Optional[bool] = None):
    """Capped inner equi-join (jit-traceable): (lmap, rmap, valid,
    overflow) with `ops.inner_join_capped`'s exact contract."""
    from .join import _cols
    _require_x64("inner_join_capped (pallas)")
    lcols, rcols = _cols(left_keys), _cols(right_keys)
    nl, nr = lcols[0].length, rcols[0].length
    if nl == 0 or nr == 0:
        z = jnp.zeros((row_cap,), jnp.int32)
        return z, z, jnp.zeros((row_cap,), bool), jnp.asarray(False)
    lvalid = _side_valid(lcols, nl, lalive)
    rvalid = _side_valid(rcols, nr, ralive)
    counts, lplanes, layout, C, tbl, interpret = _prep_probe(
        lcols, rcols, lvalid, rvalid, interpret)
    total = jnp.sum(counts.astype(jnp.int64))
    lsel, ktgt, sel_planes = _emit_rows(counts, lplanes, row_cap)
    rmap = _probe_emit(sel_planes, ktgt, layout, C, tbl, interpret)
    valid = jnp.arange(row_cap, dtype=jnp.int32) < total
    lmap = jnp.where(valid, lsel, 0)
    rmap = jnp.where(valid, jnp.clip(rmap, 0, max(nr - 1, 0)), 0)
    return lmap, rmap, valid, total > row_cap


# ---- registry wiring --------------------------------------------------------

def make_signature(lcols: Sequence[Column], rcols: Sequence[Column],
                   how: str, tier: str):
    from .registry import Signature
    kinds_match = all(a.dtype.kind == b.dtype.kind
                      for a, b in zip(lcols, rcols))
    return Signature.of(list(lcols) + list(rcols), how=how, tier=tier,
                        kinds_match=kinds_match,
                        build_rows=rcols[0].length if rcols else 0,
                        probe_rows=lcols[0].length if lcols else 0)


# The largest probe side the EAGER entry takes (defined down here: a line
# added above a `pallas_call` moves its location, which is part of every
# holding program's compile-cache key). From this many rows on an eager
# join with a small build side is the small-side path's (ops/join.py,
# which needs `ops/join_lookup.py:LOOKUP_LARGE` rows, fewer): the count
# kernel took 122 ns a probe row at 18 M and at 36 M rows against 366 build
# rows (2.196 and 4.391 s, and the emit kernel 0.34 and 0.68 s more; my
# chip run, PR 43, `q97.batch`'s date joins), where that path reads a row
# for 0.33 ns and moves it for 10 to 12. The kernel's only measured eager
# probe below is `q3.share`'s 333 K rows (67 ns a row against 193 build
# rows, 22.3 ms; PERF.md section 5), which stays here: between the two
# nothing was compared. The capped entry has no row count to branch on.
EAGER_MAX_PROBE = 1 << 20


def _supports(sig) -> bool:
    return (sig.extra("how") == "inner"
            and sig.extra("tier") in ("eager", "capped")
            and bool(sig.extra("kinds_match"))
            and (sig.extra("build_rows") or 0) <= MAX_BUILD
            and (sig.extra("tier") != "eager"
                 or (sig.extra("probe_rows") or 0) < EAGER_MAX_PROBE)
            and all(k in _SUPPORTED_KINDS for k in sig.kinds))


from .registry import REGISTRY as _REGISTRY  # noqa: E402

_REGISTRY.register("hash_join", "xla", fallback=True)
_REGISTRY.register("hash_join", "pallas", fn=inner_join_pallas,
                   backends=("tpu",), supports=_supports)
