"""Pallas TPU FusedSelect kernel: predicate evaluation + projection gather
in one `pallas_call`, so each input byte crosses HBM once.

The eager tier's FusedSelect (optimizer-fused Filter+Project, docs/
optimizer.md) lowers generically as mask = predicate(t); nonzero(mask);
per-column take — the predicate columns cross HBM to build the mask, the
mask crosses again for the index vector, and every projected column pays a
data-sized gather. This kernel does the whole front half in one HBM pass
per block: evaluate the predicate in VMEM (the plan expression tree is
pure elementwise jnp — see plan/expr.py — so the SAME `_BIN_FNS` run on
(1, N) tiles with identical semantics), then compact the selected rows of
every projection-referenced column in-block via one-hot matrix products on
the MXU:

    prefix  = mask  @ upper_tri          (in-block positions, exact in f32)
    onehot[r, q] = mask[r] & (pos[r] == q)
    out_q   = halves(x) @ onehot         (u32 planes split into u16 halves:
                                          each one-hot column has at most
                                          one term, so f32 stays bit-exact)

Per-block counts drive one tiny XLA epilogue (`jnp.repeat` over the block
count vector — the engine's blessed expansion idiom) that squeezes the
block-compacted planes into the final contiguous relation; columns travel
as exact-bitcast u32 word planes (1 plane for <=32-bit, lo/hi for 64-bit),
so any fixed-width dtype round-trips losslessly, validity riding as one
more plane.

Registered as `fused_select`/"pallas" for the TPU backend (ops/registry.py,
docs/kernels.md). Declines cleanly — strings/decimal128/nested anywhere,
float or 64-bit predicate inputs (no f64 emulation in-kernel: the same
guard class as row_conversion's traced-f64 rule), scalar-aggregate
predicates, out-of-int32 literals — and the XLA lowering runs instead.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..columnar import Column, Table
from ..dtypes import Kind
from ..utils.tracing import span
from .gather import take
from .hash_pallas import _exact_dot, _to_tiles, _u16_halves

_LANES = 128
_U32 = jnp.uint32

# predicate inputs must stay in the 32-bit lane domain (no in-kernel f64 /
# i64 emulation for arbitrary arithmetic); floats decline entirely — float
# literals promote to f64 under x64 and the fallback's f64 compare has no
# exact 32-bit kernel form
_PRED_KINDS = frozenset(k.value for k in (
    Kind.BOOL, Kind.INT8, Kind.INT16, Kind.INT32, Kind.DATE32))

# compacted (projection-referenced) columns: anything that round-trips
# through 1-2 exact u32 word planes
_DATA_KINDS_1 = (Kind.BOOL, Kind.INT8, Kind.INT16, Kind.INT32, Kind.DATE32,
                 Kind.FLOAT32, Kind.DECIMAL32)
_DATA_KINDS_2 = (Kind.INT64, Kind.TIMESTAMP_US, Kind.TIMESTAMP_S,
                 Kind.TIMESTAMP_MS, Kind.DECIMAL64, Kind.FLOAT64)
_DATA_KINDS = frozenset(k.value for k in _DATA_KINDS_1 + _DATA_KINDS_2)


# ---- exact u32 word planes (bit-preserving, unlike hash_pallas's
# normalized planes) ----------------------------------------------------------

def _encode_planes(col: Column) -> List[jnp.ndarray]:
    k = col.dtype.kind
    d = col.data
    if k == Kind.FLOAT32:
        return [jax.lax.bitcast_convert_type(d, _U32)]   # bits, not values
    if k in _DATA_KINDS_1:
        return [jax.lax.bitcast_convert_type(d.astype(jnp.int32), _U32)]
    if k in _DATA_KINDS_2:
        u = jax.lax.bitcast_convert_type(d.astype(col.dtype.storage_dtype()),
                                         jnp.uint64)
        return [(u & jnp.uint64(0xFFFFFFFF)).astype(_U32),
                (u >> jnp.uint64(32)).astype(_U32)]
    raise TypeError(f"fused_select pallas: unsupported dtype {col.dtype}")


def _decode_planes(dtype, planes: List[jnp.ndarray],
                   validity: Optional[jnp.ndarray]) -> Column:
    k = dtype.kind
    n = int(planes[0].shape[0])
    if k in _DATA_KINDS_1:
        i = jax.lax.bitcast_convert_type(planes[0], jnp.int32)
        if k == Kind.FLOAT32:
            d = jax.lax.bitcast_convert_type(planes[0], jnp.float32)
        elif k == Kind.BOOL:
            d = i != 0
        else:
            d = i.astype(dtype.storage_dtype())
    else:
        u = (planes[1].astype(jnp.uint64) << jnp.uint64(32)) \
            | planes[0].astype(jnp.uint64)
        d = jax.lax.bitcast_convert_type(u, dtype.storage_dtype())
    return Column(dtype=dtype, length=n, data=d, validity=validity)


def _pred_tile(kind: Kind, plane):
    """Typed predicate tile from a u32 word plane — in the column's OWN
    dtype, so arithmetic width/overflow semantics match the fallback."""
    i = jax.lax.bitcast_convert_type(plane, jnp.int32)
    if kind == Kind.BOOL:
        return i != 0
    if kind == Kind.INT8:
        return i.astype(jnp.int8)
    if kind == Kind.INT16:
        return i.astype(jnp.int16)
    return i   # INT32 / DATE32


# ---- predicate compilability + in-kernel evaluation --------------------------

def _pure_literal(e) -> bool:
    from ..plan import expr as pexpr
    if isinstance(e, pexpr.Literal):
        return True
    if isinstance(e, pexpr.BinOp):
        return _pure_literal(e.left) and _pure_literal(e.right)
    if isinstance(e, pexpr.UnaryOp):
        return _pure_literal(e.child)
    return False


def _compilable(e, table: Table) -> bool:
    from ..plan import expr as pexpr
    if isinstance(e, pexpr.ColumnRef):
        # the kernel reads data planes alone: a nullable input declines
        return (table[e.name].dtype.kind.value in _PRED_KINDS
                and table[e.name].validity is None)
    if isinstance(e, pexpr.Literal):
        if isinstance(e.value, bool):
            return True
        if isinstance(e.value, int):
            return -(2 ** 31) <= e.value < 2 ** 31
        return False
    if isinstance(e, pexpr.BinOp):
        # literal-only subtrees evaluate in PYTHON arithmetic in-kernel
        # (unbounded ints) where the fallback's weak-i64 arrays wrap —
        # the optimizer folds these anyway; decline the unfolded stragglers
        if _pure_literal(e):
            return False
        return _compilable(e.left, table) and _compilable(e.right, table)
    if isinstance(e, pexpr.UnaryOp):
        if _pure_literal(e):
            return False       # python ~True = -2 vs jnp logical not
        return _compilable(e.child, table)
    return False       # ScalarAgg and anything newer decline


def _eval_tiles(e, tiles: Dict[str, jnp.ndarray], shape):
    """plan/expr evaluation over kernel tiles: the SAME _BIN_FNS as
    Expr.evaluate, applied to (1, N) arrays instead of (n,) arrays —
    semantics match by construction. Literals stay RAW python scalars:
    they are weak-typed in jnp binops exactly like Literal.evaluate's
    weak `jnp.full` (the column dtype wins promotion in both paths), and
    they keep i64 broadcasts out of the kernel trace — Mosaic has no
    64-bit vector support, the same hazard class as the `i - i` index-map
    guard."""
    from ..plan import expr as pexpr
    if isinstance(e, pexpr.ColumnRef):
        return tiles[e.name]
    if isinstance(e, pexpr.Literal):
        return e.value
    if isinstance(e, pexpr.BinOp):
        return pexpr._BIN_FNS[e.op](_eval_tiles(e.left, tiles, shape),
                                    _eval_tiles(e.right, tiles, shape))
    if isinstance(e, pexpr.UnaryOp):
        v = _eval_tiles(e.child, tiles, shape)
        return ~v if e.op == "~" else -v
    raise TypeError(f"uncompilable expression {e!r}")   # guarded by supports


# ---- the kernel --------------------------------------------------------------

def _kernel_body(predicate, pred_layout, comp_planes: int, n: int, N: int,
                 refs):
    """pred_layout: [(name, Kind, plane_index)] for predicate tiles;
    refs = [plane_0..plane_{P-1}, out_0..out_{comp-1}, counts]. The first
    `comp_planes` input planes are also the compaction payload."""
    n_in = len(refs) - comp_planes - 1
    in_refs = refs[:n_in]
    out_refs = refs[n_in:n_in + comp_planes]
    cnt_ref = refs[-1]

    tiles = {name: _pred_tile(kind, in_refs[pi][...])
             for name, kind, pi in pred_layout}
    mask = _eval_tiles(predicate, tiles, (1, N))
    mask = mask.astype(jnp.bool_)
    # rows past n are padding, never selected
    i = pl.program_id(0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
    mask = mask & ((i * N + lane) < n)

    maskf = mask.astype(jnp.float32)
    r_ids = jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
    q_ids = jax.lax.broadcasted_iota(jnp.int32, (N, N), 1)
    tri = (r_ids <= q_ids).astype(jnp.float32)
    # inclusive in-block prefix: exact in f32 (counts <= N << 2^24)
    csum = _exact_dot(maskf, tri)
    pos = csum - 1.0
    mask_col = jnp.transpose(maskf)            # (N, 1)
    pos_col = jnp.transpose(pos)
    onehot = ((pos_col == q_ids.astype(jnp.float32)) & (mask_col > 0)) \
        .astype(jnp.float32)
    for p in range(comp_planes):
        x = in_refs[p][...]                    # (1, N) u32
        lo, hi = _u16_halves(x)
        # one term per one-hot column: both halves exact in f32
        clo = _exact_dot(lo, onehot)
        chi = _exact_dot(hi, onehot)
        out_refs[p][...] = (clo.astype(jnp.int32).astype(_U32)
                            | (chi.astype(jnp.int32).astype(_U32)
                               << _U32(16)))
    cnt_ref[...] = jnp.broadcast_to(
        csum[:, N - 1:N].astype(jnp.int32), cnt_ref.shape)


def fused_select_compact(table: Table, predicate, needed: Sequence[str],
                         block_rows: int = 2 * _LANES,
                         interpret: Optional[bool] = None) -> Table:
    """The compacted `needed` columns of rows passing `predicate` — drop-in
    for `apply_boolean_mask(table.select(needed), predicate.truth(table))`
    (the eager FusedSelect front half; the caller projects the result)."""
    if block_rows % _LANES:
        raise ValueError(f"block_rows must be a multiple of {_LANES}")
    N = block_rows
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = table.num_rows
    needed = list(needed)
    cols = [table[c] for c in needed]
    empty = jnp.zeros((0,), jnp.int32)
    if n == 0:
        return Table([take(c, empty, _has_negative=False) for c in cols],
                     names=needed)

    # input planes: compaction payload first (data planes + validity planes
    # of needed columns), then planes of predicate-only columns
    n_pad = ((n + N - 1) // N) * N
    B = n_pad // N

    def tile(x):
        # (B, 1, N): a leading grid axis, so each block's last two dims
        # equal the array's (Mosaic's (8, 128) block rule)
        return _to_tiles(x, n_pad, lanes=N).reshape(B, 1, N)

    planes: List[jnp.ndarray] = []
    layout: List[Tuple[str, int, Optional[bool]]] = []   # (col, nplanes, has_valid)
    plane_of: Dict[str, int] = {}
    for name, c in zip(needed, cols):
        ps = _encode_planes(c)
        plane_of[name] = len(planes)
        planes.extend(tile(p) for p in ps)
        has_valid = c.validity is not None
        if has_valid:
            planes.append(tile(c.validity.astype(_U32)))
        layout.append((name, len(ps), has_valid))
    comp_planes = len(planes)
    pred_layout = []
    for name in sorted(predicate.references()):
        c = table[name]
        if c.dtype.kind.value not in _PRED_KINDS:
            # direct callers get the same contract the registry's
            # `supports` gate enforces — a 64-bit/float predicate column
            # would otherwise evaluate on its lo word alone, silently
            raise TypeError(
                f"fused_select pallas: predicate column {name!r} has "
                f"unsupported dtype {c.dtype}")
        if name in plane_of:
            pi = plane_of[name]
        else:
            pi = len(planes)
            planes.append(tile(_encode_planes(c)[0]))
        pred_layout.append((name, c.dtype.kind, pi))

    def kernel(*refs):
        _kernel_body(predicate, pred_layout, comp_planes, n, N, refs)

    def row_spec(lanes):
        return pl.BlockSpec((None, 1, lanes), lambda i: (i, i - i, i - i),
                            memory_space=pltpu.VMEM)

    in_specs = [row_spec(N) for _ in planes]
    out_shape = [jax.ShapeDtypeStruct((B, 1, N), _U32)
                 for _ in range(comp_planes)]
    out_specs = [row_spec(N) for _ in range(comp_planes)]
    # per-block kept-row count, broadcast over one lane row
    out_shape.append(jax.ShapeDtypeStruct((B, 1, _LANES), jnp.int32))
    out_specs.append(row_spec(_LANES))
    outs = pl.pallas_call(
        kernel, out_shape=out_shape, in_specs=in_specs, out_specs=out_specs,
        grid=(B,), interpret=interpret, name="pallas_fused_select")(*planes)
    comp, counts = outs[:-1], outs[-1][:, 0, 0]

    # epilogue: squeeze block-compacted planes into one contiguous relation
    with span("ops.host_sync", site="select_pallas.compact"):
        total = int(jnp.sum(counts))           # the one host sync — the same
        #                                        sync the fallback's nonzero()
        #                                        pays for the keep vector
    if total == 0:
        return Table([take(c, empty, _has_negative=False) for c in cols],
                     names=needed)
    excl = jnp.cumsum(counts) - counts
    block_of = jnp.repeat(jnp.arange(B, dtype=jnp.int32), counts,
                          total_repeat_length=total)
    src = block_of * N + (jnp.arange(total, dtype=jnp.int32)
                          - jnp.take(excl, block_of, axis=0))
    out_cols = []
    p = 0
    for (name, nplanes, has_valid), c in zip(layout, cols):
        ps = [jnp.take(comp[p + j].reshape(-1), src, axis=0)
              for j in range(nplanes)]
        p += nplanes
        validity = None
        if has_valid:
            validity = jnp.take(comp[p].reshape(-1), src, axis=0) != 0
            p += 1
        out_cols.append(_decode_planes(c.dtype, ps, validity))
    return Table(out_cols, names=needed)


# ---- registry wiring --------------------------------------------------------

def needed_columns(table: Table, exprs) -> List[str]:
    """The columns a FusedSelect compacts: the union of projection
    references, or — for an all-literal projection — the first input
    column as the row-count carrier. ONE definition shared by the
    executor's dispatch and make_signature, so the supports() gate always
    describes exactly what the kernel will be handed."""
    needed = sorted(set().union(*(e.references() for _, e in exprs))
                    if exprs else set())
    if not needed and table.names:
        needed = [table.names[0]]
    return needed


def make_signature(table: Table, predicate, exprs, tier: str):
    """Signature for a FusedSelect dispatch: projection-referenced +
    predicate columns, with compilability folded in as extras (the
    predicate tree itself is not hashable)."""
    from .registry import Signature
    needed = needed_columns(table, exprs)
    cols = [table[c] for c in needed if c in table.names]
    data_ok = all(c.dtype.kind.value in _DATA_KINDS for c in cols)
    # a whole-literal predicate evaluates to a python scalar, not a tile
    # (and should have been folded away upstream) — decline it too
    pred_ok = _compilable(predicate, table) and not _pure_literal(predicate)
    return Signature.of(cols, tier=tier, predicate_ok=pred_ok,
                        data_ok=data_ok)


def _supports(sig) -> bool:
    return (sig.extra("tier") == "eager"
            and bool(sig.extra("predicate_ok"))
            and bool(sig.extra("data_ok")))


from .registry import REGISTRY as _REGISTRY  # noqa: E402

_REGISTRY.register("fused_select", "xla", fallback=True)
_REGISTRY.register("fused_select", "pallas", fn=fused_select_compact,
                   backends=("tpu",), supports=_supports)
