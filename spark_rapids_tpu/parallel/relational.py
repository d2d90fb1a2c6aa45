"""Distributed relational ops over the device mesh.

The reference's distributed story is Spark's: the plugin partial-aggregates
per task, shuffles by key hash (UCX), and final-aggregates (SURVEY.md §2.4).
Here the same physical plan runs as ONE jitted SPMD program per op —
`shard_map` over the mesh with the ICI all-to-all from shuffle.py in the
middle, XLA static shapes throughout:

    distributed_groupby:  local sorted partial agg (padded, key_cap groups)
        → murmur-pmod partition of the group keys → all-to-all (capacity =
        key_cap: a source sends ≤ key_cap groups total, so no bucket can
        overflow) → local final merge agg.
    distributed_inner_join: both sides hash-partitioned by key → all-to-all
        (slack-sized buckets, like shuffle.repartition_table) → shard-local
        sort-merge join into a fixed row_cap output.

Every stage reports overflow instead of corrupting: the returned flag is
the SplitAndRetry signal (retry with bigger caps / smaller batch), the same
detect-then-retry contract as the arbiter (SURVEY.md §5).

Everything is device-resident end to end; the only host interaction is the
caller-supplied static capacities, exactly like exchange()'s slack model.
"""
from __future__ import annotations

import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.join import expand_spans, join_spans
from ..ops.scans import live_positions as _live_positions
from ..ops.scans import running as _running
from .shuffle import build_partition_map, partition_ids

_AGGS = ("sum", "count", "min", "max")

# key int64.max is the dead-slot sentinel throughout (padded all-to-all
# slots); a real key with that exact value would merge with padding
_DEAD_KEY = jnp.iinfo(jnp.int64).max


def _spark_murmur_i64(keys) -> jnp.ndarray:
    """Spark murmur3_32 (seed 42, like GpuHashPartitioning) of one or more
    int64 key columns (chained per column, like Spark's hash of the key
    tuple)."""
    from ..ops.hash import murmur_hash3_32
    from ..columnar import Column, Table
    from .. import dtypes
    key_list = keys if isinstance(keys, (list, tuple)) else [keys]
    cols = [Column(dtype=dtypes.INT64, length=k.shape[0],
                   data=k.astype(jnp.int64)) for k in key_list]
    return murmur_hash3_32(Table(cols), seed=42).data


def _fit(x: jnp.ndarray, cap: int, fill) -> jnp.ndarray:
    """Slice or pad a (n,) array to exactly (cap,)."""
    n = x.shape[0]
    if n >= cap:
        return x[:cap]
    return jnp.concatenate([x, jnp.full((cap - n,), fill, x.dtype)])


def _identity(op: str) -> int:
    info = jnp.iinfo(jnp.int64)
    return {"sum": 0, "min": info.max, "max": info.min}[op]


def _bucket_exchange(axis: str, n_peers: int, cap: int, part: jnp.ndarray,
                     payloads: Sequence[Tuple[jnp.ndarray, object]],
                     how: str = "hash"):
    """Shared bucket-then-all-to-all body (the shape of shuffle.py's
    _exchange_local): bucket rows by `part` into (n_peers, cap) slots, ship
    each bucket to its peer, and — like _exchange_local — ship only the (P,)
    sent counts and rebuild the validity mask receiver-side (capacity× less
    ICI traffic than a full bool mask).

    payloads: [(array, dead-slot fill)]. Returns (received arrays (P*cap,),
    recv_valid (P*cap,), spilled scalar bool). The collectives run under
    the scope `exchange.<how>`, so a device trace can tell them from
    their neighbours by name."""
    gi, bvalid, counts = build_partition_map(part, n_peers, cap)
    spilled = jnp.any(counts > cap)
    outs = []
    sent = jnp.minimum(counts, cap)
    with jax.named_scope("exchange." + how):
        for x, fill in payloads:
            b = jnp.where(bvalid, jnp.take(x, gi, axis=0),
                          jnp.asarray(fill, x.dtype))
            outs.append(jax.lax.all_to_all(b, axis, 0, 0,
                                           tiled=True).reshape(-1))
        sent_recv = jax.lax.all_to_all(sent, axis, 0, 0, tiled=True)
    slot = jnp.arange(cap, dtype=jnp.int32)[None, :]
    recv_valid = (slot < sent_recv[:, None]).reshape(-1)
    return outs, recv_valid, spilled


def _merge_groups(keys, alive: jnp.ndarray,
                  cols: Sequence[Tuple[jnp.ndarray, str]], key_cap: int):
    """Shard-local merge of rows with equal keys (the shared kernel behind
    both the partial and final stages; same sorted-span machinery as
    ops/aggregate.py's scatter-free groupby).

    `keys` is one int64 array or a list of them (multi-key groupby: rows
    merge when ALL key columns are equal). cols: [(int64 column, merge op in
    sum|min|max)]. Dead rows (alive False) are excluded. Returns
    (keys like the input shape, outs [(key_cap,)], valid (key_cap,),
    n_real_groups) — padded/sliced to exactly key_cap."""
    multi = isinstance(keys, (list, tuple))
    key_list = list(keys) if multi else [keys]
    n = key_list[0].shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    # dead rows last; the sentinel in the key's own width (a caller may
    # pass keys narrowed to 32 bits: parallel.relational.narrow_keys)
    ks = [jnp.where(alive, k, jnp.iinfo(k.dtype).max) for k in key_list]
    sorted_all = jax.lax.sort([*ks, iota], num_keys=len(ks), is_stable=True)
    sks, order = sorted_all[:-1], sorted_all[-1]
    salive = jnp.take(alive, order, axis=0)

    neq = jnp.zeros((n,), bool)
    for o in sks:
        neq = neq | (o != jnp.roll(o, 1))
    boundary = neq.at[0].set(True) if n else neq
    gid = _running(boundary.astype(jnp.int32)) - 1
    # boundary-compaction sort for group starts (see ops/aggregate.py)
    flag = jnp.where(boundary, jnp.int32(0), jnp.int32(1))
    payload = jnp.where(boundary, iota, jnp.int32(n))
    starts = jax.lax.sort([flag, payload], num_keys=1, is_stable=True)[1]
    if n:
        ends = jnp.concatenate([starts[1:], jnp.full((1,), n, jnp.int32)])
    else:
        ends = starts
    last = jnp.clip(ends - 1, 0, max(n - 1, 0))
    prev = starts - 1

    def span_sum(x):
        c = _running(x)
        hi = jnp.take(c, last, axis=0)
        lo = jnp.where(prev >= 0, jnp.take(c, jnp.maximum(prev, 0), axis=0), 0)
        return hi - lo

    alive_cnt = span_sum(salive.astype(jnp.int32))
    outs: List[jnp.ndarray] = []
    for col, op in cols:
        sc = jnp.take(col, order, axis=0)
        if op == "sum":
            outs.append(span_sum(jnp.where(salive, sc.astype(jnp.int64), 0)))
        else:
            ident = jnp.int64(_identity(op))
            masked = jnp.where(salive, sc.astype(jnp.int64), ident)
            res = _segmented_cummax(gid, boundary,
                                    ~masked if op == "min" else masked)
            res = ~res if op == "min" else res
            outs.append(jnp.take(res, last, axis=0))

    n_groups = (gid[-1] + 1) if n else jnp.int32(0)
    # real groups only: the dead-key sentinel group (if any padding existed)
    # sorts last and has alive_cnt == 0 — it must not trip overflow
    in_range = iota < n_groups
    n_real = jnp.sum((alive_cnt > 0) & in_range).astype(jnp.int32)

    valid = (_fit(alive_cnt, key_cap, 0) > 0) & \
        (jnp.arange(key_cap, dtype=jnp.int32) < n_groups)
    gkeys = [_fit(jnp.take(k, starts, axis=0, mode="clip"), key_cap,
                  jnp.iinfo(k.dtype).max) for k in sks]
    out_keys = gkeys if multi else gkeys[0]
    return (out_keys, [_fit(o, key_cap, 0) for o in outs], valid, n_real)


def _segmented_cummax(gid, boundary, x):
    """Running maximum of int64 `x` within runs (`gid`: the run's number,
    never falling; `boundary`: where a run starts), as three prefix scans
    and no gather: an unrolled `associative_scan` of the same merge was
    most of a program's code at fact scale (PERF.md, PR 29). A run's
    number packed above a 32-bit half makes one `cummax` restart at every
    run; the high halves go first, then the low halves of the rows that
    hold their run's high half so far (whenever that changes, a new
    sub-run starts: earlier low halves no longer count)."""
    hi = (x >> 32) + (1 << 31)                       # 0 .. 2**32 - 1
    lo = x & 0xFFFFFFFF
    run_hi = _running((gid.astype(jnp.int64) << 32) | hi, "max")
    rises = boundary | (run_hi != jnp.roll(run_hi, 1))
    sub = _running(rises.astype(jnp.int32)) - 1
    holds = (run_hi & 0xFFFFFFFF) == hi
    run_lo = _running((sub.astype(jnp.int64) << 32)
                      | jnp.where(holds, lo, 0), "max")
    return (((run_hi & 0xFFFFFFFF) - (1 << 31)) << 32) | (run_lo & 0xFFFFFFFF)


def distributed_groupby(mesh: Mesh, keys: jnp.ndarray, vals: jnp.ndarray,
                        aggs: Sequence[str], key_cap: int,
                        axis: str = "data"):
    """Groupby over mesh-sharded int64 key/value columns — ONE jitted SPMD
    program (partial agg → ICI all-to-all by key hash → final agg).

    `key_cap` bounds the distinct keys per shard at both stages (static
    shapes); the returned per-shard `overflow` flag means results are
    incomplete — retry with a bigger key_cap (SplitAndRetry contract).
    Returns per-shard padded (keys, [agg arrays], valid, overflow).

    Thin wrapper over distributed_groupby_multi (single key, single value
    column)."""
    (gk,), outs, valid, overflow = distributed_groupby_multi(
        mesh, [keys], [vals], [(0, a) for a in aggs], key_cap, axis)
    return gk, outs, valid, overflow


def distributed_groupby_multi(mesh: Mesh, keys: Sequence[jnp.ndarray],
                              vals: Sequence[jnp.ndarray],
                              aggs: Sequence[Tuple[int, str]], key_cap: int,
                              axis: str = "data", hash_fn=None, alive=None):
    """Multi-key, multi-value groupby over the mesh — same two-stage shape
    as distributed_groupby but grouping on a tuple of int64 key columns and
    aggregating [(value index, op)] pairs.

    `hash_fn(key_arrays) -> (n,) hash` overrides the partition hash (the
    typed-key path passes keys.spark_partition_hash so string/decimal keys
    place exactly like GpuHashPartitioning); default is the chained murmur
    over raw int64 words.

    `alive` (optional sharded (n,) bool) excludes dead rows — the plan
    tier's padded sharded relations aggregate live rows only.

    Returns per-shard padded ([key arrays], [agg arrays], valid, overflow).
    """
    for _, a in aggs:
        if a not in _AGGS:
            raise ValueError(f"unsupported distributed agg {a!r}")
    keys = list(keys)
    vals = list(vals)
    if not keys:
        raise ValueError("at least one key column is required")
    n_peers = mesh.shape[axis]
    aggs = tuple((int(i), a) for i, a in aggs)
    for i, a in aggs:
        if a != "count" and not (0 <= i < len(vals)):
            raise ValueError(f"agg value index {i} out of range "
                             f"({len(vals)} value columns)")

    def partial_cols(key0, val_arrays):
        ones = jnp.ones(key0.shape, jnp.int64)   # count needs no value column
        return [(ones if a == "count" else val_arrays[i],
                 "sum" if a in ("sum", "count") else a) for i, a in aggs]

    def merge_cols(partials):
        return [(p, "sum" if a in ("sum", "count") else a)
                for p, (_, a) in zip(partials, aggs)]

    nk = len(keys)
    nv = len(vals)
    has_alive = alive is not None

    def local(*arrs):
        ks, vs = list(arrs[:nk]), list(arrs[nk:nk + nv])
        live = arrs[-1] if has_alive else jnp.ones(ks[0].shape, bool)
        gks, partials, gvalid, n_real = _merge_groups(
            ks, live, partial_cols(ks[0], vs), key_cap)
        overflow = n_real > key_cap

        part = partition_ids((hash_fn or _spark_murmur_i64)(gks), n_peers)
        part = jnp.where(gvalid, part, jnp.int32(n_peers))
        recv, recv_alive, _ = _bucket_exchange(
            axis, n_peers, key_cap, part,
            [(g, _DEAD_KEY) for g in gks] +
            [(p, _identity(op)) for p, op in merge_cols(partials)])
        recv_ks, recv_ps = recv[:nk], recv[nk:]

        fks, fouts, fvalid, fn_real = _merge_groups(
            list(recv_ks), recv_alive, merge_cols(list(recv_ps)), key_cap)
        overflow = overflow | (fn_real > key_cap)
        return (tuple(fks), tuple(fouts), fvalid, overflow.reshape(1))

    spec = P(axis)
    fn = shard_map(local, mesh=mesh,
                   in_specs=(spec,) * (nk + nv + int(has_alive)),
                   out_specs=(tuple(spec for _ in keys),
                              tuple(spec for _ in aggs), spec, spec))
    args = list(keys) + list(vals) + ([alive] if has_alive else [])
    return fn(*args)


def distributed_groupby_keyed(mesh: Mesh, key_words: Sequence[jnp.ndarray],
                              key_specs, vals: Sequence[jnp.ndarray],
                              aggs: Sequence[Tuple[int, str]], key_cap: int,
                              axis: str = "data", alive=None):
    """Typed-key groupby: key columns of ANY supported dtype (string,
    decimal128, float, nullable int — see parallel/keys.py) encoded as word
    lists ride the same SPMD program as the int64 path; partition placement
    is Spark-exact (keys.spark_partition_hash). Returns per-shard padded
    ([key word arrays], [agg arrays], valid, overflow); decode the words
    with keys.decode_key_columns(words, specs, alive=valid)."""
    from .keys import spark_partition_hash
    return distributed_groupby_multi(
        mesh, key_words, vals, aggs, key_cap, axis,
        hash_fn=lambda ws: spark_partition_hash(ws, key_specs), alive=alive)


def distributed_local_groupby(mesh: Mesh, key_words: Sequence[jnp.ndarray],
                              vals: Sequence[jnp.ndarray],
                              aggs: Sequence[Tuple[int, str]], key_cap: int,
                              axis: str = "data", alive=None):
    """Shard-local groupby merge for PRE-PARTITIONED inputs: every row of a
    group is already co-located (the input sits below an ELIDED exchange —
    e.g. a shuffle join on a subset of the group keys already placed equal
    keys on one shard), so the two-stage shape collapses to ONE
    `_merge_groups` per shard with no collective at all. Same return
    contract as distributed_groupby_multi; `overflow` means a shard held
    more than key_cap distinct live groups."""
    for _, a in aggs:
        if a not in _AGGS:
            raise ValueError(f"unsupported distributed agg {a!r}")
    key_words = list(key_words)
    vals = list(vals)
    nk, nv = len(key_words), len(vals)
    aggs = tuple((int(i), a) for i, a in aggs)
    has_alive = alive is not None

    def local(*arrs):
        ks, vs = list(arrs[:nk]), list(arrs[nk:nk + nv])
        live = arrs[-1] if has_alive else jnp.ones(ks[0].shape, bool)
        ones = jnp.ones(ks[0].shape, jnp.int64)
        cols = [(ones if a == "count" else vs[i],
                 "sum" if a in ("sum", "count") else a) for i, a in aggs]
        gks, outs, gvalid, n_real = _merge_groups(ks, live, cols, key_cap)
        overflow = n_real > key_cap
        return (tuple(gks), tuple(outs), gvalid, overflow.reshape(1))

    spec = P(axis)
    fn = shard_map(local, mesh=mesh,
                   in_specs=(spec,) * (nk + nv + int(has_alive)),
                   out_specs=(tuple(spec for _ in key_words),
                              tuple(spec for _ in aggs), spec, spec))
    args = key_words + vals + ([alive] if has_alive else [])
    return fn(*args)


def distributed_repartition_keyed(mesh: Mesh,
                                  key_words: Sequence[jnp.ndarray],
                                  key_specs, vals: Sequence[jnp.ndarray],
                                  cap: int, axis: str = "data",
                                  alive=None, word_codecs=None,
                                  word_refs=None):
    """Standalone hash-partition exchange of one relation — the physical
    form of an `Exchange(hash)` plan node: every row moves to the shard
    given by the Spark-exact hash of its key words (pmod n_peers), so a
    downstream co-located operator (colocated join, elided-exchange
    groupby) can run with no further collective. `alive` marks live rows
    of a padded sharded relation; dead rows are dropped by the bucketing.

    `word_codecs`/`word_refs` carry the narrowed-key wire form
    (plan/transport.narrow_words): `word_codecs` is a static per-word
    codec tuple ("raw" | "forN") and `word_refs` the traced (1,) int64
    reference arrays, one per non-raw word in order. Narrowed planes are
    widened back to their exact 64-bit words INSIDE the collective body
    for the Spark-exact hash — placement is bit-identical to the raw
    path — while the all-to-all ships the narrow planes. References ride
    as traced arrays (replicated specs), not baked constants, so one
    compiled program serves every execution of the same layout.

    `cap` is the bucket capacity (the caller counts the buckets with
    `distributed_partition_counts`, so no slot beyond the fullest bucket
    is shipped and no slack is guessed).

    Returns ([key words], [vals], valid, overflow); the key words come
    back in the wire form they were passed (the caller widens). overflow
    (one bool a shard) means a bucket held more than `cap` rows and lost
    the rest: the count and the exchange disagreed, and the caller
    raises."""
    from .keys import spark_partition_hash
    n_peers = mesh.shape[axis]
    hash_fn = lambda ws: spark_partition_hash(ws, key_specs)  # noqa: E731
    key_words = list(key_words)
    vals = list(vals)
    nk, nv = len(key_words), len(vals)
    has_alive = alive is not None
    codecs_t = tuple(word_codecs) if word_codecs else ("raw",) * nk
    refs = list(word_refs or [])
    narrowed = any(c != "raw" for c in codecs_t)

    def local(*arrs):
        ws, vs = list(arrs[:nk]), list(arrs[nk:nk + nv])
        live = arrs[nk + nv] if has_alive else None
        if narrowed:
            rs = iter(arrs[nk + nv + int(has_alive):])
            ws64 = [w if c == "raw" else next(rs)[0] + w.astype(jnp.int64)
                    for w, c in zip(ws, codecs_t)]
            fills = [_DEAD_KEY if c == "raw" else 0 for c in codecs_t]
            Ws, Vs, recv_alive, spilled = _hash_exchange(
                axis, n_peers, 0.0, ws, vs, hash_fn, alive=live,
                hash_keys=ws64, key_fills=fills, cap=cap)
        else:
            Ws, Vs, recv_alive, spilled = _hash_exchange(
                axis, n_peers, 0.0, ws, vs, hash_fn, alive=live, cap=cap)
        return (tuple(Ws), tuple(Vs), recv_alive, spilled.reshape(1))

    spec = P(axis)
    fn = shard_map(local, mesh=mesh,
                   in_specs=(spec,) * (nk + nv + int(has_alive))
                   + (P(),) * len(refs),
                   out_specs=(tuple(spec for _ in key_words),
                              tuple(spec for _ in vals), spec, spec))
    args = key_words + vals + ([alive] if has_alive else []) + refs
    return fn(*args)


def distributed_colocated_join_keyed(mesh: Mesh,
                                     l_words: Sequence[jnp.ndarray],
                                     lvals: Sequence[jnp.ndarray],
                                     r_words: Sequence[jnp.ndarray],
                                     key_specs, axis: str = "data",
                                     how: str = "left_semi",
                                     lalive=None, ralive=None,
                                     r_replicated: bool = False):
    """Semi- or anti-join of two ALREADY-ALIGNED sides with no exchange:
    both sides are either hash-partitioned by the positionally-matching
    key tuples (the explicit `Exchange(hash)` ran upstream, so matching
    rows are co-located), or the right side is REPLICATED
    (`r_replicated=True`: the `Exchange(broadcast)` replicated the small
    build side onto every shard, the probe side never moves). Each shard
    then joins locally — the plan tier's counterpart of Spark executing a
    join above its exchanges. The output stays left-shaped; an inner join
    of aligned sides is `distributed_colocated_join_spans` / `_emit`.

    `lalive`/`ralive` mark live rows of padded sharded relations; NULL
    keys never match (Spark equi-join semantics).

    Returns ([l key words], [lvals], keep)."""
    from .keys import keys_null_mask
    l_words, lvals, r_words = list(l_words), list(lvals), list(r_words)
    _check_word_counts(l_words, r_words)
    nw, nlv = len(l_words), len(lvals)
    has_lal, has_ral = lalive is not None, ralive is not None
    if how not in ("left_semi", "left_anti"):
        raise ValueError(f"unsupported colocated join type {how!r}")

    def local(*arrs):
        lw, lv = list(arrs[:nw]), list(arrs[nw:nw + nlv])
        rw = list(arrs[nw + nlv:2 * nw + nlv])
        i = 2 * nw + nlv
        Lal = arrs[i] if has_lal else jnp.ones(lw[0].shape, bool)
        i += int(has_lal)
        Ral = arrs[i] if has_ral else jnp.ones(rw[0].shape, bool)
        lmatch = Lal & ~keys_null_mask(lw, key_specs)
        rmatch = Ral & ~keys_null_mask(rw, key_specs)
        operands = tuple(jnp.concatenate([a, b]) for a, b in zip(lw, rw))
        counts, _, _ = join_spans(operands, lmatch, rmatch,
                                  nl=lw[0].shape[0], need_rorder=False)
        hit = counts > 0
        keep = Lal & (hit if how == "left_semi" else ~hit)
        return (tuple(jnp.where(keep, w, jnp.asarray(0, w.dtype))
                      for w in lw),
                tuple(jnp.where(keep, v, jnp.asarray(0, v.dtype))
                      for v in lv), keep)

    spec = P(axis)
    rspec = P() if r_replicated else spec
    in_specs = ((spec,) * (nw + nlv) + (rspec,) * nw
                + (spec,) * int(has_lal) + (rspec,) * int(has_ral))
    fn = shard_map(local, mesh=mesh, in_specs=in_specs,
                   out_specs=(tuple(spec for _ in l_words),
                              tuple(spec for _ in lvals), spec))
    args = (l_words + lvals + r_words
            + ([lalive] if has_lal else [])
            + ([ralive] if has_ral else []))
    return fn(*args)


def distributed_colocated_join_spans(mesh: Mesh, l_words, r_words, key_specs,
                                     axis: str = "data", lalive=None,
                                     ralive=None, r_replicated: bool = False):
    """The first half of an inner join of two aligned sides (see
    `distributed_colocated_join_keyed`): each shard's match spans over its
    union sort, and HOW MANY rows it will emit. The eager walk reads that
    count and sizes the second half (`distributed_colocated_join_emit`) by
    it: an output frame is then never a guess that a fan-out overflows
    (each escalation is another compile of a three-sort program), nor a
    probe side's worth of slots for a join that keeps a tenth of it.
    Returns (counts, lo, rorder, total): the spans in left-row order,
    `total` one int32 a shard."""
    from .keys import keys_null_mask
    l_words, r_words = list(l_words), list(r_words)
    _check_word_counts(l_words, r_words)
    nw = len(l_words)

    def local(*arrs):
        lw, rw = list(arrs[:nw]), list(arrs[nw:2 * nw])
        lmatch = arrs[-2] & ~keys_null_mask(lw, key_specs)
        rmatch = arrs[-1] & ~keys_null_mask(rw, key_specs)
        operands = tuple(jnp.concatenate([a, b]) for a, b in zip(lw, rw))
        counts, lo, rorder = join_spans(operands, lmatch, rmatch,
                                        nl=lw[0].shape[0])
        return counts, lo, rorder, jnp.sum(counts).astype(
            jnp.int32).reshape(1)

    spec = P(axis)
    rspec = P() if r_replicated else spec
    return shard_map(local, mesh=mesh,
                     in_specs=(spec,) * nw + (rspec,) * nw + (spec, rspec),
                     out_specs=(spec,) * 4)(*l_words, *r_words, lalive,
                                            ralive)


def distributed_colocated_join_emit(mesh: Mesh, l_words, lvals, rvals,
                                    counts, lo, rorder, row_cap: int,
                                    axis: str = "data",
                                    r_replicated: bool = False):
    """The second half: expand the spans into `row_cap` slots a shard (at
    least the fullest shard's `total`) and gather both sides' columns.
    Returns ([l key words], [lvals], [rvals], valid)."""
    l_words, lvals, rvals = list(l_words), list(lvals), list(rvals)
    nw, nlv, nrv = len(l_words), len(lvals), len(rvals)

    def local(*arrs):
        lw, lv = list(arrs[:nw]), list(arrs[nw:nw + nlv])
        rv = list(arrs[nw + nlv:nw + nlv + nrv])
        counts, lo, rorder = arrs[-3:]
        lsel, rsel = expand_spans(counts, lo, rorder, total=row_cap)
        live = jnp.arange(row_cap, dtype=jnp.int32) < jnp.sum(counts)

        def take(x, sel):
            return jnp.where(live, jnp.take(x, sel, axis=0),
                             jnp.asarray(0, x.dtype))
        rsel = jnp.maximum(rsel, 0)
        return (tuple(take(w, lsel) for w in lw),
                tuple(take(v, lsel) for v in lv),
                tuple(take(v, rsel) for v in rv), live)

    spec = P(axis)
    rspec = P() if r_replicated else spec
    return shard_map(
        local, mesh=mesh,
        in_specs=(spec,) * (nw + nlv) + (rspec,) * nrv + (spec,) * 3,
        out_specs=(tuple(spec for _ in l_words), tuple(spec for _ in lvals),
                   tuple(spec for _ in rvals), spec))(
        *l_words, *lvals, *rvals, counts, lo, rorder)


def distributed_sort(mesh: Mesh, keys: jnp.ndarray, vals: jnp.ndarray,
                     slack: float = 2.0, axis: str = "data"):
    """Global sort of mesh-sharded (key, value) columns — sample-sort as one
    jitted SPMD program. This is the scale-past-one-device primitive (a
    "sequence" longer than any single chip's memory): shard 0 ends with the
    smallest keys, shard P-1 the largest, each locally sorted.

    1. each shard samples P-1 local quantile keys from its sorted run
    2. all_gather the samples; global splitters = quantiles of the pool
    3. bucket rows by splitter interval; ICI all-to-all (slack-sized)
    4. local sort of the received rows

    Returns per-shard (keys, vals, valid, overflow); overflow means a shard
    received more than cap rows (skewed keys) — retry with bigger slack.

    The single-int64-key case of distributed_sort_keyed (one word, no
    specs), kept as the plain-array front door."""
    (w,), ov, valid, overflow = distributed_sort_keyed(
        mesh, [keys], None, vals, slack=slack, axis=axis)
    return w, ov, valid, overflow


def distributed_sort_keyed(mesh: Mesh, key_words: Sequence[jnp.ndarray],
                           key_specs, vals, slack: float = 2.0,
                           axis: str = "data", alive=None):
    """Global sort over typed keys (word lists from keys.encode_key_columns,
    so string/decimal128/float/nullable keys all sort) — sample-sort as one
    jitted SPMD program, the multi-word generalization of distributed_sort.
    The word encoding is order-preserving (tuple lexicographic order == the
    column's sort order, nulls first), so splitters are word TUPLES and the
    partition id is a vectorized lexicographic rank against them.

    `key_specs` is accepted for API symmetry with the other keyed ops and
    for the caller's later decode; the sort itself needs only the
    order-preserving words (pass None when sorting raw arrays).

    `vals` may be one payload array or a list (a whole table side rides the
    sort); `alive` (optional sharded (n,) bool) marks live rows of a padded
    sharded relation — dead rows sink out of the sampled runs, route to the
    out-of-range partition, and never reach any shard's output.

    Returns per-shard ([key words], vals (matching the input shape), valid,
    overflow); shard 0 ends with the smallest keys. overflow means a shard
    received more than its slack-sized capacity (skewed keys) — retry with
    bigger slack."""
    del key_specs  # symmetry/decode-side only
    n_peers = mesh.shape[axis]
    key_words = list(key_words)
    nw = len(key_words)
    multi_vals = isinstance(vals, (list, tuple))
    val_list = list(vals) if multi_vals else [vals]
    nv = len(val_list)
    has_alive = alive is not None

    def local(*arrs):
        ws, vs = list(arrs[:nw]), list(arrs[nw:nw + nv])
        live = arrs[-1] if has_alive else jnp.ones(ws[0].shape, bool)
        nloc = ws[0].shape[0]
        cap = max(1, math.ceil(nloc / n_peers * slack))
        iota = jnp.arange(nloc, dtype=jnp.int32)
        # dead rows take the sentinel and sink to the end of the local run,
        # so the live prefix is exactly the shard's real rows
        ks = [jnp.where(live, w, _DEAD_KEY) for w in ws]
        out = jax.lax.sort([*ks, iota], num_keys=nw, is_stable=True)
        sws, order = list(out[:-1]), out[-1]
        svs = [jnp.take(v, order, axis=0) for v in vs]
        salive = jnp.take(live, order, axis=0)
        nlive = jnp.sum(salive.astype(jnp.int32))
        # P-1 evenly spaced local sample TUPLES from the LIVE prefix of the
        # sorted run (sampling over nloc would pull dead-sentinel tuples
        # into the splitter pool and skew every splitter high)
        pos = (jnp.arange(1, n_peers, dtype=jnp.int32) * nlive) // n_peers
        pools = []
        for w in sws:
            samples = jnp.take(w, pos, axis=0, mode="clip")
            with jax.named_scope("exchange.range"):
                pools.append(jax.lax.all_gather(samples, axis).reshape(-1))
        pool_sorted = jax.lax.sort(pools, num_keys=nw, is_stable=True)
        m = pool_sorted[0].shape[0]
        spl_pos = (jnp.arange(1, n_peers, dtype=jnp.int32) * m) // n_peers
        spl = [jnp.take(p, spl_pos, axis=0, mode="clip")
               for p in pool_sorted]                       # W x (P-1,)

        # partition id = #splitters strictly below the row tuple:
        # lexicographic splitter<row over words, vectorized (n, P-1)
        lt = jnp.zeros((nloc, n_peers - 1), bool)
        eq = jnp.ones((nloc, n_peers - 1), bool)
        for w, s in zip(sws, spl):
            lt = lt | (eq & (s[None, :] < w[:, None]))
            eq = eq & (s[None, :] == w[:, None])
        # strict splitter<row mirrors distributed_sort's `row > splitter`:
        # rows equal to a splitter stay in the lower bucket
        part = jnp.sum(lt, axis=1).astype(jnp.int32)
        part = jnp.where(salive, part, jnp.int32(n_peers))  # drop dead rows
        recv, ralive_, spilled = _bucket_exchange(
            axis, n_peers, cap, part,
            [(w, _DEAD_KEY) for w in sws] + [(sv, 0) for sv in svs],
            how="range")
        spilled = jax.lax.all_gather(spilled.reshape(1), axis).any()
        rws, rvs = recv[:nw], recv[nw:]
        # final local sort; dead slots carry the sentinel and sink last
        dead_flag = jnp.where(ralive_, jnp.int32(0), jnp.int32(1))
        keyed = [jnp.where(ralive_, w, _DEAD_KEY) for w in rws]
        out2 = jax.lax.sort([*keyed, dead_flag, *rvs], num_keys=nw + 1,
                            is_stable=True)
        out_vs = tuple(out2[nw + 1:])
        return (tuple(out2[:nw]), out_vs if multi_vals else out_vs[0],
                out2[nw] == 0, spilled.reshape(1))

    spec = P(axis)
    val_out_spec = tuple(spec for _ in val_list) if multi_vals else spec
    fn = shard_map(local, mesh=mesh,
                   in_specs=(spec,) * (nw + nv + int(has_alive)),
                   out_specs=(tuple(spec for _ in key_words), val_out_spec,
                              spec, spec))
    args = key_words + val_list + ([alive] if has_alive else [])
    return fn(*args)


def _as_list(x):
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _local_join_tail(lk, lv, lalive, rk, rv, ralive, row_cap: int,
                     outer: bool = False, lmatch=None, rmatch=None):
    """Shard-local (inner or left-outer) join into a fixed row_cap: union
    rank + sort-merge spans + padded expansion (ops/join.py machinery on
    shard-local shapes). Key sides may be single arrays or word lists
    (typed keys encoded by parallel/keys.py): rows match when ALL words are
    equal. `lmatch`/`rmatch` (default: the alive masks) restrict MATCHING
    without affecting emission — a null-keyed left row under `outer` is
    still emitted, just never matched (Spark equi-join NULL semantics).
    Returns (lkeys list, lvals list, rvals list, rmatched, live,
    overflow-scalar); rmatched is False on left-outer rows with no match
    (their rval slots are 0 and must be read as null)."""
    lks, rks = _as_list(lk), _as_list(rk)
    lvs, rvs = _as_list(lv), _as_list(rv)
    lmatch = lalive if lmatch is None else lmatch
    rmatch = ralive if rmatch is None else rmatch
    nl = lks[0].shape[0]
    operands = tuple(jnp.concatenate([a, b]) for a, b in zip(lks, rks))
    counts, lo, rorder = join_spans(operands, lmatch, rmatch, nl=nl)
    if outer:
        # dead (padded) rows emit NOTHING: a zero emit count keeps live
        # output slots a prefix with no dead-rows-last permute
        eff = jnp.where(lalive, jnp.maximum(counts, 1), 0)
        total = jnp.sum(eff)
    else:
        eff = None
        total = jnp.sum(counts)
    lsel, rsel = expand_spans(counts, lo, rorder, total=row_cap, outer=outer,
                              eff=eff)
    live = jnp.arange(row_cap, dtype=jnp.int32) < total
    rmatched = rsel >= 0 if outer else jnp.ones((row_cap,), bool)
    # dead-slot zeros keep each payload's dtype (a weak-typed python 0
    # would promote bool validity payloads to int)
    out_lks = [jnp.where(live, jnp.take(k, lsel, axis=0),
                         jnp.asarray(0, k.dtype)) for k in lks]
    out_lvs = [jnp.where(live, jnp.take(v, lsel, axis=0),
                         jnp.asarray(0, v.dtype)) for v in lvs]
    safe_rsel = jnp.maximum(rsel, 0)
    out_rvs = [jnp.where(live & rmatched, jnp.take(v, safe_rsel, axis=0),
                         jnp.asarray(0, v.dtype))
               for v in rvs]
    return out_lks, out_lvs, out_rvs, rmatched & live, live, total > row_cap


def _hash_exchange(axis: str, n_peers: int, slack: float,
                   keys, vals, hash_fn=None, alive=None,
                   hash_keys=None, key_fills=None, cap: int = 0):
    """Hash-partition by Spark murmur pmod and all-to-all one table side
    (the shared shuffle wiring of every distributed join). `keys` may be a
    single int64 array or a word list (typed keys); `vals` may be None
    (key-only sides, e.g. semi/anti build side), one array, or a list.
    `alive` (optional (n,) bool) marks live rows: dead rows route to the
    out-of-range partition id `n_peers` and are silently dropped by the
    bucketing — the padded-relation contract of the plan tier's sharded
    relations. `hash_keys` (default: `keys`) is the array list the hash
    runs over — the narrowed-key exchange ships narrow planes but hashes
    their widened 64-bit word form (plan/transport.narrow_words), so the
    wire and the hash input may legitimately differ. `key_fills` gives
    each key plane's dead-slot fill (default `_DEAD_KEY`; narrowed
    planes fill 0 — int64.max would wrap in a narrow dtype, and dead
    slots are never read anyway). Returns (key outs, val outs, alive,
    spilled)."""
    key_list = _as_list(keys)
    val_list = [] if vals is None else _as_list(vals)
    nloc = key_list[0].shape[0]
    cap = cap or max(1, math.ceil(nloc / n_peers * slack))
    hash_list = key_list if hash_keys is None else _as_list(hash_keys)
    part = partition_ids((hash_fn or _spark_murmur_i64)(hash_list), n_peers)
    if alive is not None:
        part = jnp.where(alive, part, jnp.int32(n_peers))
    fills = ([_DEAD_KEY] * len(key_list) if key_fills is None
             else list(key_fills))
    payloads = [(k, f) for k, f in zip(key_list, fills)] \
        + [(v, 0) for v in val_list]
    outs, alive, spilled = _bucket_exchange(axis, n_peers, cap, part, payloads)
    # a spill anywhere means some shard RECEIVED an incomplete side: agree on
    # the flag across the mesh (same contract as distributed_sort) so the
    # shard whose output is wrong also reports overflow
    spilled = jax.lax.all_gather(spilled.reshape(1), axis).any()
    nk = len(key_list)
    return outs[:nk], outs[nk:], alive, spilled


def distributed_inner_join(mesh: Mesh, lkeys: jnp.ndarray, lvals: jnp.ndarray,
                           rkeys: jnp.ndarray, rvals: jnp.ndarray,
                           row_cap: int, slack: float = 2.0,
                           axis: str = "data"):
    """Inner equi-join of two mesh-sharded int64-keyed tables — one jitted
    SPMD program: hash-partition both sides (slack-sized buckets, NOT the
    whole table per shard), all-to-all, shard-local sort-merge join into a
    fixed row_cap output.

    Returns per-shard padded (lkey, lval, rval, valid, overflow); overflow
    covers both bucket spill during the shuffle and join-output spill past
    row_cap — retry with bigger slack/row_cap (SplitAndRetry contract)."""
    n_peers = mesh.shape[axis]

    def local(lk, lv, rk, rv):
        (Lk,), (Lv,), Lalive, lspill = _hash_exchange(
            axis, n_peers, slack, lk, lv)
        (Rk,), (Rv,), Ralive, rspill = _hash_exchange(
            axis, n_peers, slack, rk, rv)
        out_lk, out_lv, out_rv, _, live, joverflow = _local_join_tail(
            Lk, Lv, Lalive, Rk, Rv, Ralive, row_cap)
        overflow = joverflow | lspill | rspill
        return out_lk[0], out_lv[0], out_rv[0], live, overflow.reshape(1)

    spec = P(axis)
    fn = shard_map(local, mesh=mesh, in_specs=(spec,) * 4,
                   out_specs=(spec,) * 5)
    return fn(lkeys, lvals, rkeys, rvals)


def _check_word_counts(l_words, r_words):
    if len(r_words) != len(l_words):
        # encode both sides with the SAME static max_bytes — auto-derived
        # widths differ per side and would silently mis-slice the arg tuple
        raise ValueError(
            f"join key word counts differ: left {len(l_words)} vs right "
            f"{len(r_words)}; encode both sides with identical KeySpecs")


def _distributed_join_keyed(mesh, l_words, lvals, r_words, rvals, key_specs,
                            row_cap, slack, axis, outer, broadcast=False):
    """Shared typed-key equi-join body (inner / left-outer / broadcast):
    move the build side — hash-exchange BOTH sides by the Spark-exact hash
    of the words, or (`broadcast`) all_gather the small right side onto
    every shard while the left never moves — then join shard-locally. NULL
    keys never match (keys.keys_null_mask feeds the match masks), matching
    Spark's `l.k = r.k` semantics — under `outer` a null-keyed left row is
    emitted null-extended."""
    from .keys import keys_null_mask, spark_partition_hash
    n_peers = mesh.shape[axis]
    hash_fn = lambda ws: spark_partition_hash(ws, key_specs)  # noqa: E731
    l_words, lvals = list(l_words), list(lvals)
    r_words, rvals = list(r_words), list(rvals)
    _check_word_counts(l_words, r_words)
    nw, nlv = len(l_words), len(lvals)

    def local(*arrs):
        lw = list(arrs[:nw])
        lv = list(arrs[nw:nw + nlv])
        rw = list(arrs[nw + nlv:nw + nlv + nw])
        rv = list(arrs[nw + nlv + nw:])
        if broadcast:
            # build side replicated over ICI; probe side stays in place
            Lw, Lv = lw, lv
            with jax.named_scope("exchange.broadcast"):
                Rw = [jax.lax.all_gather(w, axis, tiled=True) for w in rw]
                Rv = [jax.lax.all_gather(v, axis, tiled=True) for v in rv]
            Lalive = jnp.ones((Lw[0].shape[0],), jnp.bool_)
            Ralive = jnp.ones((Rw[0].shape[0],), jnp.bool_)
            lspill = rspill = jnp.zeros((), jnp.bool_)
        else:
            Lw, Lv, Lalive, lspill = _hash_exchange(
                axis, n_peers, slack, lw, lv, hash_fn)
            Rw, Rv, Ralive, rspill = _hash_exchange(
                axis, n_peers, slack, rw, rv, hash_fn)
        lmatch = Lalive & ~keys_null_mask(Lw, key_specs)
        rmatch = Ralive & ~keys_null_mask(Rw, key_specs)
        out_lw, out_lv, out_rv, rvalid, live, joverflow = _local_join_tail(
            Lw, Lv, Lalive, Rw, Rv, Ralive, row_cap, outer=outer,
            lmatch=lmatch, rmatch=rmatch)
        overflow = joverflow | lspill | rspill
        outs = (tuple(out_lw), tuple(out_lv), tuple(out_rv))
        if outer:
            return outs + (rvalid, live, overflow.reshape(1))
        return outs + (live, overflow.reshape(1))

    spec = P(axis)
    n_flags = 3 if outer else 2
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(spec,) * (2 * nw + nlv + len(rvals)),
        out_specs=(tuple(spec for _ in l_words), tuple(spec for _ in lvals),
                   tuple(spec for _ in rvals)) + (spec,) * n_flags)
    return fn(*l_words, *lvals, *r_words, *rvals)


def distributed_inner_join_keyed(mesh: Mesh, l_words: Sequence[jnp.ndarray],
                                 lvals: Sequence[jnp.ndarray],
                                 r_words: Sequence[jnp.ndarray],
                                 rvals: Sequence[jnp.ndarray],
                                 key_specs, row_cap: int, slack: float = 2.0,
                                 axis: str = "data"):
    """Typed-key inner join: key sides are word lists from
    keys.encode_key_columns (string/decimal128/float/nullable keys all ride
    the same machinery); placement is Spark-exact via
    keys.spark_partition_hash; NULL keys never match. Returns per-shard
    padded ([l key words], [lvals], [rvals], valid, overflow) — decode the
    key words back to typed columns with keys.decode_key_columns."""
    return _distributed_join_keyed(mesh, l_words, lvals, r_words, rvals,
                                   key_specs, row_cap, slack, axis,
                                   outer=False)


def distributed_broadcast_join(mesh: Mesh, lkeys: jnp.ndarray,
                               lvals: jnp.ndarray, rkeys: jnp.ndarray,
                               rvals: jnp.ndarray, row_cap: int,
                               axis: str = "data"):
    """Broadcast inner equi-join: `jax.lax.all_gather` replicates the (small)
    right side onto every shard over ICI — XLA lowers the gather to a ring of
    ICI hops — and each left shard joins locally. The probe side never moves,
    so collective traffic is O(|right| x peers) instead of reshuffling both
    sides: the TPU analogue of the BroadcastHashJoin the reference's plugin
    accelerates one level up (SURVEY.md §2.4's UCX-shuffle slot; here the
    broadcast IS the collective).

    `row_cap` bounds the per-shard join output (static shapes); returns
    per-shard padded (lkey, lval, rval, valid, overflow) exactly like
    distributed_inner_join, so callers reuse the same SplitAndRetry contract.
    """
    def local(lk, lv, rk, rv):
        Rk = jax.lax.all_gather(rk, axis, tiled=True)
        Rv = jax.lax.all_gather(rv, axis, tiled=True)
        all_l = jnp.ones((lk.shape[0],), jnp.bool_)
        all_r = jnp.ones((Rk.shape[0],), jnp.bool_)
        out_lk, out_lv, out_rv, _, live, overflow = _local_join_tail(
            lk, lv, all_l, Rk, Rv, all_r, row_cap)
        return out_lk[0], out_lv[0], out_rv[0], live, overflow.reshape(1)

    spec = P(axis)
    fn = shard_map(local, mesh=mesh, in_specs=(spec,) * 4,
                   out_specs=(spec,) * 5)
    return fn(lkeys, lvals, rkeys, rvals)


def distributed_broadcast_join_keyed(mesh: Mesh,
                                     l_words: Sequence[jnp.ndarray],
                                     lvals: Sequence[jnp.ndarray],
                                     r_words: Sequence[jnp.ndarray],
                                     rvals: Sequence[jnp.ndarray],
                                     key_specs, row_cap: int,
                                     axis: str = "data"):
    """Typed-key broadcast inner join: the word-encoded (small) build side
    is replicated onto every shard with `all_gather` over ICI and each left
    shard joins locally — the typed sibling of distributed_broadcast_join,
    completing the broadcast path for string/decimal128/float/nullable keys
    (the reference's BroadcastHashJoin handles any key type). NULL keys
    never match (keys.keys_null_mask). Returns per-shard padded
    ([l key words], [lvals], [rvals], valid, overflow)."""
    return _distributed_join_keyed(mesh, l_words, lvals, r_words, rvals,
                                   key_specs, row_cap, slack=1.0, axis=axis,
                                   outer=False, broadcast=True)


def distributed_left_join_keyed(mesh: Mesh, l_words: Sequence[jnp.ndarray],
                                lvals: Sequence[jnp.ndarray],
                                r_words: Sequence[jnp.ndarray],
                                rvals: Sequence[jnp.ndarray],
                                key_specs, row_cap: int, slack: float = 2.0,
                                axis: str = "data"):
    """Typed-key left-outer join (see distributed_inner_join_keyed).
    Returns per-shard padded ([l key words], [lvals], [rvals], rvalid,
    valid, overflow); rvalid is False on unmatched left rows — including
    null-keyed left rows, which never match but are still emitted."""
    return _distributed_join_keyed(mesh, l_words, lvals, r_words, rvals,
                                   key_specs, row_cap, slack, axis,
                                   outer=True)


def distributed_left_join(mesh: Mesh, lkeys: jnp.ndarray, lvals: jnp.ndarray,
                          rkeys: jnp.ndarray, rvals: jnp.ndarray,
                          row_cap: int, slack: float = 2.0,
                          axis: str = "data"):
    """Left-outer equi-join, same shuffle as distributed_inner_join.

    Returns per-shard padded (lkey, lval, rval, rvalid, valid, overflow):
    rvalid is False on unmatched left rows (their rval slot must be read as
    null)."""
    n_peers = mesh.shape[axis]

    def local(lk, lv, rk, rv):
        (Lk,), (Lv,), Lalive, lspill = _hash_exchange(
            axis, n_peers, slack, lk, lv)
        (Rk,), (Rv,), Ralive, rspill = _hash_exchange(
            axis, n_peers, slack, rk, rv)
        out_lk, out_lv, out_rv, rvalid, live, joverflow = _local_join_tail(
            Lk, Lv, Lalive, Rk, Rv, Ralive, row_cap, outer=True)
        overflow = joverflow | lspill | rspill
        return out_lk[0], out_lv[0], out_rv[0], rvalid, live, overflow.reshape(1)

    spec = P(axis)
    fn = shard_map(local, mesh=mesh, in_specs=(spec,) * 4,
                   out_specs=(spec,) * 6)
    return fn(lkeys, lvals, rkeys, rvals)


def _distributed_semi_anti(mesh, lkeys, lvals, rkeys, semi, slack, axis):
    """Shared body: mark each left row matched/unmatched after the exchange;
    output stays left-shaped (no expansion, no row_cap)."""
    n_peers = mesh.shape[axis]

    def local(lk, lv, rk):
        (Lk,), (Lv,), Lalive, lspill = _hash_exchange(
            axis, n_peers, slack, lk, lv)
        (Rk,), _, Ralive, rspill = _hash_exchange(
            axis, n_peers, slack, rk, None)
        nl = Lk.shape[0]
        counts, _, _ = join_spans((jnp.concatenate([Lk, Rk]),),
                                  Lalive, Ralive, nl=nl, need_rorder=False)
        hit = counts > 0
        keep = Lalive & (hit if semi else ~hit)
        out_lk = jnp.where(keep, Lk, 0)
        out_lv = jnp.where(keep, Lv, 0)
        overflow = lspill | rspill
        return out_lk, out_lv, keep, overflow.reshape(1)

    spec = P(axis)
    fn = shard_map(local, mesh=mesh, in_specs=(spec,) * 3,
                   out_specs=(spec,) * 4)
    return fn(lkeys, lvals, rkeys)


def _distributed_semi_anti_keyed(mesh, l_words, lvals, r_words, key_specs,
                                 semi, slack, axis):
    """Typed-key shared body: keys as word lists, same marking logic.
    NULL keys never match (Spark equi-join semantics): a null-keyed left
    row is dropped by semi and kept by anti."""
    from .keys import keys_null_mask, spark_partition_hash
    n_peers = mesh.shape[axis]
    hash_fn = lambda ws: spark_partition_hash(ws, key_specs)  # noqa: E731
    l_words, lvals = list(l_words), list(lvals)
    r_words = list(r_words)
    _check_word_counts(l_words, r_words)
    nw, nlv = len(l_words), len(lvals)

    def local(*arrs):
        lw = list(arrs[:nw])
        lv = list(arrs[nw:nw + nlv])
        rw = list(arrs[nw + nlv:])
        Lw, Lv, Lalive, lspill = _hash_exchange(
            axis, n_peers, slack, lw, lv, hash_fn)
        Rw, _, Ralive, rspill = _hash_exchange(
            axis, n_peers, slack, rw, None, hash_fn)
        lmatch = Lalive & ~keys_null_mask(Lw, key_specs)
        rmatch = Ralive & ~keys_null_mask(Rw, key_specs)
        nl = Lw[0].shape[0]
        operands = tuple(jnp.concatenate([a, b]) for a, b in zip(Lw, Rw))
        counts, _, _ = join_spans(operands, lmatch, rmatch, nl=nl,
                                  need_rorder=False)
        hit = counts > 0
        keep = Lalive & (hit if semi else ~hit)
        out_lw = [jnp.where(keep, w, 0) for w in Lw]
        out_lv = [jnp.where(keep, v, 0) for v in Lv]
        overflow = lspill | rspill
        return tuple(out_lw), tuple(out_lv), keep, overflow.reshape(1)

    spec = P(axis)
    fn = shard_map(
        local, mesh=mesh, in_specs=(spec,) * (2 * nw + nlv),
        out_specs=(tuple(spec for _ in l_words), tuple(spec for _ in lvals),
                   spec, spec))
    return fn(*l_words, *lvals, *r_words)


def distributed_left_semi_join_keyed(mesh, l_words, lvals, r_words,
                                     key_specs, slack: float = 2.0,
                                     axis: str = "data"):
    """Typed-key left-semi join: left rows with at least one match.
    Returns per-shard padded ([l key words], [lvals], valid, overflow)."""
    return _distributed_semi_anti_keyed(mesh, l_words, lvals, r_words,
                                        key_specs, True, slack, axis)


def distributed_left_anti_join_keyed(mesh, l_words, lvals, r_words,
                                     key_specs, slack: float = 2.0,
                                     axis: str = "data"):
    """Typed-key left-anti join: left rows with no match."""
    return _distributed_semi_anti_keyed(mesh, l_words, lvals, r_words,
                                        key_specs, False, slack, axis)


def distributed_left_semi_join(mesh: Mesh, lkeys: jnp.ndarray,
                               lvals: jnp.ndarray, rkeys: jnp.ndarray,
                               slack: float = 2.0, axis: str = "data"):
    """Left rows with at least one match. Returns per-shard padded
    (lkey, lval, valid, overflow); output is left-sized, no row_cap."""
    return _distributed_semi_anti(mesh, lkeys, lvals, rkeys, True, slack, axis)


def distributed_left_anti_join(mesh: Mesh, lkeys: jnp.ndarray,
                               lvals: jnp.ndarray, rkeys: jnp.ndarray,
                               slack: float = 2.0, axis: str = "data"):
    """Left rows with no match. Same contract as the semi join."""
    return _distributed_semi_anti(mesh, lkeys, lvals, rkeys, False, slack, axis)


# ---- what keeps the walk at the size of its live rows -----------------------
# (the eager SPMD walk reads a count from the device between two programs,
# so each of these takes a capacity the caller has just observed)

def distributed_live_counts(mesh: Mesh, alive: jnp.ndarray,
                            axis: str = "data") -> jnp.ndarray:
    """(n_peers,) int32: live rows on each shard of a row-sharded mask."""
    def local(live):
        return jnp.sum(live.astype(jnp.int32)).reshape(1)
    return shard_map(local, mesh=mesh, in_specs=(P(axis),),
                     out_specs=P(axis))(alive)


def _compact_local(arrs, live, cap: int):
    """The first `cap` live rows of one shard, in their order: the price
    follows what is kept, not the frame (`_live_positions`, then a gather
    of `cap` slots a column). -> ([arrays], keep, overflow)."""
    idx, keep, over = _live_positions(live, cap)
    return [jnp.where(keep, jnp.take(a, idx, axis=0),
                      jnp.asarray(0, a.dtype)) for a in arrs], keep, over


def distributed_compact(mesh: Mesh, arrays: Sequence[jnp.ndarray],
                        alive: jnp.ndarray, cap: int, axis: str = "data",
                        replicated: bool = False):
    """Each shard's live rows packed to the front of `cap` slots (at least
    the fullest shard's count: `distributed_live_counts`), order and
    placement kept, so the hash-partitioning property survives. No
    collective. A replicated relation compacts as one array.
    Returns ([arrays], alive, overflow): overflow (one bool a shard) says
    that a shard holds more than `cap` live rows and lost the rest; the
    caller raises."""
    arrays = list(arrays)
    if replicated:
        outs, keep, over = _compact_local(arrays, alive, cap)
        return tuple(outs), keep, over.reshape(1)

    def local(*arrs):
        outs, keep, over = _compact_local(list(arrs[:-1]), arrs[-1], cap)
        return tuple(outs), keep, over.reshape(1)

    spec = P(axis)
    return shard_map(local, mesh=mesh, in_specs=(spec,) * (len(arrays) + 1),
                     out_specs=(tuple(spec for _ in arrays), spec, spec)
                     )(*arrays, alive)


def distributed_head(mesh: Mesh, arrays: Sequence[jnp.ndarray], cap: int,
                     axis: str = "data"):
    """The first `cap` slots of every shard (a relation whose live rows are
    already a prefix of each shard: a group-by's output). No collective."""
    arrays = list(arrays)
    spec = P(axis)
    return shard_map(lambda *xs: tuple(x[:cap] for x in xs), mesh=mesh,
                     in_specs=(spec,) * len(arrays),
                     out_specs=tuple(spec for _ in arrays))(*arrays)


def distributed_concat(mesh: Mesh, sides: Sequence[Sequence[jnp.ndarray]],
                       axis: str = "data"):
    """UNION ALL of row-sharded relations with no collective: every shard
    appends its own rows of each side (`sides[k][j]` is side k's j-th
    array). A logical concatenation would reshard nearly every row."""
    k, width = len(sides), len(sides[0])

    def local(*arrs):
        return tuple(jnp.concatenate([arrs[s * width + j] for s in range(k)])
                     for j in range(width))

    spec = P(axis)
    flat = [a for side in sides for a in side]
    return shard_map(local, mesh=mesh, in_specs=(spec,) * len(flat),
                     out_specs=tuple(spec for _ in range(width)))(*flat)


def distributed_partition_counts(mesh: Mesh, key_words, key_specs, alive,
                                 axis: str = "data") -> jnp.ndarray:
    """(n_peers * n_peers,) int32: how many live rows each shard would send
    to each peer under the Spark-exact hash of the key words — the buckets
    of `distributed_repartition_keyed`, counted before it runs, so the
    exchange ships buckets of the fullest one's size and cannot spill."""
    from .keys import spark_partition_hash
    n_peers = mesh.shape[axis]
    key_words = list(key_words)

    def local(*arrs):
        part = partition_ids(spark_partition_hash(list(arrs[:-1]),
                                                  key_specs), n_peers)
        part = jnp.where(arrs[-1], part, jnp.int32(n_peers))
        peers = jnp.arange(n_peers, dtype=jnp.int32)
        return jnp.sum(part[:, None] == peers[None, :], axis=0,
                       dtype=jnp.int32)

    spec = P(axis)
    return shard_map(local, mesh=mesh,
                     in_specs=(spec,) * (len(key_words) + 1),
                     out_specs=spec)(*key_words, alive)


def distributed_lookup_join(mesh: Mesh, l_words, r_words, rvals, key_specs,
                            lalive, ralive, axis: str = "data"):
    """Many-to-one join of a sharded probe side against a REPLICATED build
    side of few rows with distinct keys (a filtered dimension: 15 days of
    a calendar; a small dimension whole: 402 stores): every probe row is
    compared with every build row, one elementwise pass per build row, and
    takes the one that matches. No sort, no gather, no output capacity:
    the result keeps the probe side's slots and marks the rows that found
    a partner. Null keys match nothing.
    Returns ([rvals at the probe rows], matched)."""
    from .keys import keys_null_mask
    l_words, r_words, rvals = list(l_words), list(r_words), list(rvals)
    nw, nrv = len(l_words), len(rvals)

    def local(*arrs):
        lw, rw = list(arrs[:nw]), list(arrs[nw:2 * nw])
        rv = list(arrs[2 * nw:2 * nw + nrv])
        lmatch = arrs[-2] & ~keys_null_mask(lw, key_specs)
        rmatch = arrs[-1] & ~keys_null_mask(rw, key_specs)
        def step(j, carry):
            matched, outs = carry
            eq = lmatch & rmatch[j]
            for a, b in zip(lw, rw):
                eq = eq & (a == b[j])
            return (matched | eq,
                    tuple(jnp.where(eq, v[j], o) for v, o in zip(rv, outs)))

        nothing = lw[0] - lw[0]      # zeros that vary over the mesh's axis
        matched, outs = jax.lax.fori_loop(
            0, rw[0].shape[0], step,
            (nothing != 0, tuple(nothing.astype(v.dtype) for v in rv)))
        return tuple(outs), matched

    spec, rep = P(axis), P()
    return shard_map(local, mesh=mesh,
                     in_specs=(spec,) * nw + (rep,) * (nw + nrv)
                     + (spec, rep),
                     out_specs=(tuple(spec for _ in rvals), spec)
                     )(*l_words, *r_words, *rvals, lalive, ralive)


def distributed_reduce(mesh: Mesh, vals: Sequence[jnp.ndarray],
                       aggs: Sequence[Tuple[int, str]], alive,
                       axis: str = "data"):
    """A keyless aggregate over a row-sharded relation: each shard reduces
    its live rows, an all-reduce merges the partials, and the one result
    row lives in shard 0's first slot (one slot a shard, the others dead).
    `aggs`: [(value index, sum|count|min|max)], exact in int64.
    Returns ([(n_peers,) arrays], valid)."""
    vals = list(vals)
    aggs = tuple((int(i), a) for i, a in aggs)

    def local(*arrs):
        live = arrs[-1]
        outs = []
        with jax.named_scope("exchange.reduce"):
            for i, a in aggs:
                if a == "count":
                    r = jax.lax.psum(jnp.sum(live.astype(jnp.int64)), axis)
                elif a == "sum":
                    r = jax.lax.psum(jnp.sum(jnp.where(
                        live, arrs[i].astype(jnp.int64), 0)), axis)
                else:
                    x = jnp.where(live, arrs[i].astype(jnp.int64),
                                  jnp.int64(_identity(a)))
                    r = (jax.lax.pmin(jnp.min(x), axis) if a == "min"
                         else jax.lax.pmax(jnp.max(x), axis))
                outs.append(r.reshape(1))
        first = (jax.lax.axis_index(axis) == 0).reshape(1)
        return tuple(outs), first

    spec = P(axis)
    return shard_map(local, mesh=mesh, in_specs=(spec,) * (len(vals) + 1),
                     out_specs=(tuple(spec for _ in aggs), spec)
                     )(*vals, alive)


def narrow_keys(words_by_side, alive_by_side, lo):
    """Key words as 32-bit offsets from `lo` (one int64 per word: the least
    live value over every side, which the caller has read and checked:
    no word spans 2**31 or more). A sort's compile time and its run time
    go by the width of its keys (a 64-bit key is two 32-bit operands to
    the chip's sort: 56 s against 20 s to compile one at 262,144 rows),
    and a surrogate key, an order number or a business id spans a few
    million values. Dead rows keep whatever the subtraction leaves: every
    consumer masks them. `widen_keys` is the way back."""
    return [[jnp.where(alive, w - lo[i], 0).astype(jnp.int32)
             for i, w in enumerate(words)]
            for words, alive in zip(words_by_side, alive_by_side)]


def widen_keys(words, lo):
    return [lo[i] + w.astype(jnp.int64) for i, w in enumerate(words)]


def key_ranges(words_by_side, alive_by_side):
    """(n_words, 2) int64: the least and the greatest live value of each
    key word over every side (int64 max / min where nothing is live)."""
    info = jnp.iinfo(jnp.int64)
    sides = list(zip(words_by_side, alive_by_side))
    return jnp.stack([jnp.stack([
        jnp.min(jnp.stack([jnp.min(jnp.where(a, ws[i], info.max))
                           for ws, a in sides])),
        jnp.max(jnp.stack([jnp.max(jnp.where(a, ws[i], info.min))
                           for ws, a in sides]))])
        for i in range(len(words_by_side[0]))])
