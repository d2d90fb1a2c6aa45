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
                     payloads: Sequence[Tuple[jnp.ndarray, object]]):
    """Shared bucket-then-all-to-all body (the shape of shuffle.py's
    _exchange_local): bucket rows by `part` into (n_peers, cap) slots, ship
    each bucket to its peer, and — like _exchange_local — ship only the (P,)
    sent counts and rebuild the validity mask receiver-side (capacity× less
    ICI traffic than a full bool mask).

    payloads: [(array, dead-slot fill)]. Returns (received arrays (P*cap,),
    recv_valid (P*cap,), spilled scalar bool)."""
    gi, bvalid, counts = build_partition_map(part, n_peers, cap)
    spilled = jnp.any(counts > cap)
    outs = []
    for x, fill in payloads:
        b = jnp.where(bvalid, jnp.take(x, gi, axis=0),
                      jnp.asarray(fill, x.dtype))
        outs.append(jax.lax.all_to_all(b, axis, 0, 0, tiled=True).reshape(-1))
    sent = jnp.minimum(counts, cap)
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
    ks = [jnp.where(alive, k, _DEAD_KEY) for k in key_list]  # dead rows last
    sorted_all = jax.lax.sort([*ks, iota], num_keys=len(ks), is_stable=True)
    sks, order = sorted_all[:-1], sorted_all[-1]
    salive = jnp.take(alive, order, axis=0)

    neq = jnp.zeros((n,), bool)
    for o in sks:
        neq = neq | (o != jnp.roll(o, 1))
    boundary = neq.at[0].set(True) if n else neq
    gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
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
        c = jnp.cumsum(x)
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

            def combine(a, b, op=op):
                ab, av = a
                bb, bv = b
                m = jnp.minimum(av, bv) if op == "min" else jnp.maximum(av, bv)
                return ab | bb, jnp.where(bb, bv, m)
            _, res = jax.lax.associative_scan(combine, (boundary, masked))
            outs.append(jnp.take(res, last, axis=0))

    n_groups = (gid[-1] + 1) if n else jnp.int32(0)
    # real groups only: the dead-key sentinel group (if any padding existed)
    # sorts last and has alive_cnt == 0 — it must not trip overflow
    in_range = iota < n_groups
    n_real = jnp.sum((alive_cnt > 0) & in_range).astype(jnp.int32)

    valid = (_fit(alive_cnt, key_cap, 0) > 0) & \
        (jnp.arange(key_cap, dtype=jnp.int32) < n_groups)
    gkeys = [_fit(jnp.take(k, starts, axis=0, mode="clip"), key_cap,
                  _DEAD_KEY) for k in sks]
    out_keys = gkeys if multi else gkeys[0]
    return (out_keys, [_fit(o, key_cap, 0) for o in outs], valid, n_real)


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
                                  slack: float = 2.0, axis: str = "data",
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

    Returns ([key words], [vals], valid, overflow); the key words come
    back in the wire form they were passed (the caller widens). overflow
    means a bucket spilled its slack-sized capacity — retry with bigger
    slack (SplitAndRetry contract)."""
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
                axis, n_peers, slack, ws, vs, hash_fn, alive=live,
                hash_keys=ws64, key_fills=fills)
        else:
            Ws, Vs, recv_alive, spilled = _hash_exchange(
                axis, n_peers, slack, ws, vs, hash_fn, alive=live)
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
                                     rvals: Sequence[jnp.ndarray],
                                     key_specs, row_cap: int = 0,
                                     axis: str = "data", how: str = "inner",
                                     lalive=None, ralive=None,
                                     r_replicated: bool = False):
    """Equi-join of two ALREADY-ALIGNED sides with no exchange: both sides
    are either hash-partitioned by the positionally-matching key tuples
    (the explicit `Exchange(hash)` ran upstream, so matching rows are
    co-located), or the right side is REPLICATED (`r_replicated=True`: the
    `Exchange(broadcast)` replicated the small build side onto every
    shard, the probe side never moves). Each shard then joins locally —
    the plan tier's counterpart of Spark executing a join above its
    exchanges.

    `how`: inner (padded row_cap output), left_semi / left_anti (output
    stays left-shaped, no row_cap). `lalive`/`ralive` mark live rows of
    padded sharded relations; NULL keys never match (Spark equi-join
    semantics).

    Returns: inner -> ([l key words], [lvals], [rvals], valid, overflow);
    semi/anti -> ([l key words], [lvals], keep, overflow)."""
    from .keys import keys_null_mask
    l_words, lvals = list(l_words), list(lvals)
    r_words, rvals = list(r_words), list(rvals)
    _check_word_counts(l_words, r_words)
    nw, nlv, nrv = len(l_words), len(lvals), len(rvals)
    has_lal, has_ral = lalive is not None, ralive is not None
    semi_anti = how in ("left_semi", "left_anti")
    if how not in ("inner", "left_semi", "left_anti"):
        raise ValueError(f"unsupported colocated join type {how!r}")

    def local(*arrs):
        i = 0
        lw = list(arrs[i:i + nw]); i += nw
        lv = list(arrs[i:i + nlv]); i += nlv
        rw = list(arrs[i:i + nw]); i += nw
        rv = list(arrs[i:i + nrv]); i += nrv
        Lal = arrs[i] if has_lal else jnp.ones(lw[0].shape, bool)
        i += int(has_lal)
        Ral = arrs[i] if has_ral else jnp.ones(rw[0].shape, bool)
        lmatch = Lal & ~keys_null_mask(lw, key_specs)
        rmatch = Ral & ~keys_null_mask(rw, key_specs)
        if semi_anti:
            nl = lw[0].shape[0]
            operands = tuple(jnp.concatenate([a, b])
                             for a, b in zip(lw, rw))
            counts, _, _ = join_spans(operands, lmatch, rmatch, nl=nl,
                                      need_rorder=False)
            hit = counts > 0
            keep = Lal & (hit if how == "left_semi" else ~hit)
            out_lw = [jnp.where(keep, w, jnp.asarray(0, w.dtype))
                      for w in lw]
            out_lv = [jnp.where(keep, v, jnp.asarray(0, v.dtype))
                      for v in lv]
            return (tuple(out_lw), tuple(out_lv), keep,
                    jnp.zeros((1,), bool))
        out_lw, out_lv, out_rv, _, live, ovf = _local_join_tail(
            lw, lv, Lal, rw, rv, Ral, row_cap, outer=False,
            lmatch=lmatch, rmatch=rmatch)
        return (tuple(out_lw), tuple(out_lv), tuple(out_rv), live,
                ovf.reshape(1))

    spec = P(axis)
    rspec = P() if r_replicated else spec
    in_specs = ((spec,) * (nw + nlv) + (rspec,) * (nw + nrv)
                + (spec,) * int(has_lal) + (rspec,) * int(has_ral))
    if semi_anti:
        out_specs = (tuple(spec for _ in l_words),
                     tuple(spec for _ in lvals), spec, spec)
    else:
        out_specs = (tuple(spec for _ in l_words),
                     tuple(spec for _ in lvals),
                     tuple(spec for _ in rvals), spec, spec)
    fn = shard_map(local, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs)
    args = (l_words + lvals + r_words + rvals
            + ([lalive] if has_lal else [])
            + ([ralive] if has_ral else []))
    return fn(*args)


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
            [(w, _DEAD_KEY) for w in sws] + [(sv, 0) for sv in svs])
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
                   hash_keys=None, key_fills=None):
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
    cap = max(1, math.ceil(nloc / n_peers * slack))
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
