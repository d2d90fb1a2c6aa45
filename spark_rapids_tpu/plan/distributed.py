"""Full-plan SPMD distributed lowering for the eager tier.

PR 1 distributed exactly one shape — HashAggregate over Exchange — and
every other operator of a meshed plan still funneled through one chip.
This module generalizes that special case into a whole-plan tier
(docs/distributed.md): when `PlanExecutor(mesh=...)` runs an eager plan,
every operator with a distributed form executes ON the mesh over a
`ShardedRel` — a padded, row-sharded relation (global logical arrays with
`NamedSharding`, a live-row mask, and the hash-partitioning property the
rows currently satisfy) — and data crosses the ICI only at explicit
`Exchange` boundaries (hash / broadcast / gather) or the fused exchanges
inside the two-phase aggregate and sample-sort primitives:

- Scan: the bound table pads to a multiple of the mesh size and shards
  row-wise (`NamedSharding(mesh, P(axis))`); padding rows are dead. A
  buffer born on the mesh with that sharding is adopted in place.
- Filter / Project / FusedSelect: elementwise over the sharded columns —
  sharding propagates through plain jnp, no collective; scalar-aggregate
  expressions reduce over live rows (GSPMD all-reduce).
- Exchange(hash): `distributed_repartition_keyed` — the standalone
  shuffle; Exchange(broadcast): the build side replicates onto every
  shard; Exchange(gather): the sharded relation collects to one device
  (the sink boundary, or the handoff into an operator with no
  distributed form — the same graceful-boundary pattern as the streaming
  tier's concat).
- HashJoin: consumes its exchanges — both sides partitioned (or one
  replicated) means `distributed_colocated_join_keyed` joins shard-local
  with NO further movement; an unplanned join repartitions implicitly.
- HashAggregate over Exchange(hash) FUSES into the two-phase
  partial→all-to-all→final `distributed_groupby_keyed` program (the
  exchange ships per-group partials, not rows); over an input already
  partitioned by a subset of its keys the exchange is ELIDED and
  `distributed_local_groupby` merges shard-locally.
- Sort / TopK: `distributed_sort_keyed` sample-sorts to global order
  (range partitioning; descending keys ride bitwise-inverted words);
  TopK masks the global rank prefix.
- Union: every shard appends its own rows of each side (no collective).

Frames follow their live rows: after an operator that drops rows
(`_settle`) and before a hash exchange (`_repartition_rel`) the walk reads
a count from the device and sizes the next program by it (`bucket`), so a
date window's 1% is sorted, gathered and shipped as 1%. Static capacities
that cannot be counted ahead (a final merge's key_cap, the sample sort's
slack) escalate geometrically via
`parallel.autoretry.auto_retry_overflow` and the final values memoize per
(plan fingerprint, node) on the executor, exactly like the capped tier's
caps memo. Every primitive call goes through a bounded cache of
`jax.jit`-wrapped callables — an eager `shard_map` re-traces per call;
the jitted form re-traces only per (program, shapes).

Runtime gates (a node that fails one gathers its inputs and runs on the
local eager path): fixed-width 1-D columns only, aggregate value columns
non-null and non-float (the exchange accumulates in int64), no `mean`;
Limit has no distributed form (a keyless aggregate reduces on the mesh).
What ran through that fallback is counted (`local_ops`). Join emission
order and aggregate output placement differ from the single-device
kernels, so relations carry `order_keys` — the gather re-sorts a
distributed aggregate's output by its group keys to match the local
sort-based kernel row for row; Sort's own output is globally ordered and
gathers in place (ties may order differently than the local stable sort
when the sort keys do not totally order the rows).

Transport (plan/transport.py, docs/distributed.md#transport): with
SPARK_RAPIDS_TPU_EXCHANGE_PACK on (default), every exchange payload
ships in packed wire form — FOR-narrowed integer planes and bit-packed
validity inside the collectives, dictionary/RLE on the host-materialized
broadcast build side, packed planes on the device→host gather pull —
and unpacks on the receiving side. Byte accounting is per edge, live
payload only, each edge counted once (broadcast x (n_peers-1)):
`exchange_bytes` is the wire form, `exchange_bytes_logical` the
unpacked per-column payload, and both stay at or under the certifier's
per-edge bound (analysis/footprint.py). SPARK_RAPIDS_TPU_EXCHANGE_ASYNC
dispatches an Exchange's pack+transfer on a worker thread (`PendingRel`)
so the transfer overlaps downstream operators' compute until a consumer
resolves it — the PR 4 prefetch shape at the exchange boundary; a
transfer fault then surfaces (and degrades) at the consuming operator.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import dtypes
from ..columnar import Column, Table
from ..parallel.keys import (KeySpec, _ONE_WORD_KINDS, decode_key_columns,
                             encode_key_column)
from ..utils.lru import LruDict
from ..utils.tracing import span, text as _span_text
from . import transport
from .nodes import (Exchange, Filter, FusedSelect, HashAggregate, HashJoin,
                    Limit, PlanNode, Project, Scan, Sort, TopK, Union)

_KEYABLE_KINDS = set(_ONE_WORD_KINDS) | {dtypes.Kind.FLOAT32,
                                         dtypes.Kind.FLOAT64}
_DIST_AGGS = ("sum", "count", "min", "max", "size")

# jitted distributed primitives, keyed by (name, mesh, axis, static params):
# an eager shard_map re-traces AND re-compiles per call; one bounded cache
# for the whole process keeps repeat executions at dispatch cost.
# LruDict.get/__setitem__ are internally locked (utils/lru.py — the
# serving layer made every shared memo self-guarding), so the async
# exchange workers (PendingRel) that hit this cache concurrently need no
# external lock for single get/insert operations
_JIT_PRIMS = LruDict(256)


def _jitted(key, fn, **jit_kw):
    """Bounded cache of compiled primitive callables: `fn` jitted (with
    `jit_kw`) under the name `key[0]`, so that a device trace says
    `jit_compact/fusion.3` and not `jit__lambda/fusion.3` for every program
    of the walk. Safe under concurrent async exchange workers: a lost race
    builds one redundant (cheap, un-traced) wrapper, never corrupts the
    cache."""
    prog = _JIT_PRIMS.get(key)
    if prog is None:
        def named(*args):
            return fn(*args)
        named.__name__ = named.__qualname__ = key[0]
        prog = _JIT_PRIMS[key] = jax.jit(named, **jit_kw)
    return prog


class ShardedRel:
    """A relation living on the mesh: `table` columns are GLOBAL logical
    arrays sharded row-wise (`NamedSharding(mesh, P(axis))`, or fully
    replicated for a broadcast build side), `valid` marks live rows
    (padding and exchange dead slots are False), `part` is the set of key
    tuples the rows are hash-partitioned by (equal tuples co-located —
    the exchange-elision property), and `order_keys` names the columns a
    gather must re-sort by to reproduce the local tier's row order (set
    by aggregates, whose local kernel emits key-sorted rows).

    Quacks like a Table where the executor's metric loop needs it:
    `columns` and `num_rows` (live count)."""

    __slots__ = ("table", "valid", "part", "replicated", "order_keys",
                 "_num_rows", "_local")

    def __init__(self, table: Table, valid: jnp.ndarray,
                 part: frozenset = frozenset(), replicated: bool = False,
                 order_keys: Optional[List[str]] = None):
        self.table = table
        self.valid = valid
        self.part = part
        self.replicated = replicated
        self.order_keys = order_keys
        self._num_rows = None
        self._local = None

    @property
    def columns(self):
        return self.table.columns

    @property
    def num_rows(self) -> int:
        if self._num_rows is None:
            # reduce on device, ship 8 bytes — the executor's metric loop
            # reads this per operator, and pulling the whole global mask
            # to host (np.asarray) would serialize the walk on a
            # full-mask transfer every node
            with span("ops.host_sync", site="dist.num_rows"):
                self._num_rows = int(
                    jnp.sum(self.valid.astype(jnp.int64)))
        return self._num_rows

    @property
    def padded_rows(self) -> int:
        return self.table.num_rows

    def sharding_str(self, n_peers: int) -> str:
        return _sharding_str(self.part, self.replicated, n_peers)

    def to_local_table(self) -> Table:
        """Gather to one device and compact to the live rows (restoring
        the local tier's row order via `order_keys` when set) — the sink
        boundary. Cached: DAG-shared consumers gather once."""
        if self._local is not None:
            return self._local
        # every buffer crosses to the host whole and comes back compacted
        with span("ops.host_sync", site="dist.to_local"):
            mask = np.asarray(self.valid)
            idx = np.nonzero(mask)[0]
            cols = []
            for c in self.table.columns:
                data = jnp.asarray(np.asarray(c.data)[idx])
                validity = c.validity
                if validity is not None:
                    validity = jnp.asarray(np.asarray(validity)[idx])
                cols.append(dataclasses.replace(
                    c, data=data, validity=validity,
                    length=int(idx.shape[0])))
        t = Table(cols, names=list(self.table.names))
        if self.order_keys:
            from .executor import _ops
            t = _ops().sort_table(t, key_names=list(self.order_keys),
                                  ascending=[True] * len(self.order_keys))
        self._local = t
        return t


def _sharding_str(part: frozenset, replicated: bool, n_peers: int) -> str:
    if replicated:
        return f"replicated@{n_peers}"
    if part:
        keys = min(part)   # deterministic pick for display
        return f"hash[{','.join(keys)}]@{n_peers}"
    return f"rows@{n_peers}"


class PendingRel:
    """A ShardedRel still in flight on an exchange worker thread
    (SPARK_RAPIDS_TPU_EXCHANGE_ASYNC): the plan walk continues past the
    Exchange node while pack+transfer run on the thread, and the first
    consumer `resolve()`s — the transfer wall that ran while the main
    thread was NOT blocked waiting here is the edge's measured
    `exchange_overlap_ms`. Placement facts (`part`/`replicated`) are
    known statically so the metric loop stamps `sharding` without
    forcing a wait; every data accessor resolves first. A transfer
    error raises at the consumer (the async fault-attribution caveat in
    docs/distributed.md#transport), and the consumer's retry loop gets
    REAL re-execution: each later resolve re-runs the exchange
    synchronously instead of re-raising a cached error."""

    pending = True

    def __init__(self, fn, metric, nbytes_fn,
                 part: frozenset = frozenset(), replicated: bool = False):
        self._fn = fn
        self._metric = metric
        self._nbytes_fn = nbytes_fn
        self.part = part
        self.replicated = replicated
        self._result = None
        self._err = None
        self._t0 = self._t1 = 0.0
        self._resolved = False

        def work():
            self._t0 = time.perf_counter()
            try:
                out = fn()
                # the transfer must COMPLETE on the thread — otherwise
                # "async" would just defer the device work to the
                # consumer and the overlap would be fiction
                with span("plan.wait", site="dist.async_exchange"):
                    jax.block_until_ready(
                        [c.data for c in out.table.columns])
                self._result = out
            except BaseException as e:    # surfaces at the consumer
                self._err = e
            finally:
                self._t1 = time.perf_counter()

        self._thread = threading.Thread(
            target=work, daemon=True, name="spark-rapids-tpu-exchange")
        self._thread.start()

    def _stamp(self, dur: float) -> None:
        m = self._metric
        m.wall_ms = dur * 1e3
        m.rows_out = self._result.num_rows
        m.bytes_out = self._nbytes_fn(self._result.table)

    def resolve(self) -> "ShardedRel":
        if not self._resolved:
            w0 = time.perf_counter()
            self._thread.join()
            blocked = time.perf_counter() - w0
            self._resolved = True
            dur = self._t1 - self._t0
            self._metric.exchange_overlap_ms = max(0.0, dur - blocked) * 1e3
            if self._result is not None:
                self._stamp(dur)
        if self._result is None:
            # the worker thread failed. Raise the original error ONCE on
            # the consuming thread; every later resolve (the consumer's
            # fault-retry loop re-entering exec_node) RE-RUNS the
            # exchange synchronously here, so transient faults get real
            # re-execution semantics instead of a cached error that
            # makes every retry futile
            err, self._err = self._err, None
            if err is not None:
                raise err
            t0 = time.perf_counter()
            out = self._fn()
            with span("plan.wait", site="dist.rerun_exchange"):
                jax.block_until_ready([c.data for c in out.table.columns])
            self._result = out
            self._stamp(time.perf_counter() - t0)
        return self._result

    def sharding_str(self, n_peers: int) -> str:
        return _sharding_str(self.part, self.replicated, n_peers)

    # -- data accessors force resolution -------------------------------------
    @property
    def table(self):
        return self.resolve().table

    @property
    def valid(self):
        return self.resolve().valid

    @property
    def columns(self):
        return self.resolve().columns

    @property
    def num_rows(self) -> int:
        return self.resolve().num_rows

    @property
    def padded_rows(self) -> int:
        return self.resolve().padded_rows

    @property
    def order_keys(self):
        return self.resolve().order_keys

    def to_local_table(self) -> Table:
        return self.resolve().to_local_table()


def _resolve_rel(c):
    return c.resolve() if getattr(c, "pending", False) else c


def table_shardable(t: Table) -> bool:
    """Whether every column can ride the distributed tier: fixed-width
    1-D buffers only (strings/lists/decimal128 keep the plan local —
    the graceful gather boundary, not an error)."""
    return all(c.data is not None and c.offsets is None and not c.children
               and getattr(c.data, "ndim", 1) == 1 for c in t.columns)


def shard_table(mesh, axis: str, t: Table,
                part: frozenset = frozenset()) -> ShardedRel:
    """Pad a bound Table to a multiple of the mesh size and shard it
    row-wise across the peers (dead padding rows carry zeros and a False
    live mask) — the mesh-sharded Scan. An empty table becomes one dead
    slot per shard so the SPMD shapes stay non-degenerate. A buffer that
    was born on the mesh (it already carries this row sharding and needs
    no padding) is adopted in place: a deployment's tables live on their
    chips, and nothing is copied or moved."""
    n_peers = mesh.shape[axis]
    n = t.num_rows
    pad = (-n) % n_peers if n else n_peers
    spec = NamedSharding(mesh, P(axis))

    def put(a, fill):
        if pad:
            a = jnp.concatenate([a, jnp.full((pad,), fill, a.dtype)])
        elif isinstance(a, jax.Array) and \
                a.sharding.is_equivalent_to(spec, a.ndim):
            return a
        return jax.device_put(a, spec)

    cols = []
    for c in t.columns:
        validity = c.validity
        if validity is not None:
            validity = put(validity, False)
        cols.append(dataclasses.replace(c, data=put(c.data, 0),
                                        validity=validity, length=n + pad))
    # the mask is made on the shards, not shipped from the first device
    valid = _jitted(("live", mesh, axis, n, n + pad),
        lambda: jnp.arange(n + pad, dtype=jnp.int32) < n,
        out_shardings=spec)()
    rel = ShardedRel(Table(cols, names=list(t.names)), valid, part=part)
    rel._num_rows = n
    return rel


# ---- capacities from counts the walk has just read --------------------------

# Three thresholds, each with the reading it was set from (PERF.md, PR 32:
# my chip runs on one TPU v5 lite unless it says otherwise).
#
# A sharded relation is packed to its live rows when that frees a quarter
# of a frame of at least this many slots a shard. NOT measured on the
# chip: it is set by what a compaction costs to build, one program of 2
# to 5 s of cold compile for each (columns, capacity) met, against the
# 273 programs and 444 s of the cell's cold set-up; a sort over 4,096
# slots is some 0.05 ms (12 ns a slot in PR 31's traced joins), so below
# it there is nothing to win back. A replicated relation has no such
# floor (`_settle`).
_COMPACT_MIN_SLOTS = 4096
# A replicated build side of at most `_LOOKUP_SLOTS` slots with distinct
# keys is probed by comparison (parallel/relational.distributed_lookup_join)
# as long as probe slots a shard x build slots stays under `_LOOKUP_WORK`.
# Readings: 16 build slots x 39.6 M probe rows (634 M compares) 9.5 ms,
# 448 x 425,984 (191 M) 3.8 ms, so 2**30 compares are some 16 to 20 ms;
# the sort join it replaces took 647 ms over 39.6 M + 15 rows (three
# sorts of 183 to 233 ms) plus 78 ms of emission, and 120 s to compile
# at 425,984 rows. 1,024 build slots is the loop's length at which a
# 1 M-row probe side reaches the work bound; no larger build side was
# timed.
_LOOKUP_SLOTS = 1024
_LOOKUP_WORK = 1 << 30


def _raise_if_lost(lost, what: str, cap: int) -> None:
    """`lost`: one bool a shard from a program that was given a capacity
    the walk had counted. Set, it means rows were dropped: the program
    that counted and the one that moved disagree, which is a fault of the
    engine and never a capacity to escalate."""
    with span("ops.host_sync", site="dist.lost"):
        dropped = bool(np.asarray(lost).any())
    if dropped:
        from ..parallel.autoretry import CapacityOverflowError
        raise CapacityOverflowError(
            f"{what}: a shard held more rows than the {cap} slots counted "
            "for it; rows would have been lost")


def bucket(n: int) -> int:
    """The capacity for `n` observed rows: `n` rounded up to four binary
    digits (at most an eighth above it), so that nearby counts share a
    compiled program and a frame is never twice its rows."""
    n = max(int(n), 8)
    step = 1 << max(n.bit_length() - 4, 0)
    return -(-n // step) * step


# ---- value packing (columns <-> primitive payload arrays) -------------------

def _pack_cols(t: Table, names: List[str]):
    """Columns -> flat payload arrays for the exchange primitives. Each
    column contributes its data array plus, when nullable, its validity
    (a bool payload — the exchanges preserve payload dtypes). Returns
    (arrays, layout) where layout rebuilds the columns."""
    arrays, layout = [], []
    for nm in names:
        c = t[nm]
        arrays.append(c.data)
        has_v = c.validity is not None
        if has_v:
            arrays.append(c.validity)
        layout.append((nm, c.dtype, has_v))
    return arrays, layout


def _unpack_cols(arrays, layout) -> List[Column]:
    """Payload arrays -> typed columns (casting back any dtype the
    collective math promoted)."""
    cols = []
    i = 0
    for nm, dt, has_v in layout:
        data = arrays[i].astype(dt.storage_dtype())
        i += 1
        validity = None
        if has_v:
            validity = arrays[i].astype(jnp.bool_)
            i += 1
        cols.append(Column(dtype=dt, length=int(data.shape[0]), data=data,
                           validity=validity))
    return cols


def _key_specs(lt: Table, lkeys, rt: Optional[Table] = None,
               rkeys=None) -> Optional[List[KeySpec]]:
    """Shared static key layout for one or two sides; None when a key
    dtype has no distributed encoding (or the sides' kinds differ)."""
    specs = []
    for i, lk in enumerate(lkeys):
        lc = lt[lk]
        kind = lc.dtype.kind
        if kind not in _KEYABLE_KINDS:
            return None
        nullable = lc.validity is not None
        if rt is not None:
            rc = rt[rkeys[i]]
            if rc.dtype.kind != kind:
                return None
            nullable = nullable or rc.validity is not None
        specs.append(KeySpec(lc.dtype, 1, nullable))
    return specs


def _encode_keys(t: Table, keys, specs) -> List[jnp.ndarray]:
    words = []
    for k, sp in zip(keys, specs):
        w, _ = encode_key_column(t[k], spec=sp)
        words.extend(w)
    return words


def _decode_keys(words, specs, names, alive) -> List[Tuple[str, Column]]:
    """Key word arrays back to typed named columns. The relation's `valid`
    mask owns dead-slot liveness, so decode must NOT fold `alive` into
    column validity — a non-nullable key column stays non-nullable (the
    downstream aggregate's non-null gate, and any later encode under the
    same spec, depend on it). Dead slots decode to sentinel garbage that
    no consumer reads."""
    del alive
    return list(zip(names, decode_key_columns(words, specs)))


# ---- partitioning transfer (the exchange-elision property) ------------------

def transfer_part(node: PlanNode, child_parts: List[frozenset],
                  child_schemas=None) -> frozenset:
    """Static/runtime-shared rule: the hash-partitioning property of a
    node's OUTPUT given its children's. Each element is a tuple of column
    names; rows equal on that tuple are co-located. Used by the
    optimizer's exchange_planning (insert/elide decisions) and mirrored
    by the runtime rels."""
    from .expr import ColumnRef
    if isinstance(node, (Filter, Limit)):
        return child_parts[0]
    if isinstance(node, (Project, FusedSelect)):
        renames = {}
        for out_name, e in node.exprs:
            if isinstance(e, ColumnRef) and e.name not in renames:
                renames[e.name] = out_name
        out = set()
        for p in child_parts[0]:
            if all(c in renames for c in p):
                out.add(tuple(renames[c] for c in p))
        return frozenset(out)
    if isinstance(node, Exchange):
        if node.how == "hash":
            return frozenset({tuple(node.keys)})
        if node.how in ("broadcast", "gather"):
            return frozenset()
        return child_parts[0]
    if isinstance(node, HashJoin):
        lp = child_parts[0]
        broadcast = (isinstance(node.right, Exchange)
                     and node.right.how == "broadcast")
        if node.how != "inner":
            # semi/anti keep the left relation's shape; shuffled -> placed
            # by left keys; broadcast -> left rows never moved
            if broadcast:
                return lp
            return frozenset({tuple(node.left_keys)})
        if broadcast:
            return lp
        return frozenset({tuple(node.left_keys), tuple(node.right_keys)})
    if isinstance(node, HashAggregate):
        if not node.keys:
            return frozenset()
        # mirror the executor's two aggregate paths, each with its own
        # TRUE placement: with a satisfying child claim the exchange is
        # ELIDED (local merge — rows never move, so exactly the child's
        # subset claims survive); otherwise the fused two-phase program
        # re-places groups by the hash of the full key tuple. Claims
        # from the other path must not leak: a stale child claim after a
        # fused re-place (or a full-keys claim after an elided merge)
        # would let a downstream consumer elide a REQUIRED exchange.
        # (A static mis-prediction of the runtime path is still safe:
        # the executor checks elision against its own runtime claims and
        # repartitions implicitly when they don't hold.)
        keys = set(node.keys)
        sub = frozenset(p for p in child_parts[0] if set(p) <= keys)
        return sub if sub else frozenset({tuple(node.keys)})
    return frozenset()      # Sort/TopK (range), Union, Scan, unknown


def part_satisfies(part: frozenset, keys) -> bool:
    """Whether `part` already co-locates every group of `keys` — the
    groupby exchange-elision test (a partition tuple that is a SUBSET of
    the group keys suffices: equal group tuples imply equal subsets)."""
    keyset = set(keys)
    return any(set(p) <= keyset for p in part)


def join_alignment(lpart: frozenset, rpart: frozenset, lkeys, rkeys
                   ) -> Optional[Tuple[Tuple[str, ...], Tuple[str, ...]]]:
    """The (left tuple, right tuple) placement pair under which both join
    sides are already partitioned positionally alike (same permutation of
    the key pairing on both sides) — matching rows are then guaranteed
    co-located and the join needs no exchange. Returns the ACTUAL aligned
    tuples (which may be a permutation of the join-key order — the
    output's true placement claim), or None."""
    lk, rk = tuple(lkeys), tuple(rkeys)
    for lp in lpart:
        if len(lp) != len(lk) or set(lp) != set(lk):
            continue
        perm = tuple(lk.index(c) for c in lp)
        rp = tuple(rk[i] for i in perm)
        if rp in rpart:
            return lp, rp
    return None


def join_aligned(lpart: frozenset, rpart: frozenset, lkeys, rkeys) -> bool:
    return join_alignment(lpart, rpart, lkeys, rkeys) is not None


# ---- the distributed walk ---------------------------------------------------

class DistContext:
    """Per-execution distributed lowering state: the mesh, the jitted
    primitive handles, the fused-exchange set, and the caps memo shared
    with the executor."""

    def __init__(self, executor, plan, inputs):
        from .. import config
        self.ex = executor
        self.mesh = executor.mesh
        self.axis = executor.mesh_axis
        self.n_peers = self.mesh.shape[self.axis]
        self.plan = plan
        self.slack = config.dist_slack()
        # transport knobs (plan/transport.py), snapshotted per execution:
        # pack off restores the byte-identical legacy payload layout
        self.pack = config.exchange_pack()
        self.codecs = config.exchange_codecs() if self.pack else frozenset()
        self.async_on = config.exchange_async()
        self.spec = NamedSharding(self.mesh, P(self.axis))
        self.rep_spec = NamedSharding(self.mesh, P())
        # what one execution did, for `plan.execute` (docs/plan.md):
        # operators that ran over the mesh and through the local fallback
        # below a sharded input, edges and wire bytes moved, capacity
        # escalations
        self.dist_ops = self.local_ops = 0
        self.exchange_edges = self.exchange_bytes = 0
        self.cap_escalations = 0
        self._off_mesh: set = set()
        parents: Dict[int, List[PlanNode]] = {}
        for n in plan.nodes:
            for c in n.children:
                parents.setdefault(id(c), []).append(n)
        self.parents = parents
        self._node_index = {id(n): i for i, n in enumerate(plan.nodes)}
        # hash Exchanges whose only consumer is a HashAggregate FUSE into
        # the two-phase groupby program: the Exchange defers (identity) and
        # the aggregate attributes the exchange bytes back to it
        self.fused_exchanges = {
            id(n) for n in plan.nodes
            if isinstance(n, Exchange) and n.how == "hash"
            and len(parents.get(id(n), [])) == 1
            and isinstance(parents[id(n)][0], HashAggregate)
            and parents[id(n)][0].keys
        }

    # -- caps memo (fingerprint x node index x primitive, like the capped
    # tier's fingerprint-keyed memo) -----------------------------------------
    def _memo_key(self, node, tag: str):
        # `tag` separates the primitives one node may drive (an
        # aggregate's final merge escalates key_cap, a sort its slack —
        # their caps must not merge); joins and hash exchanges count
        # their capacities and escalate nothing
        return (self.plan.fingerprint, self._node_index[id(node)], tag)

    def _caps(self, node, tag: str, defaults: Dict) -> Dict:
        memo = self.ex._dist_caps_memo.get(self._memo_key(node, tag))
        caps = dict(defaults)
        for k, v in (memo or {}).items():
            if k in caps:
                caps[k] = max(caps[k], v)
        return caps

    def _retry(self, node, tag: str, run, caps: Dict, m):
        from ..parallel.autoretry import auto_retry_overflow
        attempts = [0]

        def attempt(**kw):
            attempts[0] += 1
            return run(**kw)

        out, final = auto_retry_overflow(attempt, caps,
                                         self.ex.max_cap_attempts)
        if m is not None:
            m.escalations += attempts[0] - 1
        self.cap_escalations += attempts[0] - 1
        self.ex._dist_caps_memo[self._memo_key(node, tag)] = \
            dict(final)
        return out

    # -- helpers -------------------------------------------------------------
    def lift(self, rel_or_table, part: frozenset = frozenset()):
        if isinstance(rel_or_table, ShardedRel):
            return rel_or_table
        return shard_table(self.mesh, self.axis, rel_or_table, part=part)

    def localize(self, rel_or_table) -> Table:
        rel_or_table = _resolve_rel(rel_or_table)
        if isinstance(rel_or_table, ShardedRel):
            return rel_or_table.to_local_table()
        return rel_or_table

    @staticmethod
    def _nbytes(table: Table) -> int:
        from ..runtime.admission import operand_nbytes
        return operand_nbytes(table)

    def _put(self, arr):
        return jax.device_put(arr, self.spec)

    def _default_cap(self, *padded_lens) -> int:
        """A shard's slots of the largest input: what a group-by can at
        most emit. The walk keeps its frames at the size of their live
        rows (`_settle`), so this is no loose bound."""
        return max(64, max(padded_lens, default=1) // self.n_peers)

    def _narrowed(self, specs, words_by_side, alive_by_side):
        """Key words of one or two sides as 32-bit offsets where every
        word's live range allows (`parallel/relational.narrow_keys`), and
        the offsets to widen by; (the sides as they were, None) where a
        key is nullable (its null word is read as such), wide, or a side
        holds no live row. One pass over the keys, 2 numbers a word read
        back."""
        from ..parallel.relational import key_ranges, narrow_keys
        if any(sp.nullable or sp.n_words != 1 for sp in specs):
            return words_by_side, None
        shape = tuple(len(ws) for ws in words_by_side)
        ranges = _jitted(("key_ranges", self.mesh, self.axis, shape),
                         key_ranges)(words_by_side, alive_by_side)
        with span("ops.host_sync", site="dist.key_ranges"):
            ranges = np.asarray(ranges)
        if any(int(hi) < int(lo) or int(hi) - int(lo) >= (1 << 31) - 1
               for lo, hi in ranges):
            return words_by_side, None
        lo = jnp.asarray(ranges[:, 0])
        narrowed = _jitted(
            ("narrow_keys", self.mesh, self.axis, shape),
            narrow_keys)(words_by_side, alive_by_side, lo)
        return narrowed, lo

    def _exchange(self):
        """The span of one movement of data between chips (or to the host,
        at the sink): opened inside the `plan.op` of the operator that
        moves it, closed when the data has arrived; `_edge` says what
        moved."""
        return span("plan.exchange", peers=self.n_peers)

    # -- node dispatch -------------------------------------------------------
    def exec_node(self, node, childs, inputs, schemas, m, metrics):
        """Execute one node: distributed when it has a form and its
        children allow it, local otherwise (gathering sharded children —
        the graceful boundary). Returns a ShardedRel, a PendingRel (async
        exchange in flight), or a Table. In-flight child exchanges
        resolve HERE — the consumer boundary is where the async overlap
        window closes."""
        childs = [_resolve_rel(c) for c in childs]
        out = self._try_dist(node, childs, inputs, schemas, m, metrics)
        if out is None:
            local = [self.localize(c) for c in childs]
            out = self.ex._exec_eager_node(node, local, inputs, schemas, m)
        if isinstance(out, (ShardedRel, PendingRel)):
            if isinstance(out, ShardedRel) and \
                    isinstance(node, (Filter, FusedSelect, HashJoin)):
                out = self._settle(out)
            m.sharding = out.sharding_str(self.n_peers)
            m.n_peers = self.n_peers
            self.dist_ops += 1
        else:
            below_mesh = any(isinstance(c, ShardedRel) for c in childs)
            if below_mesh:
                m.sharding = "local"
            if below_mesh or any(id(c) in self._off_mesh
                                 for c in node.children):
                # an operator that left the mesh, and all above it; the
                # gather itself is the boundary, not a fallback
                self._off_mesh.add(id(node))
                if not (isinstance(node, Exchange) and node.how == "gather"):
                    self.local_ops += 1
        return out

    # -- frames at the size of their live rows -------------------------------
    def _live_counts(self, valid) -> np.ndarray:
        """(n_peers,) live rows of each shard of a row-sharded mask: the
        one number a shard the walk reads back to size its next program."""
        from ..parallel.relational import distributed_live_counts
        mesh, axis = self.mesh, self.axis
        counts = _jitted(
            ("live_counts", mesh, axis),
                lambda v: distributed_live_counts(mesh, v, axis))(valid)
        with span("ops.host_sync", site="dist.live_counts"):
            return np.asarray(counts)

    def _settle(self, rel: ShardedRel) -> ShardedRel:
        """After an operator that drops rows (a filter, a join): read each
        shard's live count (which the metric loop would read anyway) and,
        where a quarter of the frame or more is dead, pack every shard's
        live rows into `bucket(fullest shard)` slots. Everything above then
        runs at that length: a date window keeps under 1% of a fact
        table's rows, and a sort or a gather over the other 99% is the
        cost the capped tier shed in PR 31. A replicated relation is
        packed whatever its size: it is a build side, every probe row
        meets every one of its slots, and the 15 days of a calendar
        filtered on four shards arrive as 60 slots (all 15 on one shard,
        so each shard keeps 15 slots); at 60 the date joins of 39.6 M and
        19.8 M probe rows a chip passed `_LOOKUP_WORK` and ran as sort
        joins, 1.08 s and 0.50 s a request (PERF.md, PR 32)."""
        from ..parallel.relational import distributed_compact
        mesh, axis = self.mesh, self.axis
        if rel.replicated:
            slots = rel.padded_rows
            need = rel.num_rows
        else:
            slots = rel.padded_rows // self.n_peers
            counts = self._live_counts(rel.valid)
            rel._num_rows = int(counts.sum())
            need = int(counts.max())
        cap = bucket(need)
        if 4 * cap > 3 * slots:
            return rel
        if slots < _COMPACT_MIN_SLOTS and not rel.replicated:
            return rel
        arrays, layout = _pack_cols(rel.table, list(rel.table.names))
        replicated = rel.replicated
        fn = _jitted(("compact", mesh, axis, len(arrays), cap, replicated),
                     lambda *xs: distributed_compact(
                         mesh, xs[:-1], xs[-1], cap, axis, replicated))
        outs, valid, lost = fn(*arrays, rel.valid)
        _raise_if_lost(lost, "compaction", cap)
        out = ShardedRel(Table(_unpack_cols(list(outs), layout),
                               names=list(rel.table.names)), valid,
                         part=rel.part, replicated=replicated,
                         order_keys=rel.order_keys)
        out._num_rows = rel._num_rows
        return out

    def _try_dist(self, node, childs, inputs, schemas, m, metrics):
        try:
            if isinstance(node, Scan):
                return self._dist_scan(node, inputs, m)
            if isinstance(node, Filter):
                return self._dist_filter(node, childs)
            if isinstance(node, (Project, FusedSelect)):
                return self._dist_project(node, childs)
            if isinstance(node, Exchange):
                return self._dist_exchange(node, childs, m)
            if isinstance(node, HashJoin):
                return self._dist_join(node, childs, m, metrics)
            if isinstance(node, HashAggregate):
                return self._dist_aggregate(node, childs, schemas, m,
                                            metrics)
            if isinstance(node, (Sort, TopK)):
                return self._dist_sort(node, childs, m)
            if isinstance(node, Union):
                return self._dist_union(node, childs)
        except NotImplementedError:
            return None
        return None        # Limit & anything else: no distributed form

    # -- scans ---------------------------------------------------------------
    def _dist_scan(self, node, inputs, m):
        t = inputs[node.source]
        if not isinstance(t, Table):
            # streaming source: one pruned+projected materialized read,
            # then shard — the distributed tier's morsel is the shard
            t = self.ex._materialize_scan(node, t, m)
        elif node.projection is not None:
            t = t.select(list(node.projection))
        if t.num_rows == 0 or not table_shardable(t) or node.types:
            # a scan that declares logical types (decimals over int64
            # buffers) stays local, like a decimal128 column: the typed
            # decimal path is not lowered over shards
            return None
        return self.lift(t)

    # -- row-wise ------------------------------------------------------------
    def _dist_filter(self, node, childs):
        (c,) = childs
        if not isinstance(c, ShardedRel) or c.replicated:
            return None
        mask = self._eval(node.predicate, c.table, c.valid, truth=True)
        return ShardedRel(c.table, c.valid & mask, part=c.part,
                          order_keys=c.order_keys)

    def _eval(self, e, table: Table, valid, truth: bool = False):
        """An expression over a sharded relation, as ONE program whose
        result is born row-sharded: (data, validity or None), or with
        `truth` the rows where a predicate is TRUE (a null drops the row).
        Evaluated eagerly a literal is an
        array of the relation's whole length on the first device (1.27 GB
        for a zero column of the store channel), and the next operator
        reshards it."""
        from .optimizer import _fp_expr
        names = list(table.names)
        arrays, layout = _pack_cols(table, names)
        n, spec = table.num_rows, self.spec

        def run(valid, *arrays):      # closes over no buffer: it is cached
            t = Table(_unpack_cols(list(arrays), layout), names=names)
            if truth:
                return e.truth(t, valid)
            c = e.column(t, valid)
            return c.data, c.validity

        key = ("expr", truth, self.mesh, self.axis, _fp_expr(e), n,
               tuple((nm, repr(dt), has_v) for nm, dt, has_v in layout))
        return _jitted(key, run, out_shardings=spec)(
            valid, *arrays)

    def _dist_project(self, node, childs):
        from .expr import ColumnRef, decimal_type, untyped_column
        (c,) = childs
        if not isinstance(c, ShardedRel) or c.replicated:
            return None
        if any(decimal_type(e, lambda n: c.table[n].dtype) is not None
               for _, e in node.exprs if not isinstance(e, ColumnRef)):
            return None     # typed decimal results are not lowered over
            #                 shards: the gather boundary, as decimal128
        valid = c.valid
        if isinstance(node, FusedSelect):
            mask = self._eval(node.predicate, c.table, valid, truth=True)
            valid = valid & mask
        cols = []
        for name, e in node.exprs:
            if isinstance(e, ColumnRef):
                cols.append(c.table[e.name])
            else:
                data, validity = self._eval(e, c.table, valid)
                cols.append(untyped_column(data, validity))
        part = transfer_part(node, [c.part])
        order = None
        if c.order_keys:
            renames = {e.name: nm for nm, e in node.exprs
                       if isinstance(e, ColumnRef)}
            if all(k in renames for k in c.order_keys):
                order = [renames[k] for k in c.order_keys]
        return ShardedRel(Table(cols, names=[n for n, _ in node.exprs]),
                          valid, part=part, order_keys=order)

    # -- exchanges -----------------------------------------------------------
    def _dist_exchange(self, node, childs, m):
        (c,) = childs
        if not isinstance(c, ShardedRel):
            if node.how == "broadcast" and isinstance(c, Table) and \
                    table_shardable(c) and c.num_rows:
                # a locally-computed small build side can still feed a
                # distributed broadcast join: replicate it directly
                if self.async_on:
                    return PendingRel(
                        lambda: self._replicate_local(c, m), m,
                        self._nbytes, replicated=True)
                return self._replicate_local(c, m)
            return None       # single-chip semantics: Exchange is a no-op
        if node.how == "identity":
            return c
        if node.how == "gather":
            return self._gather(c, m)
        if node.how == "broadcast":
            if c.replicated:
                return c
            if self.async_on:
                return PendingRel(lambda: self._broadcast(c, m), m,
                                  self._nbytes, replicated=True)
            return self._broadcast(c, m)
        if id(node) in self.fused_exchanges:
            return c          # defers into the aggregate above (fusion)
        if self.async_on:
            # the has-a-distributed-form checks must fail HERE,
            # synchronously: a NotImplementedError raised on the worker
            # thread would surface at the consumer, outside _try_dist's
            # graceful local-fallback net
            if _key_specs(c.table, list(node.keys)) is None or \
                    not table_shardable(c.table):
                raise NotImplementedError
            return PendingRel(lambda: self._repartition(node, c, m), m,
                              self._nbytes,
                              part=frozenset({tuple(node.keys)}))
        return self._repartition(node, c, m)

    def _edge(self, m, how: str, logical: int, wire: int, codec: str,
              copies: int = 1, sp=None):
        """Stamp one exchange edge's movement on a metric row: logical =
        unpacked per-column payload, wire = packed bytes actually shipped
        (== logical with packing off). Live payload only, each edge
        counted once; broadcast passes copies = n_peers-1. `sp`, the
        edge's `plan.exchange` span, takes the same numbers, and the
        execution's totals grow by them."""
        m.exchange_how = how
        m.exchange_bytes_logical += logical * copies
        m.exchange_bytes += wire * copies
        if codec:
            m.exchange_codecs = (m.exchange_codecs + ";" + codec
                                 if m.exchange_codecs else codec)
        self.exchange_edges += 1
        self.exchange_bytes += wire * copies
        if sp is not None:
            sp.set_metadata(how=how, bytes=int(wire * copies),
                            bytes_logical=int(logical * copies),
                            codec=_span_text(codec).replace(",", "+")
                            .replace("=", ":") or "raw")

    @staticmethod
    def _reset_edge(m):
        """A retried (or re-run) exchange attempt RE-DESCRIBES its edge:
        the metric must show the execution that produced the output, not
        a sum over failed attempts."""
        m.exchange_bytes = 0
        m.exchange_bytes_logical = 0
        m.exchange_codecs = ""

    def _gather(self, c: ShardedRel, m) -> Table:
        """The sink/boundary collect. Packed: static wire planes compute
        on the mesh, ONE narrow pull per plane crosses to host, and the
        receiving side decodes + compacts (plan/transport.py); the result
        caches on the rel like to_local_table so DAG-shared consumers
        gather once — a cache-served gather moves NOTHING and reports
        zero bytes (the first crossing carried the payload)."""
        self._reset_edge(m)
        if c._local is not None:
            m.exchange_how = "gather"
            return c._local
        live = c.num_rows
        cols = list(c.table.columns)
        logical = live * transport.logical_row_bytes(cols)
        with self._exchange() as sp:
            if self.pack:
                t, wire_row, codec = self._gather_packed(c)
                self._edge(m, "gather", logical, live * wire_row, codec,
                           sp=sp)
            else:
                t = c.to_local_table()
                self._edge(m, "gather", logical, logical, "", sp=sp)
        return t

    def _gather_packed(self, c: ShardedRel):
        names = list(c.table.names)
        dp = transport.pack_device(list(c.table.columns), names, c.valid,
                                   self.codecs)
        mask_plane, n = transport.pack_bits_device(c.valid)
        with span("ops.host_sync", site="dist.gather"):
            planes = [np.asarray(p) for p in dp.planes]
            mask_plane = np.asarray(mask_plane)
        mask = transport.unpack_bits_np(mask_plane, n)
        idx = np.nonzero(mask)[0]
        decoded = transport.unpack_device_np(planes, dp)
        cols = []
        for src, (data, validity) in zip(c.table.columns, decoded):
            v = None if validity is None else jnp.asarray(validity[idx])
            cols.append(dataclasses.replace(
                src, data=jnp.asarray(data[idx]), validity=v,
                length=int(idx.shape[0])))
        t = Table(cols, names=names)
        if c.order_keys:
            from .executor import _ops
            t = _ops().sort_table(t, key_names=list(c.order_keys),
                                  ascending=[True] * len(c.order_keys))
        c._local = t
        return t, dp.wire_row_bytes, dp.codec_str

    def _replicate_local(self, t: Table, m) -> ShardedRel:
        self._reset_edge(m)
        live = t.num_rows
        logical = live * transport.logical_row_bytes(t.columns)
        copies = self.n_peers - 1
        rep = self.rep_spec

        def put(a):
            return jax.device_put(a, rep)

        with self._exchange() as sp:
            if self.pack:
                # host-materialized payload: the dynamic-size codecs
                # (dict/rle) apply here, and the decode runs on the lifted
                # (replicated) planes — unpack on the receiving shard
                hp = transport.pack_host(list(t.columns), list(t.names),
                                         self.codecs)
                cols = transport.unpack_host_device(hp, put)
                self._edge(m, "broadcast", logical, hp.wire_bytes,
                           hp.codec_str, copies=copies, sp=sp)
            else:
                cols = []
                for c in t.columns:
                    validity = c.validity
                    if validity is not None:
                        validity = put(validity)
                    cols.append(dataclasses.replace(c, data=put(c.data),
                                                    validity=validity))
                self._edge(m, "broadcast", logical, logical, "",
                           copies=copies, sp=sp)
            valid = put(jnp.ones((t.num_rows,), bool))
            with span("plan.wait", site="dist.replicate"):
                jax.block_until_ready(valid)
        return ShardedRel(Table(cols, names=list(t.names)), valid,
                          replicated=True)

    def _broadcast(self, c: ShardedRel, m) -> ShardedRel:
        if c.replicated:
            return c
        self._reset_edge(m)
        names = list(c.table.names)
        cols = list(c.table.columns)
        live = c.num_rows
        copies = self.n_peers - 1
        logical = live * transport.logical_row_bytes(cols)
        dp = layout = None
        if self.pack:
            dp = transport.pack_device(cols, names, c.valid, self.codecs)
            arrays = dp.planes
            wire = live * dp.wire_row_bytes
            codec = dp.codec_str
        else:
            arrays, layout = _pack_cols(c.table, names)
            wire, codec = logical, ""
        key = ("broadcast", self.mesh, self.axis, len(arrays) + 1)

        fn = _jitted(key,
            lambda *xs: xs, out_shardings=self.rep_spec)
        with self._exchange() as sp:
            outs = fn(*arrays, c.valid)
            with span("plan.wait", site="dist.broadcast"):
                jax.block_until_ready(outs)
            self._edge(m, "broadcast", logical, wire, codec, copies=copies,
                       sp=sp)
        if dp is not None:
            out_cols = transport.unpack_device(outs[:-1], dp)
        else:
            out_cols = _unpack_cols(outs[:-1], layout)
        out = ShardedRel(Table(out_cols, names=names),
                         outs[-1].astype(jnp.bool_), replicated=True)
        out._num_rows = live
        # a filtered dimension arrives as a frame of mostly dead slots
        return self._settle(out)

    def _repartition(self, node, c: ShardedRel, m) -> ShardedRel:
        self._reset_edge(m)
        return self._repartition_rel(node, c, list(node.keys), m)

    def _repartition_rel(self, node, c: ShardedRel, keys, m) -> ShardedRel:
        """Hash-exchange a sharded relation by `keys` and stamp the edge
        on `m` and on its `plan.exchange` span. Key columns ride their
        64-bit order-preserving word encoding — logically 8 B x
        total_words each; with packing on the shipped planes FOR-narrow
        (transport.narrow_words) and the collective body widens them back
        for the Spark-exact hash, so placement stays bit-identical while
        the wire shrinks. Value columns ship packed. The buckets are
        counted first (one pass of compares, 16 numbers read back) and
        shipped at the size of the fullest: no slack is guessed and none
        escalates into a second program. The exchange still says whether
        a bucket spilled, and the walk raises if one did: the count and
        the exchange are two programs that must hash alike."""
        from ..parallel.relational import (distributed_partition_counts,
                                           distributed_repartition_keyed)
        specs = _key_specs(c.table, keys)
        if specs is None or not table_shardable(c.table):
            raise NotImplementedError
        words = _encode_keys(c.table, keys, specs)
        mesh, axis = self.mesh, self.axis
        n_words = len(words)
        counts = _jitted(
            ("part_counts", mesh, axis, tuple(specs), n_words),
            lambda *xs: distributed_partition_counts(
                mesh, xs[:-1], specs, xs[-1], axis))(*words, c.valid)
        with span("ops.host_sync", site="dist.part_counts"):
            counts = np.asarray(counts)
        cap = bucket(int(counts.max()))
        c._num_rows = int(counts.sum())
        vnames = [nm for nm in c.table.names if nm not in set(keys)]
        val_cols = [c.table[nm] for nm in vnames]
        live = c.num_rows
        key_word_bytes = 8 * sum(sp.total_words for sp in specs)
        logical_row = key_word_bytes + transport.logical_row_bytes(val_cols)
        dp = layout = wplans = None
        word_codecs, refs = (), []
        if self.pack:
            dp = transport.pack_device(val_cols, vnames, c.valid,
                                       self.codecs)
            vals = dp.planes
            codec = dp.codec_str
            key_wire_bytes = key_word_bytes
            if "for" in self.codecs:
                words, wplans, key_wire_bytes, knote = \
                    transport.narrow_words(words, c.valid)
                if knote:
                    codec = ",".join(x for x in (codec, knote) if x)
                word_codecs = tuple(p.codec for p in wplans)
                # references ride as traced (1,) arrays so the compiled
                # program is reusable across executions (and the jit
                # cache keys on the static codec layout, not the data)
                refs = [jnp.full((1,), p.ref, jnp.int64)
                        for p in wplans if p.codec != "raw"]
            wire_row = key_wire_bytes + dp.wire_row_bytes
        else:
            vals, layout = _pack_cols(c.table, vnames)
            wire_row, codec = logical_row, ""

        nw, nv = len(words), len(vals)
        # the cached jitted callables must close over LOCALS only: a
        # `self` capture would pin the executor (and its plan/LRU graph)
        # in the process-global cache long after the session ends
        key = ("repart", mesh, axis, tuple(specs), nw, nv, cap, word_codecs)
        fn = _jitted(key,
            lambda *arrs: distributed_repartition_keyed(
                mesh, list(arrs[:nw]), specs,
                list(arrs[nw:nw + nv]), cap, axis=axis,
                alive=arrs[nw + nv],
                word_codecs=word_codecs or None,
                word_refs=list(arrs[nw + nv + 1:]) or None))
        with self._exchange() as sp:
            ws, vs, alive, lost = fn(*words, *vals, c.valid, *refs)
            with span("plan.wait", site="dist.repartition"):
                jax.block_until_ready((ws, vs, alive, lost))
            _raise_if_lost(lost, "hash exchange", cap)
            self._edge(m, "hash", live * logical_row, live * wire_row,
                       codec, sp=sp)
        alive = alive.astype(jnp.bool_)
        if wplans is not None:
            ws = transport.widen_words(list(ws), wplans)
        cols = dict(_decode_keys(ws, specs, keys, alive))
        if dp is not None:
            unpacked = transport.unpack_device(list(vs), dp)
        else:
            unpacked = _unpack_cols(vs, layout)
        cols.update({nm: col for nm, col in zip(vnames, unpacked)})
        table = Table([cols[nm] for nm in c.table.names],
                      names=list(c.table.names))
        out = ShardedRel(table, alive, part=frozenset({tuple(keys)}))
        out._num_rows = live
        return out

    # -- joins ---------------------------------------------------------------
    def _dist_join(self, node, childs, m, metrics):
        from ..parallel.relational import distributed_colocated_join_keyed
        if node.how not in ("inner", "left_semi", "left_anti"):
            return None
        l, r = childs
        # lift a local side when the other is on the mesh (a broadcast
        # Exchange above a local child already replicated it)
        if not isinstance(l, ShardedRel) and not isinstance(r, ShardedRel):
            return None
        if not isinstance(l, ShardedRel):
            if not (isinstance(l, Table) and table_shardable(l)
                    and l.num_rows):
                return None
            l = self.lift(l)
        if not isinstance(r, ShardedRel):
            if not (isinstance(r, Table) and table_shardable(r)
                    and r.num_rows):
                return None
            r = self.lift(r)
        if l.replicated:
            return None     # probe side must be partitioned, not replicated
        if not (table_shardable(l.table) and table_shardable(r.table)):
            return None
        specs = _key_specs(l.table, node.left_keys, r.table, node.right_keys)
        if specs is None:
            return None

        lk, rk = list(node.left_keys), list(node.right_keys)
        inner = node.how == "inner"
        l_moved = False
        if r.replicated and r.padded_rows <= _LOOKUP_SLOTS and \
                l.padded_rows // self.n_peers * r.padded_rows <= _LOOKUP_WORK:
            out = self._lookup_join(node, l, r, lk, rk, specs)
            if out is not None:
                return out
        # align the sides: already-aligned parts (explicit exchanges ran,
        # or upstream operators preserved a suitable partitioning) join
        # co-located; a replicated right side probes locally; anything
        # else repartitions implicitly here (bytes on this node's metric)
        if not r.replicated and \
                not join_aligned(l.part, r.part, lk, rk):
            # a fault-retried attempt re-describes its implicit edges
            self._reset_edge(m)
            if tuple(lk) not in l.part:
                l = self._repartition_rel(node, l, lk, m)
                l_moved = True
            if tuple(rk) not in r.part:
                r = self._repartition_rel(node, r, rk, m)
        # the output's placement claim must name the tuples the rows are
        # ACTUALLY placed by — the aligned permutation, not the join-key
        # order (hash(b,a) placement claimed as (a,b) would let a
        # downstream consumer elide a required exchange)
        aligned = (None if r.replicated
                   else join_alignment(l.part, r.part, lk, rk))
        out_names = list(l.table.names) + \
            (list(r.table.names) if inner else [])
        if inner and not r.replicated and r.padded_rows < l.padded_rows:
            # two partitioned sides of an inner join: the shorter one
            # probes. The optimizer's build_side rule puts the SMALLER
            # side on the right, as a hash join wants it; this sort-merge
            # join sorts both sides whichever probes, and sizes its output
            # frame, its expansion and every column gather by the probe
            # side's slots
            l, r, lk, rk = r, l, rk, lk
            if aligned is not None:
                aligned = (aligned[1], aligned[0])

        l_words = _encode_keys(l.table, lk, specs)
        r_words = _encode_keys(r.table, rk, specs)
        (l_words, r_words), key_lo = self._narrowed(
            specs, [l_words, r_words], [l.valid, r.valid])
        lvnames = [nm for nm in l.table.names if nm not in set(lk)]
        lvals, l_layout = _pack_cols(l.table, lvnames)
        if inner:
            rvnames = [nm for nm in r.table.names if nm not in set(rk)]
            rvals, r_layout = _pack_cols(r.table, rvnames)
        else:
            rvnames, rvals, r_layout = [], [], []

        nlw, nlv, nrv = len(l_words), len(lvals), len(rvals)

        rrep = r.replicated
        mesh, axis, how = self.mesh, self.axis, node.how  # no self capture

        if inner:
            # spans first, then the emission at the size the spans give:
            # the walk READS each shard's output count between the two
            # programs, so the frame is the join's rows (no guess for a
            # fan-out to overflow into another compile, no probe side's
            # worth of slots for a join that keeps a tenth of it)
            from ..parallel.relational import (
                distributed_colocated_join_emit,
                distributed_colocated_join_spans)
            spans = _jitted(
                ("cojoin_spans", mesh, axis, tuple(specs), nlw, rrep),
                lambda *xs: distributed_colocated_join_spans(
                    mesh, xs[:nlw], xs[nlw:2 * nlw], specs, axis,
                    xs[-2], xs[-1], rrep))
            counts, lo, rorder, totals = spans(*l_words, *r_words,
                                               l.valid, r.valid)
            with span("ops.host_sync", site="dist.join_totals"):
                totals = np.asarray(totals)
            row_cap = bucket(int(totals.max()))
            emit = _jitted(
                ("cojoin_emit", mesh, axis, nlw, nlv, nrv, rrep, row_cap),
                lambda *xs: distributed_colocated_join_emit(
                    mesh, xs[:nlw], xs[nlw:nlw + nlv],
                    xs[nlw + nlv:nlw + nlv + nrv], *xs[-3:], row_cap, axis,
                    rrep))
            ws, lvs, rvs, live = emit(*l_words, *lvals, *rvals,
                                      counts, lo, rorder)
            n_out = int(totals.sum())
        else:
            key = ("cojoin", mesh, axis, tuple(specs), how, nlw, nlv, rrep)
            fn = _jitted(key,
                lambda *arrs: distributed_colocated_join_keyed(
                    mesh, list(arrs[:nlw]), list(arrs[nlw:nlw + nlv]),
                    list(arrs[nlw + nlv:2 * nlw + nlv]), specs,
                    axis=axis, how=how, lalive=arrs[-2], ralive=arrs[-1],
                    r_replicated=rrep))
            ws, lvs, live = fn(*l_words, *lvals, *r_words,
                               l.valid, r.valid)
            n_out = None
        live = live.astype(jnp.bool_)
        if key_lo is not None:
            from ..parallel.relational import widen_keys
            ws = widen_keys(list(ws), key_lo)
        cols = dict(_decode_keys(ws, specs, lk, live))
        cols.update({nm: col for nm, col
                     in zip(lvnames, _unpack_cols(lvs, l_layout))})
        if inner:
            # right key columns equal the left keys on every matched row
            for nm, lkey in zip(rk, lk):
                rc = r.table[nm]
                cols[nm] = dataclasses.replace(
                    cols[lkey], dtype=rc.dtype,
                    data=cols[lkey].data.astype(rc.dtype.storage_dtype()))
            cols.update({nm: col for nm, col
                         in zip(rvnames, _unpack_cols(rvs, r_layout))})
        if r.replicated:
            part = l.part              # probe side never moved
        elif aligned is None:
            part = frozenset()         # defensive: repartition guarantees
            #                            an identity-permutation alignment
        elif inner:
            part = frozenset(aligned)
        else:
            part = frozenset({aligned[0]})   # left columns only survive
        # a broadcast semi/anti never moves the left rows, so the left
        # relation's gather-order contract survives; everything else
        # (inner emission, shuffled placement) re-orders
        order = l.order_keys if (not inner and r.replicated
                                 and not l_moved) else None
        out = ShardedRel(Table([cols[nm] for nm in out_names],
                               names=out_names),
                         live, part=part, order_keys=order)
        out._num_rows = n_out
        return out

    def _lookup_join(self, node, l, r, lk, rk, specs):
        """A replicated build side of a handful of rows with distinct live
        keys (what is left of a dimension under a narrow filter): the
        probe side keeps its frame and its buffers, every row is compared
        with each build row, and the rows without a partner go dead
        (`_settle` then packs the rest). None where two build rows share
        a key: that join fans out, and takes the general path."""
        from ..parallel.relational import distributed_lookup_join
        r_words = _encode_keys(r.table, rk, specs)
        with span("ops.host_sync", site="dist.lookup_keys"):
            live = np.asarray(r.valid)
            keys = np.stack([np.asarray(w) for w in r_words], axis=1)[live]
        if len(np.unique(keys, axis=0)) != len(keys):
            return None
        inner = node.how == "inner"
        l_words = _encode_keys(l.table, lk, specs)
        rvnames = [nm for nm in r.table.names if nm not in set(rk)] \
            if inner else []
        rvals, r_layout = _pack_cols(r.table, rvnames)
        nw, nrv = len(l_words), len(rvals)
        mesh, axis = self.mesh, self.axis
        fn = _jitted(("lookup", mesh, axis, tuple(specs), nw, nrv),
                     lambda *xs: distributed_lookup_join(
                         mesh, xs[:nw], xs[nw:2 * nw],
                         xs[2 * nw:2 * nw + nrv], specs, xs[-2], xs[-1],
                         axis))
        outs, matched = fn(*l_words, *r_words, *rvals, l.valid, r.valid)
        cols = {nm: l.table[nm] for nm in l.table.names}
        names = list(l.table.names)
        if inner:
            for nm, lkey in zip(rk, lk):
                rc = r.table[nm]
                cols[nm] = dataclasses.replace(
                    cols[lkey], dtype=rc.dtype, validity=None,
                    data=cols[lkey].data.astype(rc.dtype.storage_dtype()))
            cols.update(zip(rvnames, _unpack_cols(list(outs), r_layout)))
            names = names + list(r.table.names)
        valid = (l.valid & ~matched) if node.how == "left_anti" else matched
        return ShardedRel(Table([cols[nm] for nm in names], names=names),
                          valid, part=l.part,
                          order_keys=None if inner else l.order_keys)

    # -- aggregates ----------------------------------------------------------
    def _dist_aggregate(self, node, childs, schemas, m, metrics):
        from ..parallel.relational import (distributed_groupby_keyed,
                                           distributed_local_groupby)
        (c,) = childs
        fused_child = (isinstance(node.child, Exchange)
                       and id(node.child) in self.fused_exchanges)
        if not isinstance(c, ShardedRel) or c.replicated:
            return None
        if any(o not in _DIST_AGGS for _, o, _ in node.aggs):
            return None
        if not node.keys and c.num_rows == 0:
            return None       # SQL's aggregates of no rows: the local tier
        specs = _key_specs(c.table, node.keys)
        if specs is None:
            return None
        val_names, agg_pairs = [], []
        for cn, o, _ in node.aggs:
            if o == "size":
                agg_pairs.append((0, "count"))
                continue
            col = c.table[cn]
            if col.validity is not None or not (col.dtype.is_integer or
                                                col.dtype.kind ==
                                                dtypes.Kind.BOOL):
                return None   # exact int64 accumulation only
            if cn not in val_names:
                val_names.append(cn)
            agg_pairs.append((val_names.index(cn),
                              "count" if o == "count" else o))
        vals = [c.table[v].data for v in val_names]
        if not node.keys:
            return self._reduce(node, c, vals, agg_pairs, schemas, m)
        words = _encode_keys(c.table, list(node.keys), specs)
        key_cap0 = node.key_cap or self.ex.caps.get("key_cap") or \
            self._default_cap(c.padded_rows)
        elide = (not fused_child) and part_satisfies(c.part, node.keys)
        nbytes = [0]
        live_in = c.num_rows

        nw, nv = len(words), len(vals)
        mesh, axis, n_peers = self.mesh, self.axis, self.n_peers
        valid_in = c.valid
        slots = c.padded_rows // n_peers
        groups_in = None
        if not elide and slots >= _COMPACT_MIN_SLOTS and key_cap0 == slots:
            # a large frame: merge each shard's rows first, READ how many
            # groups the fullest shard holds, and run the exchange and
            # the final merge at that size. One program at key_cap =
            # the frame would ship and sort n_peers frames for what may
            # be a few hundred groups.
            from ..parallel.relational import distributed_head
            pairs1 = tuple(agg_pairs)
            fn1 = _jitted(("lgroup", mesh, axis, tuple(specs), nw, nv,
                           pairs1, slots),
                lambda *arrs: distributed_local_groupby(
                    mesh, list(arrs[:nw]), list(arrs[nw:-1]), list(pairs1),
                    key_cap=slots, axis=axis, alive=arrs[-1]))
            (words1,), key_lo = self._narrowed(specs, [words], [valid_in])
            gws1, outs1, gvalid1, _ = fn1(*words1, *vals, valid_in)
            if key_lo is not None:
                from ..parallel.relational import widen_keys
                gws1 = widen_keys(list(gws1), key_lo)
            counts = self._live_counts(gvalid1)
            key_cap0 = bucket(int(counts.max()))
            groups_in = int(counts.sum())
            k = nw + len(pairs1) + 1
            heads = _jitted(("head", mesh, axis, k, key_cap0),
                            lambda *xs: distributed_head(
                                mesh, xs, key_cap0, axis))(
                *gws1, *outs1, gvalid1)
            words, vals = list(heads[:nw]), list(heads[nw:-1])
            valid_in = heads[-1]
            agg_pairs = [(j, "sum" if a in ("sum", "count") else a)
                         for j, (_, a) in enumerate(pairs1)]
            nv = len(vals)

        def run(key_cap):
            if elide:
                key = ("lgroup", mesh, axis, tuple(specs),
                       nw, nv, tuple(agg_pairs), key_cap)
                fn = _jitted(key,
                    lambda *arrs: distributed_local_groupby(
                        mesh, list(arrs[:nw]),
                        list(arrs[nw:-1]), list(agg_pairs),
                        key_cap=key_cap, axis=axis, alive=arrs[-1]))
            else:
                key = ("group", mesh, axis, tuple(specs),
                       nw, nv, tuple(agg_pairs), key_cap)
                fn = _jitted(key,
                    lambda *arrs: distributed_groupby_keyed(
                        mesh, list(arrs[:nw]), specs,
                        list(arrs[nw:-1]), list(agg_pairs),
                        key_cap=key_cap, axis=axis, alive=arrs[-1]))
                # the all-to-all ships per-group PARTIALS, not rows: one
                # int64 per key word and per agg partial, for at most
                # min(live input rows, key_cap per shard) groups — the
                # payload, counted once (bucket padding/slack excluded,
                # like every other edge)
                nbytes[0] = (8 * (nw + len(agg_pairs))
                             * (min(live_in, n_peers * key_cap)
                                if groups_in is None else groups_in))
            return fn(*words, *vals, valid_in)

        with (contextlib.nullcontext() if elide else self._exchange()) as sp:
            gws, outs, gvalid, _ = self._retry(
                node, "group", run,
                self._caps(node, "group", {"key_cap": key_cap0}), m)
            with span("plan.wait", site="dist.groupby"):
                jax.block_until_ready((gws, outs, gvalid))
            if not elide:
                sp.set_metadata(how="hash", bytes=nbytes[0],
                                bytes_logical=nbytes[0], codec="raw")
        gvalid = gvalid.astype(jnp.bool_)
        if not elide:
            # the fused program's all-to-all ships per-group partials; the
            # bytes belong to the exchange BOUNDARY — the child Exchange
            # node when the optimizer placed one, this node otherwise.
            # Partials are 64-bit exact accumulators: no packing applies,
            # wire == logical on this edge
            tgt = m
            if fused_child and node.child.label in metrics:
                tgt = metrics[node.child.label]
            # re-describe on a fault-retried aggregate attempt (the
            # fused Exchange's own execution deferred, so the child row
            # carries only this attribution)
            self._reset_edge(tgt)
            tgt.exchange_how = "hash"
            tgt.exchange_bytes = nbytes[0]
            tgt.exchange_bytes_logical = nbytes[0]
            self.exchange_edges += 1
            self.exchange_bytes += nbytes[0]
        from ..ops.aggregate import _agg_value_dtype
        cols = dict(_decode_keys(gws, specs, list(node.keys), gvalid))
        for (i, op), arr, (cn, o, out_name) in zip(agg_pairs, outs,
                                                   node.aggs):
            dt = _agg_value_dtype(o, c.table[cn].dtype
                                  if o != "size" else dtypes.INT64)
            cols[out_name] = Column(dtype=dt, length=int(arr.shape[0]),
                                    data=arr.astype(dt.storage_dtype()))
        names = schemas[id(node)]
        # truthful placement per the path that RAN: the elided local
        # merge left rows at the child's satisfying subset claims; the
        # fused two-phase program re-placed groups by the hash of the
        # full key tuple (so any child claim — including one riding
        # through a deferred fused Exchange — is stale here)
        if elide:
            keyset = set(node.keys)
            part = frozenset(p for p in c.part if set(p) <= keyset)
        else:
            part = frozenset({tuple(node.keys)})
        return ShardedRel(Table([cols[nm] for nm in names],
                                names=list(names)),
                          gvalid, part=part, order_keys=list(node.keys))

    def _reduce(self, node, c, vals, agg_pairs, schemas, m):
        """The keyless aggregate (a rollup's grand total): per-shard
        reductions of the live rows merged by an all-reduce, one 64-bit
        partial per aggregate and peer on the wire; the one result row
        stays on the mesh, so the operators above it do too."""
        from ..ops.aggregate import _agg_value_dtype
        from ..parallel.relational import distributed_reduce
        mesh, axis = self.mesh, self.axis
        pairs = tuple(agg_pairs)
        fn = _jitted(("reduce", mesh, axis, len(vals), pairs),
                     lambda *xs: distributed_reduce(
                         mesh, xs[:-1], pairs, xs[-1], axis))
        self._reset_edge(m)
        with self._exchange() as sp:
            outs, valid = fn(*vals, c.valid)
            with span("plan.wait", site="dist.reduce"):
                jax.block_until_ready((outs, valid))
            nbytes = 8 * len(pairs) * (self.n_peers - 1)
            self._edge(m, "reduce", nbytes, nbytes, "", sp=sp)
        cols = []
        for arr, (cn, o, out_name) in zip(outs, node.aggs):
            dt = _agg_value_dtype(o, c.table[cn].dtype
                                  if o != "size" else dtypes.INT64)
            cols.append(Column(dtype=dt, length=int(arr.shape[0]),
                               data=arr.astype(dt.storage_dtype())))
        out = ShardedRel(Table(cols, names=list(schemas[id(node)])), valid)
        out._num_rows = 1
        return out

    # -- sort / topk ---------------------------------------------------------
    def _dist_sort(self, node, childs, m):
        from ..parallel.relational import distributed_sort_keyed
        (c,) = childs
        if not isinstance(c, ShardedRel) or c.replicated:
            return None
        if not table_shardable(c.table):
            return None
        specs = _key_specs(c.table, node.keys)
        if specs is None:
            return None
        keys = list(node.keys)
        words = []
        for k, sp, asc in zip(keys, specs, node.ascending):
            w, _ = encode_key_column(c.table[k], spec=sp)
            if not asc:
                # bitwise NOT reverses signed int64 order word-wise, and
                # word-wise reversal reverses the tuple's lexicographic
                # order — a descending key costs one elementwise op
                w = [~x for x in w]
            words.extend(w)
        vnames = [nm for nm in c.table.names if nm not in set(keys)]
        val_cols = [c.table[nm] for nm in vnames]
        live = c.num_rows
        key_word_bytes = 8 * sum(sp.total_words for sp in specs)
        logical_row = key_word_bytes + transport.logical_row_bytes(val_cols)
        dp = layout = None
        if self.pack:
            dp = transport.pack_device(val_cols, vnames, c.valid,
                                       self.codecs)
            vals = dp.planes
            wire_row = key_word_bytes + dp.wire_row_bytes
            codec = dp.codec_str
        else:
            vals, layout = _pack_cols(c.table, vnames)
            wire_row, codec = logical_row, ""
        nw, nv = len(words), len(vals)
        mesh, axis = self.mesh, self.axis

        def run(slack):
            key = ("sort", mesh, axis, tuple(specs),
                   tuple(node.ascending), nw, nv, slack)
            fn = _jitted(key,
                lambda *arrs: distributed_sort_keyed(
                    mesh, list(arrs[:nw]), None, list(arrs[nw:-1]),
                    slack=slack, axis=axis, alive=arrs[-1]))
            return fn(*words, *vals, c.valid)

        # each live row crosses the range partition once; splitter
        # samples/pool are metadata (uncounted, like bucket counts). A
        # fault-retried attempt re-describes the edge, not accumulates
        self._reset_edge(m)
        with self._exchange() as sp:
            ws, vs, valid, _ = self._retry(
                node, "sort", run, self._caps(node, "sort",
                                              {"slack": self.slack}), m)
            with span("plan.wait", site="dist.sort"):
                jax.block_until_ready((ws, vs, valid))
            self._edge(m, "range", live * logical_row, live * wire_row,
                       codec, sp=sp)
        valid = valid.astype(jnp.bool_)
        # un-invert descending words before decode
        i = 0
        dec_words = []
        for sp, asc in zip(specs, node.ascending):
            tw = list(ws[i:i + sp.total_words])
            if not asc:
                tw = [~x for x in tw]
            dec_words.extend(tw)
            i += sp.total_words
        cols = dict(_decode_keys(dec_words, specs, keys, valid))
        if nv:
            if dp is not None:
                unpacked = transport.unpack_device(list(vs), dp)
            else:
                unpacked = _unpack_cols(list(vs), layout)
            cols.update({nm: col for nm, col in zip(vnames, unpacked)})
        table = Table([cols[nm] for nm in c.table.names],
                      names=list(c.table.names))
        if isinstance(node, TopK):
            # global rank mask: the live slots in logical order ARE the
            # globally sorted run (shard 0 holds the smallest keys), so
            # the first-n filter is a sharded prefix count — on device,
            # GSPMD turns the logical cumsum into the cross-shard scan
            valid = valid & (jnp.cumsum(valid.astype(jnp.int32)) <= node.n)
        return ShardedRel(table, valid)

    # -- union ---------------------------------------------------------------
    def _dist_union(self, node, childs):
        if not all(isinstance(c, ShardedRel) and not c.replicated
                   for c in childs):
            return None
        from ..parallel.relational import distributed_concat
        names = list(childs[0].table.names)
        mesh, axis = self.mesh, self.axis
        # every shard appends its own rows of each side: UNION ALL owes no
        # order, and a logical concatenation would reshard the rows
        fn = _jitted(("concat", mesh, axis, len(childs)),
            lambda *sides: distributed_concat(mesh, sides, axis))
        nullable = [any(c.table.columns[i].validity is not None
                        for c in childs) for i in range(len(names))]
        sides = []
        for c in childs:
            side = [c.valid]
            for col, nul in zip(c.table.columns, nullable):
                side.append(col.data)
                if nul:
                    side.append(col.null_mask)
            sides.append(tuple(side))
        outs = list(fn(*sides))
        valid, cols, at = outs[0], [], 1
        for i, nul in enumerate(nullable):
            data, validity = outs[at], (outs[at + 1] if nul else None)
            at += 2 if nul else 1
            cols.append(dataclasses.replace(
                childs[0].table.columns[i], data=data, validity=validity,
                length=int(data.shape[0])))
        return ShardedRel(Table(cols, names=names), valid)
