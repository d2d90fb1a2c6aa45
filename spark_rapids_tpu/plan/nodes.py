"""Typed physical-plan operator nodes.

Each node is an immutable dataclass over child nodes — together a DAG
(shared subtrees execute ONCE per run: q23's two reused subqueries are the
same node object on both sides). Nodes carry only the logical parameters;
execution strategy (eager kernels vs capped whole-plan jit vs the
distributed tier behind `Exchange`) is the executor's concern, exactly as
the reference plugin lowers one Catalyst plan onto different kernel tiers.

`output_names(child_schemas)` is the single place each operator's schema
contract lives; `builder.validate` and the executor both call it, so a
schema error raises the same `PlanValidationError` whether it is caught at
build time (declared scan schemas) or at bind time (inferred from the bound
tables).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Optional, Tuple

from .expr import Expr

JOIN_TYPES = ("inner", "left_semi", "left_anti", "left_outer", "full_outer")
# the join types whose output is left ++ right columns (and whose row count
# a match count decides): everything that asks "does this join expand"
# reads this, so that no walk takes an outer join for a filter of its left
# side, nor for an inner join
PAIRING_JOINS = ("inner", "left_outer", "full_outer")
# the pairing joins that keep a side's rows without a match, null-extended
# on the other side: the sides whose columns are nullable after the join
# are the OTHER ones (`nullable_sides`)
OUTER_JOINS = ("left_outer", "full_outer")


def nullable_sides(how: str):
    """(left, right): which side's columns a join of type `how` can put
    out null though its input held none."""
    return how == "full_outer", how in OUTER_JOINS
AGG_OPS = ("sum", "count", "min", "max", "mean", "size")   # ops.aggregate.AGG_OPS
# what a `Window` may say today (ops.window.FRAMES / WINDOW_OPS): the other
# frames, `row_number`, `rank`, `lag` / `lead` are later VALUES of the same
# fields, not other nodes
WINDOW_FRAMES = ("running",)
WINDOW_OPS = ("sum", "min", "max", "count")

_ids = itertools.count()


class PlanValidationError(ValueError):
    """A plan failed schema/reference validation."""


def _require(cond: bool, msg: str):
    if not cond:
        raise PlanValidationError(msg)


@dataclasses.dataclass(frozen=True, eq=False)
class PlanNode:
    def __post_init__(self):
        object.__setattr__(self, "_id", next(_ids))

    @property
    def kind(self) -> str:
        return type(self).__name__

    @property
    def label(self) -> str:
        return f"{self.kind}#{self._id}"

    @property
    def children(self) -> Tuple["PlanNode", ...]:
        return ()

    def output_names(self, child_schemas) -> Tuple[str, ...]:
        """Output column names given the children's schemas (validates)."""
        raise NotImplementedError

    def describe(self) -> str:
        """One-line parameter summary for explain()."""
        return ""


@dataclasses.dataclass(frozen=True, eq=False)
class Scan(PlanNode):
    """Leaf: one named input relation, bound at execute() to a concrete
    Table (`inputs={name: table}`) or a streaming source (an
    `io.ParquetSource`, either via `inputs=` or attached here as
    `parquet` by `PlanBuilder.scan(parquet=...)`). A declared `schema`
    validates at build time and is checked against the binding.
    `projection` (set by the optimizer's column-pruning rule) narrows the
    output to a subset of the bound columns — unpruned columns never
    enter the plan; on a parquet source they are never even DECODED.
    `predicate` (set by the optimizer's scan_pruning rule) is a
    PRUNING-ONLY hint: row groups whose footer min/max statistics prove
    it matches nothing are skipped, while the authoring Filter stays
    above for exact semantics — it never changes the result, only the
    bytes decoded. `est_rows` is an optional cardinality hint for the
    optimizer's build-side selection when no table is bound yet.
    `types` declares columns' LOGICAL types over the bound physical
    buffers ((name, DType) pairs): `decimal(15,2)` over an int64 buffer is
    DECIMAL64's own layout, what a parquet reader does with an INT64 page
    annotated DECIMAL. `typed()` re-tags the bound columns, no copy; the
    verifier checks that each buffer has the declared type's storage."""
    source: str
    schema: Optional[Tuple[str, ...]] = None
    projection: Optional[Tuple[str, ...]] = None
    est_rows: Optional[int] = None
    predicate: Optional[Expr] = None
    parquet: Optional[object] = None    # io.ParquetSource (not fingerprinted)
    types: Optional[Tuple[Tuple[str, object], ...]] = None

    def __post_init__(self):
        super().__post_init__()
        if self.schema is not None:
            object.__setattr__(self, "schema", tuple(self.schema))
        if self.projection is not None:
            object.__setattr__(self, "projection", tuple(self.projection))
        if self.types is not None:
            pairs = (self.types.items() if isinstance(self.types, dict)
                     else self.types)
            object.__setattr__(self, "types",
                               tuple(sorted((str(n), dt) for n, dt in pairs)))

    def typed(self, table):
        """`table` with the declared logical types over its buffers."""
        if not self.types:
            return table
        from ..columnar import Column, Table
        declared = dict(self.types)
        cols = [c if declared.get(n, c.dtype) == c.dtype else Column(
            dtype=declared[n], length=c.length, data=c.data,
            validity=c.validity) for n, c in zip(table.names, table.columns)]
        return Table(cols, names=list(table.names))

    def output_names(self, child_schemas):
        _require(self.schema is not None,
                 f"{self.label}: schema for input {self.source!r} is unknown "
                 "(declare it at scan() or bind inputs)")
        if self.predicate is not None:
            # pruning predicates compare FILE columns (they need not be
            # projected: stats come from the footer, not decoded data)
            missing = self.predicate.references() - set(self.schema)
            _require(not missing,
                     f"{self.label}: pruning predicate references unknown "
                     f"column(s) {sorted(missing)}")
        return self.apply_projection(self.schema)

    def apply_projection(self, schema) -> Tuple[str, ...]:
        """Narrowed output over a (declared or bound) full schema."""
        if self.projection is None:
            return tuple(schema)
        missing = set(self.projection) - set(schema)
        _require(not missing,
                 f"{self.label}: projected column(s) {sorted(missing)} not "
                 f"in {list(schema)}")
        return self.projection

    def describe(self):
        out = self.source
        if self.parquet is not None:
            out += " (parquet)"
        if self.projection is not None:
            out += f" [{', '.join(self.projection)}]"
        if self.types:
            out += " types[" + ", ".join(f"{n}: {dt!r}"
                                         for n, dt in self.types) + "]"
        if self.predicate is not None:
            out += f" prune[{self.predicate!r}]"
        return out


@dataclasses.dataclass(frozen=True, eq=False)
class Filter(PlanNode):
    child: PlanNode
    predicate: Expr

    @property
    def children(self):
        return (self.child,)

    def output_names(self, child_schemas):
        (schema,) = child_schemas
        missing = self.predicate.references() - set(schema)
        _require(not missing,
                 f"{self.label}: predicate references unknown column(s) "
                 f"{sorted(missing)} (have {list(schema)})")
        return schema

    def describe(self):
        return repr(self.predicate)


@dataclasses.dataclass(frozen=True, eq=False)
class Project(PlanNode):
    """Full projection: the output is exactly `exprs` [(name, Expr)]."""
    child: PlanNode
    exprs: Tuple[Tuple[str, Expr], ...]

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "exprs", tuple(
            (n, e) for n, e in self.exprs))

    @property
    def children(self):
        return (self.child,)

    def output_names(self, child_schemas):
        (schema,) = child_schemas
        names = [n for n, _ in self.exprs]
        _require(len(set(names)) == len(names),
                 f"{self.label}: duplicate output name in {names}")
        for n, e in self.exprs:
            missing = e.references() - set(schema)
            _require(not missing,
                     f"{self.label}: {n!r} references unknown column(s) "
                     f"{sorted(missing)} (have {list(schema)})")
        return tuple(names)

    def describe(self):
        return ", ".join(f"{e!r} AS {n}" for n, e in self.exprs)


@dataclasses.dataclass(frozen=True, eq=False)
class FusedSelect(PlanNode):
    """Filter + Project in one operator (optimizer-produced: the
    `select_fusion` rule rewrites Project(Filter(c)) into this). Semantics:
    rows passing `predicate` (over the CHILD schema), projected to `exprs`.
    The eager tier gathers only the projection-referenced columns once,
    instead of materializing the full filtered child and projecting it."""
    child: PlanNode
    predicate: Expr
    exprs: Tuple[Tuple[str, Expr], ...]

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "exprs", tuple(
            (n, e) for n, e in self.exprs))

    @property
    def children(self):
        return (self.child,)

    def output_names(self, child_schemas):
        (schema,) = child_schemas
        missing = self.predicate.references() - set(schema)
        _require(not missing,
                 f"{self.label}: predicate references unknown column(s) "
                 f"{sorted(missing)} (have {list(schema)})")
        names = [n for n, _ in self.exprs]
        _require(len(set(names)) == len(names),
                 f"{self.label}: duplicate output name in {names}")
        for n, e in self.exprs:
            missing = e.references() - set(schema)
            _require(not missing,
                     f"{self.label}: {n!r} references unknown column(s) "
                     f"{sorted(missing)} (have {list(schema)})")
        return tuple(names)

    def describe(self):
        proj = ", ".join(f"{e!r} AS {n}" for n, e in self.exprs)
        return f"{self.predicate!r} -> {proj}"


@dataclasses.dataclass(frozen=True, eq=False)
class HashJoin(PlanNode):
    """Equi-join on key column lists. `inner`, `left_outer` and
    `full_outer` output left++right columns (`left_outer` keeps every left
    row: one without a match, a null key included, comes out once with the
    right side's columns null, so those columns are nullable after the
    join; `full_outer` keeps every right row the same way too, after the
    left join's rows, so both sides' columns are nullable); semi/anti
    output the left columns only (the right side is a filter). `row_cap`,
    when set, overrides the executor's shared row cap for this node in the
    capped tier (a `full_outer` join's frame is that cap plus the right
    side's rows)."""
    left: PlanNode
    right: PlanNode
    left_keys: Tuple[str, ...]
    right_keys: Tuple[str, ...]
    how: str = "inner"
    row_cap: Optional[int] = None

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "left_keys", tuple(self.left_keys))
        object.__setattr__(self, "right_keys", tuple(self.right_keys))
        _require(self.how in JOIN_TYPES,
                 f"{self.label}: join type {self.how!r} not in {JOIN_TYPES}")
        _require(len(self.left_keys) == len(self.right_keys) > 0,
                 f"{self.label}: key lists must be equal-length and "
                 f"non-empty (got {self.left_keys} vs {self.right_keys})")

    @property
    def children(self):
        return (self.left, self.right)

    def output_names(self, child_schemas):
        lschema, rschema = child_schemas
        missing = set(self.left_keys) - set(lschema)
        _require(not missing, f"{self.label}: left key(s) {sorted(missing)} "
                              f"not in {list(lschema)}")
        missing = set(self.right_keys) - set(rschema)
        _require(not missing, f"{self.label}: right key(s) {sorted(missing)} "
                              f"not in {list(rschema)}")
        if self.how not in PAIRING_JOINS:
            return lschema
        dup = set(lschema) & set(rschema)
        _require(not dup,
                 f"{self.label}: output name collision {sorted(dup)} — "
                 "project/rename one side first")
        return lschema + rschema

    def describe(self):
        on = ", ".join(f"{l} = {r}"
                       for l, r in zip(self.left_keys, self.right_keys))
        return f"{self.how} ({on})"


@dataclasses.dataclass(frozen=True, eq=False)
class HashAggregate(PlanNode):
    """Group by `keys`, computing `aggs` [(column, op, out_name)]; empty
    `keys` is a global (one-row) aggregate; keys and no aggregate is a
    DISTINCT over the keys (Spark's `HashAggregate(keys, functions=[])`:
    NULL keys group together). Output schema: keys ++ out
    names. `key_cap` overrides the executor's shared key cap."""
    child: PlanNode
    keys: Tuple[str, ...]
    aggs: Tuple[Tuple[str, str, str], ...]
    key_cap: Optional[int] = None

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "keys", tuple(self.keys))
        object.__setattr__(self, "aggs", tuple(
            (c, o, n) for c, o, n in self.aggs))
        _require(len(self.aggs) > 0 or len(self.keys) > 0,
                 f"{self.label}: a key or an aggregation is required")
        for c, o, n in self.aggs:
            _require(o in AGG_OPS,
                     f"{self.label}: unknown aggregation {o!r} (have "
                     f"{AGG_OPS})")
        if not self.keys:
            for c, o, n in self.aggs:
                _require(o in ("sum", "min", "max", "count", "size"),
                         f"{self.label}: global {o!r} is not supported "
                         "(sum/min/max/count/size only)")

    @property
    def children(self):
        return (self.child,)

    def output_names(self, child_schemas):
        (schema,) = child_schemas
        missing = set(self.keys) - set(schema)
        _require(not missing, f"{self.label}: group key(s) "
                              f"{sorted(missing)} not in {list(schema)}")
        for c, o, n in self.aggs:
            _require(o == "size" or c in schema,
                     f"{self.label}: aggregated column {c!r} not in "
                     f"{list(schema)}")
        names = list(self.keys) + [n for _, _, n in self.aggs]
        _require(len(set(names)) == len(names),
                 f"{self.label}: duplicate output name in {names}")
        return tuple(names)

    def describe(self):
        aggs = ", ".join(f"{o}({c}) AS {n}" for c, o, n in self.aggs)
        return f"keys=[{', '.join(self.keys)}] {aggs or 'distinct'}"


@dataclasses.dataclass(frozen=True, eq=False)
class Window(PlanNode):
    """Window functions (Spark's WindowExec): the child's columns plus one
    column a function `(out_name, op, column)`, each `op(column) OVER
    (PARTITION BY partition_by ORDER BY order_by <frame>)`. One window
    specification a node; `frame` and `op` are values: `running` is `ROWS
    BETWEEN UNBOUNDED PRECEDING AND CURRENT ROW`, the ops `sum`, `min`,
    `max`, `count` (a frame or an op the kernel does not lower is refused
    here by name). Empty `partition_by`: the whole relation is one
    partition. A NULL partition key is a partition of its own (GROUP BY's
    rule); a NULL order key sorts first ascending, last descending; a NULL
    value is skipped and the result is NULL until the partition's first
    value (`count` never is). The output's ROW ORDER is (partition, order),
    whatever the child's was: SQL promises none. Rows that tie on every key
    take the frame in the child's order."""
    child: PlanNode
    partition_by: Tuple[str, ...]
    order_by: Tuple[str, ...]
    functions: Tuple[Tuple[str, str, str], ...]
    ascending: Tuple[bool, ...] = ()
    frame: str = "running"

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "partition_by", tuple(self.partition_by))
        object.__setattr__(self, "order_by", tuple(self.order_by))
        object.__setattr__(self, "functions", tuple(
            (n, o, c) for n, o, c in self.functions))
        asc = self.ascending
        if isinstance(asc, bool):
            asc = (asc,) * len(self.order_by)
        elif not asc:
            asc = (True,) * len(self.order_by)
        object.__setattr__(self, "ascending", tuple(asc))
        _require(self.frame in WINDOW_FRAMES,
                 f"{self.label}: window frame {self.frame!r} is not lowered "
                 f"(have {WINDOW_FRAMES})")
        _require(len(self.functions) > 0,
                 f"{self.label}: needs at least one window function")
        for n, o, c in self.functions:
            _require(o in WINDOW_OPS,
                     f"{self.label}: window function {o!r} is not lowered "
                     f"(have {WINDOW_OPS})")
        _require(len(self.order_by) > 0,
                 f"{self.label}: a {self.frame!r} frame needs an order "
                 "(ORDER BY): without one its rows have no sequence")
        _require(len(self.ascending) == len(self.order_by),
                 f"{self.label}: ascending list must match the order keys")

    @property
    def children(self):
        return (self.child,)

    def output_names(self, child_schemas):
        (schema,) = child_schemas
        for role, keys in (("partition", self.partition_by),
                           ("order", self.order_by)):
            missing = set(keys) - set(schema)
            _require(not missing, f"{self.label}: {role} key(s) "
                                  f"{sorted(missing)} not in {list(schema)}")
        for n, o, c in self.functions:
            _require(c in schema,
                     f"{self.label}: window function input {c!r} not in "
                     f"{list(schema)}")
        names = list(schema) + [n for n, _, _ in self.functions]
        _require(len(set(names)) == len(names),
                 f"{self.label}: duplicate output name in {names}")
        return tuple(names)

    def describe(self):
        fns = ", ".join(f"{o}({c}) AS {n}" for n, o, c in self.functions)
        order = ", ".join(f"{k} {'ASC' if a else 'DESC'}"
                          for k, a in zip(self.order_by, self.ascending))
        return (f"{fns} over (partition by [{', '.join(self.partition_by)}] "
                f"order by [{order}] {self.frame})")


@dataclasses.dataclass(frozen=True, eq=False)
class Sort(PlanNode):
    child: PlanNode
    keys: Tuple[str, ...]
    ascending: Tuple[bool, ...] = ()

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "keys", tuple(self.keys))
        asc = self.ascending
        if isinstance(asc, bool):
            asc = (asc,) * len(self.keys)
        elif not asc:
            asc = (True,) * len(self.keys)
        object.__setattr__(self, "ascending", tuple(asc))
        _require(len(self.keys) > 0, f"{self.label}: needs sort keys")
        _require(len(self.ascending) == len(self.keys),
                 f"{self.label}: ascending list must match the key count")

    @property
    def children(self):
        return (self.child,)

    def output_names(self, child_schemas):
        (schema,) = child_schemas
        missing = set(self.keys) - set(schema)
        _require(not missing, f"{self.label}: sort key(s) "
                              f"{sorted(missing)} not in {list(schema)}")
        return schema

    def describe(self):
        return ", ".join(f"{k} {'ASC' if a else 'DESC'}"
                         for k, a in zip(self.keys, self.ascending))


@dataclasses.dataclass(frozen=True, eq=False)
class TopK(PlanNode):
    """Sort + Limit in one operator (optimizer-produced: the
    `limit_pushdown` rule rewrites Limit(Sort(c)) into this). Output: the
    first `n` rows of the sorted relation — one operator, one metrics row,
    one traversal step in both tiers."""
    child: PlanNode
    keys: Tuple[str, ...]
    ascending: Tuple[bool, ...]
    n: int

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "keys", tuple(self.keys))
        object.__setattr__(self, "ascending", tuple(self.ascending))
        _require(len(self.keys) > 0, f"{self.label}: needs sort keys")
        _require(len(self.ascending) == len(self.keys),
                 f"{self.label}: ascending list must match the key count")
        _require(self.n >= 0, f"{self.label}: negative limit {self.n}")

    @property
    def children(self):
        return (self.child,)

    def output_names(self, child_schemas):
        (schema,) = child_schemas
        missing = set(self.keys) - set(schema)
        _require(not missing, f"{self.label}: sort key(s) "
                              f"{sorted(missing)} not in {list(schema)}")
        return schema

    def describe(self):
        keys = ", ".join(f"{k} {'ASC' if a else 'DESC'}"
                         for k, a in zip(self.keys, self.ascending))
        return f"top {self.n} by {keys}"


@dataclasses.dataclass(frozen=True, eq=False)
class Limit(PlanNode):
    child: PlanNode
    n: int

    def __post_init__(self):
        super().__post_init__()
        _require(self.n >= 0, f"{self.label}: negative limit {self.n}")

    @property
    def children(self):
        return (self.child,)

    def output_names(self, child_schemas):
        return child_schemas[0]

    def describe(self):
        return str(self.n)


@dataclasses.dataclass(frozen=True, eq=False)
class Union(PlanNode):
    """UNION ALL of same-schema inputs (by name, positional)."""
    inputs: Tuple[PlanNode, ...]

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "inputs", tuple(self.inputs))
        _require(len(self.inputs) >= 2,
                 f"{self.label}: needs at least two inputs")

    @property
    def children(self):
        return self.inputs

    def output_names(self, child_schemas):
        first = child_schemas[0]
        for s in child_schemas[1:]:
            _require(tuple(s) == tuple(first),
                     f"{self.label}: input schemas differ: {list(first)} vs "
                     f"{list(s)}")
        return first

    def describe(self):
        return f"{len(self.inputs)} inputs"


EXCHANGE_KINDS = ("hash", "broadcast", "gather", "identity")


@dataclasses.dataclass(frozen=True, eq=False)
class Exchange(PlanNode):
    """Distribution boundary (Spark's ShuffleExchangeExec /
    BroadcastExchangeExec slot) — a REAL physical node on the distributed
    tier (docs/distributed.md). `how` selects the movement:

    - ``hash``: rows move to the shard given by the Spark-exact hash of
      `keys` (pmod n_peers) — the shuffle boundary below shuffle joins and
      two-phase aggregates. A HashAggregate directly above a hash Exchange
      FUSES into the partial-agg → all-to-all → final-agg SPMD program
      (the exchange ships per-group partials, not rows).
    - ``broadcast``: the (small) relation is replicated onto every shard
      over ICI; a join above it probes locally and its other side never
      moves.
    - ``gather``: the sharded relation collects onto one device — the
      sink boundary (or the handoff into an operator with no distributed
      form).
    - ``identity``: no movement (the pre-distributed-tier marker shape;
      also what every Exchange is on a single chip, where the whole node
      is a no-op).

    The optimizer's `exchange_planning` rule inserts and elides these from
    sharding requirements and row-count estimates; `keys` is required for
    ``hash`` and ignored otherwise."""
    child: PlanNode
    keys: Tuple[str, ...] = ()
    how: str = ""

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "keys", tuple(self.keys))
        if not self.how:
            # back-compat default: a keyed Exchange was always the hash
            # marker, a keyless one the identity marker
            object.__setattr__(self, "how",
                               "hash" if self.keys else "identity")
        _require(self.how in EXCHANGE_KINDS,
                 f"{self.label}: exchange kind {self.how!r} not in "
                 f"{EXCHANGE_KINDS}")
        _require(self.how != "hash" or len(self.keys) > 0,
                 f"{self.label}: hash exchange needs partition keys")

    @property
    def children(self):
        return (self.child,)

    def output_names(self, child_schemas):
        (schema,) = child_schemas
        missing = set(self.keys) - set(schema)
        _require(not missing, f"{self.label}: partition key(s) "
                              f"{sorted(missing)} not in {list(schema)}")
        return schema

    def describe(self):
        if self.how == "hash":
            return f"hash[{', '.join(self.keys)}]"
        return self.how
