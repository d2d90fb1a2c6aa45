"""Static plan verifier: symbolic invariant checks over a Plan DAG.

Every check here answers one question WITHOUT executing the plan: could
this DAG — authored or optimizer-rewritten — produce something other than
the Spark-exact answer? Three layers, each independently skippable when
its inputs are unknown (the verifier is sound-but-incomplete: it flags
only DEFINITE violations, so it can gate every test execution without
false alarms):

1. **Schema propagation** — every node's output schema must be derivable
   from its children under the `output_names` contract in `plan/nodes.py`.
   This layer IS the builder's validation (`Plan.__init__` and
   `Plan.resolve_schemas` route through it), so build-time and
   execute-time diagnostics share one error vocabulary: a `Violation`
   with an invariant code and the offending operator's label.

2. **Dtype typing** — with bound-input dtypes known, expressions type
   bottom-up (`plan/expr.py` semantics: comparisons yield BOOL, `&`/`|`
   on floats is a jnp error, STRING/LIST/DECIMAL128 columns are not
   expression-addressable because `Expr.evaluate` reads the raw data
   buffer), predicates must type to BOOL, and aggregates must reduce
   scalar columns.

3. **Partitioning soundness** (`planned=True`, i.e. the plan went through
   the optimizer's `exchange_planning`) — re-derive every node's
   hash-partitioning claim bottom-up with the SAME `transfer_part`
   transfer function `plan/distributed.py` uses at runtime, then prove:
   every shuffle-join's sides co-located (`join_alignment`), every keyed
   aggregate's input co-located or hash-exchanged, no sharded relation
   flowing into an operator with no distributed form, exactly one gather
   at the sink (the PR 5 stale-partitioning-claim bug becomes a verifier
   error here, not a review comment).

`verify_rewrite` adds the pair checks mirroring optimizer-rule side
conditions that a single plan cannot witness: root-schema preservation,
and join build-side swaps only in order-unobservable regions and never
under floating-point inputs (fp reductions are not reorder-exact — the
other PR 5 review finding).

See docs/analysis.md for the invariant catalogue and how the executor's
`SPARK_RAPIDS_TPU_VERIFY_PLANS` gate and the optimizer's fall-back
diagnostics consume this module.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from .. import dtypes
from ..plan.expr import (BinOp, Coalesce, ColumnRef, Expr, IsNull, Literal,
                         ScalarAgg, UnaryOp, When)
from ..plan.nodes import (PAIRING_JOINS, Exchange, Filter, FusedSelect,
                          HashAggregate, HashJoin, Limit, PlanNode,
                          PlanValidationError, Project, Scan, Sort, TopK,
                          Union, Window)

__all__ = ["Violation", "VerifyReport", "PlanVerificationError",
           "verify", "verify_rewrite", "check_build", "resolve_schemas",
           "column_types"]


# ---- error vocabulary -------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Violation:
    """One broken invariant: a machine-readable code, the offending
    operator's label, and the human diagnostic."""
    invariant: str          # e.g. "partitioning.join-not-colocated"
    node: str               # node label, e.g. "HashJoin#12"
    message: str

    def __str__(self):
        return f"[{self.invariant}] {self.message}"


class PlanVerificationError(PlanValidationError):
    """A plan failed static verification. Subclasses the builder's
    `PlanValidationError` so every existing `except`/`raises` contract
    holds; carries the structured `violations` so callers (the optimizer's
    fall-back diagnostic, the bench JSONL) can name the invariant and node
    instead of parsing message text."""

    def __init__(self, violations: List[Violation], context: str = ""):
        self.violations = list(violations)
        head = f"plan verification failed ({context}):\n" if context else ""
        super().__init__(head + "\n".join(str(v) for v in self.violations))


class VerifyReport:
    """Outcome of one verification: the violations found (empty = the plan
    is provably consistent with every checked invariant)."""

    def __init__(self, violations: Optional[List[Violation]] = None):
        self.violations: List[Violation] = list(violations or [])

    @property
    def ok(self) -> bool:
        return not self.violations

    def add(self, invariant: str, node: PlanNode, message: str):
        self.violations.append(Violation(invariant, node.label, message))

    def raise_if_failed(self, context: str = ""):
        if self.violations:
            raise PlanVerificationError(self.violations, context)

    def __repr__(self):
        return f"VerifyReport({len(self.violations)} violation(s))"


# ---- layer 1: schema propagation (the builder's validation backend) ---------

def _propagate_schemas(nodes, bound, strict
                       ) -> Tuple[Dict[int, Tuple[str, ...]],
                                  List[Violation]]:
    """node-id -> output names over a toposorted node list, collecting
    violations instead of raising. Mirrors the historical
    `Plan.resolve_schemas` exactly (same messages — tests match on them);
    a node whose schema cannot be derived poisons its subtree silently so
    one authoring mistake yields one violation, not a cascade."""
    bound = bound or {}
    out: Dict[int, Tuple[str, ...]] = {}
    vs: List[Violation] = []
    broken = set()
    for node in nodes:
        if isinstance(node, Scan):
            schema = bound.get(node.source, node.schema)
            if schema is None and not strict:
                broken.add(id(node))
                continue
            if schema is None:
                vs.append(Violation(
                    "schema.unbound-scan", node.label,
                    f"{node.label}: input {node.source!r} is not bound "
                    f"and no schema was declared"))
                broken.add(id(node))
                continue
            schema = tuple(schema)
            if node.schema is not None and tuple(node.schema) != schema:
                vs.append(Violation(
                    "schema.binding-mismatch", node.label,
                    f"{node.label}: bound table schema {list(schema)} "
                    f"does not match declared {list(node.schema)}"))
                broken.add(id(node))
                continue
            try:
                # the declared/bound cross-check above ran on the full
                # schema; the pruned projection narrows the OUTPUT
                out[id(node)] = node.apply_projection(schema)
            except PlanValidationError as e:
                vs.append(Violation("schema", node.label, str(e)))
                broken.add(id(node))
            continue
        child_schemas = []
        ok = True
        for c in node.children:
            if id(c) not in out:
                ok = False
                break
            child_schemas.append(out[id(c)])
        if not ok:
            if strict and not any(id(c) in broken for c in node.children):
                vs.append(Violation(
                    "schema.unresolved", node.label,
                    f"{node.label}: child schema unresolved"))
            broken.add(id(node))
            continue
        try:
            out[id(node)] = tuple(node.output_names(child_schemas))
        except PlanValidationError as e:
            vs.append(Violation("schema", node.label, str(e)))
            broken.add(id(node))
    return out, vs


def resolve_schemas(nodes, bound=None, strict: bool = True
                    ) -> Dict[int, Tuple[str, ...]]:
    """Raising form of the schema layer — `Plan.resolve_schemas` delegates
    here, so a schema error surfaces as a `PlanVerificationError` (still a
    `PlanValidationError`) whether it is caught at build time or at
    execute()'s bind-time re-resolution."""
    out, vs = _propagate_schemas(nodes, bound, strict)
    if vs:
        raise PlanVerificationError(vs)
    return out


def check_build(plan) -> Dict[int, Tuple[str, ...]]:
    """Build-time validation for `Plan.__init__`: duplicate-source check +
    non-strict schema propagation, one error vocabulary with everything
    else in this module. Returns the resolvable schemas."""
    sources = [s.source for s in plan.scans]
    dup = {s for s in sources if sources.count(s) > 1}
    if dup:
        raise PlanVerificationError([Violation(
            "schema.duplicate-source", plan.root.label,
            f"multiple Scan nodes bind the same input(s) {sorted(dup)}; "
            "reuse one Scan node (the DAG executes it once)")])
    schemas, vs = _propagate_schemas(plan.nodes, None, strict=False)
    if vs:
        raise PlanVerificationError(vs)
    return schemas


# ---- layer 2: expression / operator dtype typing ----------------------------

_BOOL = dtypes.BOOL
_INT64 = dtypes.INT64
_FLOAT64 = dtypes.FLOAT64


def _expr_addressable(dt: Optional[dtypes.DType]) -> bool:
    """Whether `Expr.evaluate` can read the column: it reads the `data`
    buffer row by row, so STRING (chars buffer) and nested columns are
    out — their buffer's length is not the row count. A DECIMAL128
    column's (n, 4) limbs are read by the typed decimal path
    (plan/expr.py `decimal_type`)."""
    if dt is None:
        return True
    return not (dt.is_string or dt.is_nested)


def _decimal_typing(e: Expr, coltypes, node, report: "VerifyReport"):
    """(known, type): Spark's decimal type of `e` by the rule the
    evaluation uses (plan/expr.py). known False: a decimal reaches an
    operator that is not lowered (flagged here)."""
    from ..plan.expr import decimal_sides, decimal_type
    try:
        if isinstance(e, BinOp) and e.op in _CMP_OPS:
            sides = decimal_sides(e, coltypes.get)
            if sides is not None:
                from ..ops.decimal_utils import comparison_scale
                comparison_scale(*sides)    # TypeError: not lowered
            return True, None
        return True, decimal_type(e, coltypes.get)
    except TypeError as err:
        report.add("typing.decimal-not-lowered", node,
                   f"{node.label}: {err}")
        return False, None


def _same_storage(bound: dtypes.DType, declared: dtypes.DType) -> bool:
    """A declared logical type fits a bound buffer of the same fixed-width
    storage: same element dtype, and limbs only over limbs."""
    if any(dt.is_string or dt.is_nested for dt in (bound, declared)):
        return bound == declared
    return bound.storage_dtype() == declared.storage_dtype() and (
        (bound.kind == dtypes.Kind.DECIMAL128)
        == (declared.kind == dtypes.Kind.DECIMAL128))


def _lit_dtype(v) -> Optional[dtypes.DType]:
    if isinstance(v, bool):
        return _BOOL
    if isinstance(v, int):
        return _INT64
    if isinstance(v, float):
        return _FLOAT64
    return None


_CMP_OPS = ("==", "!=", "<", "<=", ">", ">=")


def type_expr(e: Expr, coltypes: Dict[str, Optional[dtypes.DType]],
              node: PlanNode, report: VerifyReport
              ) -> Optional[dtypes.DType]:
    """Bottom-up dtype of `e` under `plan/expr.py` evaluation semantics
    (pure jnp under x64). Returns None when unknowable; appends a
    violation only for expressions that DEFINITELY fail or corrupt at
    runtime — unknown dtypes never flag."""
    if isinstance(e, ColumnRef):
        dt = coltypes.get(e.name)
        if not _expr_addressable(dt):
            report.add("typing.column-not-expr-addressable", node,
                       f"{node.label}: column {e.name!r} is {dt!r} — "
                       "expressions read the data buffer row by row, "
                       "which a string or nested column's is not")
            return None
        return dt
    if isinstance(e, Literal):
        return _lit_dtype(e.value)
    if isinstance(e, (BinOp, UnaryOp, ScalarAgg, When, Coalesce)):
        known, dec = _decimal_typing(e, coltypes, node, report)
        if not known or dec is not None:
            if isinstance(e, When):
                _check_predicate(e.cond, coltypes, node, report)
            return dec          # Spark's decimal type, or flagged
    if isinstance(e, IsNull):
        type_expr(e.child, coltypes, node, report)
        return _BOOL            # never null itself
    if isinstance(e, (When, Coalesce)):
        if isinstance(e, When):
            _check_predicate(e.cond, coltypes, node, report)
        branches = e.children()[1:] if isinstance(e, When) else e.args
        types = [type_expr(b, coltypes, node, report) for b in branches]
        if any(t is None for t in types):
            return None
        kinds = {("bool" if t.kind == dtypes.Kind.BOOL else
                  "float" if t.is_floating else
                  "int" if t.is_integer else repr(t)) for t in types}
        if len(kinds) > 1 and kinds != {"int", "float"}:
            report.add("typing.branch-type-mismatch", node,
                       f"{node.label}: the branches of {e!r} type to "
                       f"{types!r} — one result column has one type (a "
                       "plan states the cast)")
            return None
        if kinds == {"bool"}:
            return _BOOL
        if "float" in kinds:
            return _FLOAT64
        return _INT64 if kinds == {"int"} else types[0]
    if isinstance(e, BinOp):
        lt = type_expr(e.left, coltypes, node, report)
        rt = type_expr(e.right, coltypes, node, report)
        if e.op in _CMP_OPS:
            return _BOOL
        if e.op in ("&", "|"):
            for side in (lt, rt):
                if side is not None and side.is_floating:
                    report.add("typing.bitwise-on-float", node,
                               f"{node.label}: {e.op!r} over a "
                               f"floating-point operand in {e!r} — jnp "
                               "bitwise ops reject floats at runtime")
                    return None
            if lt is not None and rt is not None:
                if lt.kind == dtypes.Kind.BOOL and \
                        rt.kind == dtypes.Kind.BOOL:
                    return _BOOL
                if lt.is_integer and rt.is_integer:
                    return _INT64
            return None
        # + - * arithmetic: x64 promotion — any float makes float
        if lt is not None and rt is not None:
            if lt.is_floating or rt.is_floating:
                return _FLOAT64
            if lt.is_integer and rt.is_integer:
                return _INT64
        return None
    if isinstance(e, UnaryOp):
        ct = type_expr(e.child, coltypes, node, report)
        if e.op == "~":
            if ct is not None and ct.is_floating:
                report.add("typing.invert-on-float", node,
                           f"{node.label}: ~ over a floating-point "
                           f"operand in {e!r} — jnp rejects it at "
                           "runtime")
                return None
            return ct
        if ct is not None and ct.kind == dtypes.Kind.BOOL:
            return None          # -bool: promotion is backend-subtle
        return ct
    if isinstance(e, ScalarAgg):
        ct = type_expr(e.child, coltypes, node, report)
        if ct is None:
            return None
        if e.op == "sum":
            return ct if ct.is_floating else _INT64
        return ct               # min/max preserve
    return None


def _agg_out_dtype(op: str, child_dt: Optional[dtypes.DType]
                   ) -> Optional[dtypes.DType]:
    if op in ("count", "size"):
        return _INT64
    if child_dt is not None and child_dt.is_decimal \
            and op in ("sum", "mean"):
        # Spark's Sum / Average types (ops/aggregate.py)
        from ..ops.aggregate import _agg_value_dtype
        return _agg_value_dtype(op, child_dt)
    if op == "mean":
        return _FLOAT64
    if child_dt is None:
        return None
    if op == "sum":
        return child_dt if child_dt.is_floating else _INT64
    return child_dt             # min/max


def _check_predicate(pred: Expr, coltypes, node, report: VerifyReport):
    t = type_expr(pred, coltypes, node, report)
    if t is not None and t.kind != dtypes.Kind.BOOL:
        report.add("typing.predicate-not-bool", node,
                   f"{node.label}: predicate {pred!r} types to {t!r}, "
                   "not BOOL — a non-boolean mask silently corrupts the "
                   "capped tier's alive set")


def _check_types(nodes, schemas, input_dtypes, report: VerifyReport
                 ) -> Dict[int, Dict[str, Optional[dtypes.DType]]]:
    """Walk node dtypes bottom-up; unknown columns stay unknown and never
    flag. `input_dtypes` maps scan source -> {column: DType}. Returns the
    per-node column-dtype map — the resource certifier
    (analysis/footprint.py) reuses this exact propagation for its byte
    widths, so typing and sizing can never disagree about a column."""
    types: Dict[int, Dict[str, Optional[dtypes.DType]]] = {}
    for node in nodes:
        if id(node) not in schemas:
            continue            # schema layer already poisoned this subtree
        if any(id(c) not in types for c in node.children):
            types[id(node)] = {}
            continue
        kids = [types[id(c)] for c in node.children]
        if isinstance(node, Scan):
            src = dict(input_dtypes.get(node.source) or {})
            for name, declared in node.types or ():
                bound_dt = src.get(name)
                if bound_dt is not None and not _same_storage(bound_dt,
                                                              declared):
                    report.add(
                        "typing.scan-type-storage", node,
                        f"{node.label}: column {name!r} is declared "
                        f"{declared!r} over a bound {bound_dt!r} buffer — "
                        "a logical type re-tags a buffer of its own "
                        "storage (kind and width), it converts nothing")
                src[name] = declared
            types[id(node)] = {n: src.get(n) for n in schemas[id(node)]}
            continue
        if isinstance(node, Filter):
            _check_predicate(node.predicate, kids[0], node, report)
            types[id(node)] = kids[0]
            continue
        if isinstance(node, (Project, FusedSelect)):
            if isinstance(node, FusedSelect):
                _check_predicate(node.predicate, kids[0], node, report)
            # bare ColumnRefs ZERO-COPY through the executor's _project
            # (never Expr.evaluate), so string/nested columns pass
            # untouched — and the column_pruning rule inserts exactly
            # such bare-ref selects; only computed expressions type-check
            types[id(node)] = {
                n: (kids[0].get(e.name) if isinstance(e, ColumnRef)
                    else type_expr(e, kids[0], node, report))
                for n, e in node.exprs}
            continue
        if isinstance(node, HashJoin):
            out = dict(kids[0])
            if node.how in PAIRING_JOINS:
                out.update(kids[1])
            types[id(node)] = out
            continue
        if isinstance(node, HashAggregate):
            out = {k: kids[0].get(k) for k in node.keys}
            for c, o, n in node.aggs:
                cdt = kids[0].get(c) if o != "size" else None
                # flag only ops that READ the data buffer as a scalar
                # array: sum/mean always; min/max only in the keyless
                # global path (the grouped kernel handles string
                # extremes via its value-ordered-sort path, and count
                # consumes validity only)
                reads_data = o in ("sum", "mean") or (
                    not node.keys and o in ("min", "max"))
                # the keyless path reduces the buffer as one array: no
                # decimal there; the grouped kernels sum a decimal as
                # planes and keep min/max to its 64-bit storage
                decimal_out = cdt is not None and cdt.is_decimal and (
                    (reads_data and not node.keys)
                    or (o in ("min", "max")
                        and cdt.kind == dtypes.Kind.DECIMAL128))
                if (reads_data and not _expr_addressable(cdt)) \
                        or decimal_out:
                    report.add(
                        "typing.agg-over-non-scalar", node,
                        f"{node.label}: {o}({c}) over a {cdt!r} column "
                        "is not lowered: a string or nested buffer is "
                        "not row-shaped, and decimals aggregate in "
                        "grouped sum/mean (min/max up to 18 digits)")
                out[n] = _agg_out_dtype(o, cdt)
            types[id(node)] = out
            continue
        if isinstance(node, Window):
            # the kernel's own rules (ops/window.py), asked before
            # anything runs: a key that is not fixed-width and a value
            # type it does not lower are rejected by name, never lowered
            # wrongly (a frame or a function it does not lower never
            # became a node: plan/nodes.py refuses it)
            from ..ops import window as window_ops
            out = dict(kids[0])
            for role, keys in (("partition", node.partition_by),
                               ("order", node.order_by)):
                for k in keys:
                    try:
                        window_ops.check_key(k, kids[0].get(k), role)
                    except TypeError as err:
                        report.add("typing.window-key-not-fixed-width",
                                   node, f"{node.label}: {err}")
            for n, o, c in node.functions:
                try:
                    out[n] = window_ops.result_type(o, kids[0].get(c))
                except TypeError as err:
                    report.add("typing.window-not-lowered", node,
                               f"{node.label}: {o}({c}): {err}")
                    out[n] = None
            types[id(node)] = out
            continue
        if isinstance(node, Union):
            first = kids[0]
            for other in kids[1:]:
                for name in schemas[id(node)]:
                    a, b = first.get(name), other.get(name)
                    if a is None or b is None:
                        continue
                    if _expr_addressable(a) != _expr_addressable(b):
                        report.add(
                            "typing.union-dtype-mismatch", node,
                            f"{node.label}: column {name!r} is {a!r} on "
                            f"one input and {b!r} on another — UNION ALL "
                            "cannot concatenate scalar and non-scalar "
                            "layouts")
            types[id(node)] = dict(first)
            continue
        # Sort/TopK/Limit/Exchange: pass-through
        types[id(node)] = dict(kids[0]) if kids else {}
    return types


def column_types(nodes, schemas, input_dtypes
                 ) -> Dict[int, Dict[str, Optional[dtypes.DType]]]:
    """Public face of the typing walk for non-gating consumers: node-id ->
    {column name -> DType or None (unknown)} under the same bottom-up
    semantics the typing layer verifies. Violations found along the way are
    discarded here — callers that want them gate through verify()."""
    return _check_types(nodes, schemas, input_dtypes, VerifyReport())


# ---- layer 3: pruning-predicate legality ------------------------------------

def _conjunct_triples(pred: Expr):
    """(name, op, repr(value)) triples of the min/max-provable top-level
    AND conjuncts, plus the count of non-provable conjuncts."""
    from ..plan.optimizer import _as_comparison, split_conjuncts
    triples, unprovable = set(), 0
    for c in split_conjuncts(pred):
        cmp = _as_comparison(c)
        if cmp is None:
            unprovable += 1
        else:
            triples.add((cmp[0], cmp[1], repr(cmp[2])))
    return triples, unprovable


def _check_scan_pruning(nodes, report: VerifyReport):
    """A `Scan.predicate` is a PRUNING-ONLY hint: legality requires the
    enforcing Filter/FusedSelect to still sit directly above (retained
    semantics), the scan to be single-consumer (a DAG-shared scan feeds
    parents that did not author the filter — the scan_pruning rule's
    shared-scan guard, promoted to a verifier invariant), and every
    lowered conjunct to be min/max-provable AND implied by the retained
    predicate."""
    parents: Dict[int, List[PlanNode]] = {}
    for n in nodes:
        for c in n.children:
            parents.setdefault(id(c), []).append(n)
    for node in nodes:
        if not isinstance(node, Scan) or node.predicate is None:
            continue
        ps = parents.get(id(node), [])
        if len(ps) != 1:
            report.add("pruning.shared-scan", node,
                       f"{node.label}: carries a pruning predicate but "
                       f"has {len(ps)} consumers — pruning a DAG-shared "
                       "scan starves the parents that did not author "
                       "the filter")
            continue
        parent = ps[0]
        if not isinstance(parent, (Filter, FusedSelect)):
            report.add("pruning.unenforced-predicate", node,
                       f"{node.label}: pruning predicate "
                       f"{node.predicate!r} has no enforcing Filter/"
                       f"FusedSelect directly above (parent is "
                       f"{parent.label}) — pruned row groups would "
                       "change the result")
            continue
        scan_triples, unprovable = _conjunct_triples(node.predicate)
        if unprovable:
            report.add("pruning.unprovable-conjunct", node,
                       f"{node.label}: pruning predicate "
                       f"{node.predicate!r} contains conjunct(s) row-"
                       "group min/max statistics cannot prove — the "
                       "scan would over-prune")
            continue
        parent_triples, _ = _conjunct_triples(parent.predicate)
        missing = scan_triples - parent_triples
        if missing:
            report.add("pruning.unretained-conjunct", node,
                       f"{node.label}: pruning conjunct(s) "
                       f"{sorted(missing)} are not conjuncts of the "
                       f"retained predicate on {parent.label} — rows "
                       "the plan still wants could be pruned")


# ---- layer 4: sharding/partitioning soundness -------------------------------

def _check_partitioning(nodes, root, schemas, float_inputs: bool,
                        report: VerifyReport):
    """Re-derive each node's sharded/local state and hash-partitioning
    claim bottom-up — the same `transfer_part` transfer function the
    runtime `ShardedRel`s and the optimizer's `exchange_planning` follow —
    and prove the plan's exchange structure sound: co-located shuffle-join
    and keyed-aggregate inputs, gathers wherever a sharded relation meets
    an operator with no distributed form, exactly one gather at the sink.
    Only meaningful for exchange-PLANNED plans (`verify(planned=True)`);
    an unplanned plan legitimately relies on the runtime's implicit
    repartition."""
    from ..plan.distributed import (join_alignment, part_satisfies,
                                    transfer_part)
    from ..plan.optimizer import _statically_distributable
    sharded: Dict[int, bool] = {}
    part: Dict[int, frozenset] = {}
    for node in nodes:
        if id(node) not in schemas:
            continue
        kids = list(node.children)
        kid_sharded = [sharded.get(id(c), False) for c in kids]
        kid_parts = [part.get(id(c), frozenset()) for c in kids]
        if isinstance(node, Exchange):
            base = kid_sharded[0]
            if node.how == "gather":
                if not base:
                    report.add("partitioning.redundant-gather", node,
                               f"{node.label}: gathers an input that is "
                               "already local — the sink must gather "
                               "exactly once")
                sharded[id(node)] = False
                part[id(node)] = frozenset()
            elif node.how == "broadcast":
                # replicates a sharded rel — or lifts a local build side
                sharded[id(node)] = True
                part[id(node)] = frozenset()
            else:               # hash / identity: no-op over a local child
                sharded[id(node)] = base
                part[id(node)] = (transfer_part(node, kid_parts)
                                  if base else frozenset())
            continue
        on_mesh = _statically_distributable(node, float_inputs) and (
            isinstance(node, Scan) or (bool(kids) and all(kid_sharded)))
        sharded[id(node)] = on_mesh
        part[id(node)] = (transfer_part(node, kid_parts)
                          if on_mesh else frozenset())
        if not on_mesh:
            for c, s in zip(kids, kid_sharded):
                if s:
                    report.add(
                        "partitioning.ungathered-input", node,
                        f"{node.label}: has no distributed form for "
                        f"this binding but consumes sharded {c.label} "
                        "without a gather boundary")
            continue
        if isinstance(node, HashJoin):
            l, r = kids
            r_broadcast = isinstance(r, Exchange) and r.how == "broadcast"
            if isinstance(l, Exchange) and l.how == "broadcast":
                report.add("partitioning.broadcast-probe", node,
                           f"{node.label}: probe (left) side is a "
                           "broadcast exchange — only the build side "
                           "may replicate")
            if not r_broadcast and join_alignment(
                    kid_parts[0], kid_parts[1],
                    node.left_keys, node.right_keys) is None:
                report.add(
                    "partitioning.join-not-colocated", node,
                    f"{node.label}: sides are partitioned by "
                    f"{sorted(map(list, kid_parts[0])) or 'rows'} vs "
                    f"{sorted(map(list, kid_parts[1])) or 'rows'} — "
                    f"matching keys ({', '.join(node.left_keys)}) = "
                    f"({', '.join(node.right_keys)}) are not provably "
                    "co-located; the elided shuffle would duplicate/"
                    "drop matches")
        elif isinstance(node, HashAggregate) and node.keys:
            (c,) = kids
            fused = isinstance(c, Exchange) and c.how == "hash"
            if not fused and not part_satisfies(kid_parts[0], node.keys):
                report.add(
                    "partitioning.agg-not-colocated", node,
                    f"{node.label}: groups by ({', '.join(node.keys)}) "
                    f"over an input partitioned by "
                    f"{sorted(map(list, kid_parts[0])) or 'rows'} — no "
                    "claim co-locates every group and no hash exchange "
                    "re-places them; a shard-local merge would emit "
                    "duplicate groups")
    if sharded.get(id(root), False):
        report.add("partitioning.unsunk-root", root,
                   f"{root.label}: plan root is still sharded — the "
                   "planned sink gather is missing")


# ---- public entry points ----------------------------------------------------

def verify(plan, *, bound=None,
           input_dtypes: Optional[Dict[str, Dict]] = None,
           float_inputs: Optional[bool] = None,
           planned: bool = False) -> VerifyReport:
    """Verify one plan. `bound` maps scan source -> actual column names
    (schema layer runs strict when given); `input_dtypes` maps source ->
    {column: DType} and enables the typing layer; `planned=True` enables
    the partitioning layer (the plan claims a complete exchange plan —
    the optimizer's `exchange_planning` output). Returns a VerifyReport;
    callers gate with `.raise_if_failed()`."""
    report = VerifyReport()
    schemas, schema_vs = _propagate_schemas(plan.nodes, bound,
                                            strict=bound is not None)
    report.violations.extend(schema_vs)
    if float_inputs is None:
        float_inputs = bool(input_dtypes) and any(
            dt is not None and dt.is_floating
            for cols in input_dtypes.values() for dt in cols.values())
    if input_dtypes:
        _check_types(plan.nodes, schemas, input_dtypes, report)
    _check_scan_pruning(plan.nodes, report)
    if planned and not schema_vs:
        _check_partitioning(plan.nodes, plan.root, schemas,
                            bool(float_inputs), report)
    return report


def _plan_has_mean(nodes) -> bool:
    return any(isinstance(n, HashAggregate)
               and any(o == "mean" for _, o, _ in n.aggs) for n in nodes)


def verify_rewrite(authored, optimized, *, bound=None,
                   input_dtypes: Optional[Dict[str, Dict]] = None,
                   float_inputs: Optional[bool] = None,
                   planned: bool = False, report=None) -> VerifyReport:
    """Verify an optimizer rewrite: the optimized plan standalone, plus
    the pair invariants a single plan cannot witness — the root schema is
    preserved, and any join build-side swap honors the `build_side` rule's
    side conditions (only inside order-unobservable regions, never under
    floating-point inputs or a `mean` aggregate, whose reductions are not
    reorder-exact). `report` (the OptimizeReport) scopes the swap check to
    executions where the rule actually fired — and supplies the per-join
    decision source (hint / observed:<runs> / default, docs/adaptive.md),
    so a violation on a STATS-DRIVEN swap names the observations that
    picked it. This gate is not optional for adaptive rewrites: the
    executor runs it on every observed-driven rewrite even with
    SPARK_RAPIDS_TPU_VERIFY_PLANS off (PlanExecutor._optimized), because
    the stats store may change WHICH rewrites fire but must never weaken
    the invariants they are checked against."""
    out = verify(optimized, bound=bound, input_dtypes=input_dtypes,
                 float_inputs=float_inputs, planned=planned)
    if float_inputs is None:
        float_inputs = bool(input_dtypes) and any(
            dt is not None and dt.is_floating
            for cols in input_dtypes.values() for dt in cols.values())
    # root schema preservation (violations already reported by the
    # verify() call above; only the resolved root schemas matter here)
    a_schemas, _ = _propagate_schemas(authored.nodes, bound, strict=False)
    o_schemas, _ = _propagate_schemas(optimized.nodes, bound,
                                      strict=False)
    a_root = a_schemas.get(id(authored.root))
    o_root = o_schemas.get(id(optimized.root))
    if a_root is not None and o_root is not None and a_root != o_root:
        out.add("rewrite.schema-drift", optimized.root,
                f"{optimized.root.label}: rewrite changed the plan's "
                f"output schema {list(a_root)} -> {list(o_root)}")
    # build-side swap legality (diff-based: the pair witnesses the swap).
    # MULTISET comparison of inner-join key pairs, not set membership: a
    # plan that authors both (x)/(y) and (y)/(x) joins would otherwise
    # alias — the swapped join's reversed pair already "exists" and the
    # swap hides. An optimized pair occurring MORE times than authored,
    # with the reversed pair authored, witnesses a swap.
    if report is not None and not report.rules.get("build_side", 0):
        return out
    from collections import Counter

    def _pairs(nodes):
        return Counter((tuple(n.left_keys), tuple(n.right_keys))
                       for n in nodes
                       if isinstance(n, HashJoin) and n.how == "inner")

    a_cnt = _pairs(authored.nodes)
    excess = {p: c - a_cnt.get(p, 0)
              for p, c in _pairs(optimized.nodes).items()}
    swapped = []
    for n in optimized.nodes:
        if not (isinstance(n, HashJoin) and n.how == "inner"):
            continue
        p = (tuple(n.left_keys), tuple(n.right_keys))
        if excess.get(p, 0) > 0 and (p[1], p[0]) in a_cnt:
            excess[p] -= 1
            swapped.append(n)
    if not swapped:
        return out

    def _src(n) -> str:
        """Decision-source suffix for a swap violation: which estimate
        tier picked a swap. Only `swap (...)` stamps qualify — the
        fixpoint pass re-stamps the SWAPPED node's own label with a
        `keep` (its reversed sides never re-qualify under the 2x
        hysteresis), which describes the post-swap confirmation, not the
        decision under scrutiny. Diagnostic only — legality never
        depends on where the cardinalities came from."""
        sources = getattr(report, "decision_sources", None) or {}
        got = sources.get(f"{n.label}/build_side")
        if got is None or not got.startswith("swap"):
            swaps = [v for k, v in sorted(sources.items())
                     if k.endswith("/build_side")
                     and v.startswith("swap")]
            got = swaps[0] if len(swaps) == 1 else None
        return f" (decision source: {got})" if got else ""

    if float_inputs or _plan_has_mean(optimized.nodes) \
            or _plan_has_mean(authored.nodes):
        for n in swapped:
            out.add("rewrite.fp-build-side", n,
                    f"{n.label}: build-side swap under floating-point "
                    "inputs (or a mean aggregate) — fp reductions are "
                    "not reorder-exact on m:n joins, so the swapped "
                    f"pair enumeration changes the bits{_src(n)}")
        return out
    from ..plan.optimizer import _order_safe_ids
    safe = _order_safe_ids(optimized.root)
    for n in swapped:
        if id(n) not in safe:
            out.add("rewrite.order-unsafe-swap", n,
                    f"{n.label}: build-side swap where the join's output "
                    "row order is observable (not every path to the root "
                    "crosses a HashAggregate) — results would no longer "
                    f"be row-for-row identical{_src(n)}")
    return out
