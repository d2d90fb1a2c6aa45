"""Rule-based plan optimizer: Catalyst-style logical rewrites before tier
dispatch.

The reference plugin receives plans AFTER Spark's Catalyst optimizer has
rewritten them; this engine's builder hands over plans exactly as authored.
On accelerators the dominant wins come from not moving or computing
unneeded columns and rows before any HBM byte is touched ("Accelerating
Presto with GPUs", "Do GPUs Really Need New Tabular File Formats?" —
PAPERS.md), so `PlanExecutor.execute()` runs this pipeline by default
(`SPARK_RAPIDS_TPU_OPTIMIZER=off`, or `PlanExecutor(optimize=False)`, to
disable) and executes the rewritten DAG on whichever tier was selected.

Rules — each a pure `root -> root'` rewrite, the pipeline run to fixpoint
with a pass-count guard (`MAX_PASSES`):

- `constant_folding`: literal-only expression subtrees fold to `Literal`s
  (expr.fold); `Filter(true)` drops; `Filter(false)` short-circuits to
  `Limit(0)` (an empty relation of the same schema — no new node kind).
- `predicate_pushdown`: Filter moves below Project (predicate rewritten
  through cheap ColumnRef/Literal projections), below Union (one copy per
  input), below a Window where it reads partition keys alone (it keeps or
  drops whole partitions), and into the side of a HashJoin whose columns
  it references —
  rows die before the join/union/materialization instead of after.
- `limit_pushdown`: Limit(Limit) collapses, Limit moves below row-wise
  Projects, and Limit(Sort) fuses into one `TopK` operator.
- `build_side`: inner-join children swap (plus a column-order-restoring
  Project) when row-count estimates say the left side is much smaller —
  the smaller relation becomes the right/build side, as a CBO picks.
  Estimates come from bound table sizes, falling back to the `est_rows`
  scan hint threaded through `PlanBuilder.scan()`. Swapping reorders the
  join's output ROWS, so the rule fires only where that order is
  unobservable — every path to the root crosses a HashAggregate — keeping
  results row-for-row identical.
- `column_pruning`: required columns walk top-down through the DAG;
  Scans narrow to a `projection` (unused columns never enter the plan),
  Project/FusedSelect outputs, HashAggregate agg lists and a Window's
  functions drop dead entries, and width-sensitive operators
  (join/aggregate/window/sort/exchange inputs) get a zero-copy select-Project inserted when their input still
  carries dead columns (e.g. a Filter's predicate-only columns).
- `select_fusion`: adjacent Filters merge (`a & b`) and Project(Filter)
  fuses into one `FusedSelect` node, so the eager tier gathers the
  projection-referenced columns once instead of materializing the full
  filtered relation first.

DAG sharing is preserved: rewrites memoize per node object, and rules that
restructure a parent/child pair skip children referenced by more than one
parent (restructuring would un-share the subtree and re-execute it).
Scalar-aggregate expressions (`scalar_max(...)`) are never moved across
operators that change their input row set.

`plan_fingerprint` is the canonical structural hash (node kinds, params,
exprs, declared schemas, DAG shape) the executor keys its compiled-program
and caps memos by, so structurally identical plans built independently
share compiled XLA programs — see `Plan.fingerprint`.

If a rewritten DAG fails re-validation (a defensive impossibility given
the rule guards, but plans are user input), `optimize` falls back to the
authored plan and reports `fell_back=True` instead of failing the query.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List, Optional, Tuple

from .builder import Plan, _toposort
from .expr import (BinOp, Coalesce, ColumnRef, Expr, IsNull, Literal,
                   ScalarAgg, UnaryOp, When, col, fold, has_scalar_agg,
                   null_aware, substitute)
from .nodes import (OUTER_JOINS, PAIRING_JOINS, Exchange, Filter, FusedSelect,
                    HashAggregate, HashJoin,
                    Limit, PlanNode, PlanValidationError, Project, Scan,
                    Sort, TopK, Union, Window)

__all__ = ["optimize", "plan_fingerprint", "subtree_fingerprints",
           "OptimizeReport", "RULE_NAMES", "MAX_PASSES",
           "pruning_conjuncts", "split_conjuncts"]

MAX_PASSES = 10           # fixpoint guard: rewrite passes, not rewrites
_EST_BYTES_PER_CELL = 8   # the engine's INT64-tier column width


# ---- fingerprint ------------------------------------------------------------

# pure hints that do not change the program a plan compiles to — plus the
# attached streaming source object (its identity is execution state, not
# plan structure; shapes/names already key the executor's program cache)
_FP_SKIP_FIELDS = {"est_rows", "parquet"}


def _fp_expr(e: Expr) -> Tuple:
    """Type-TAGGED expression serialization: `col("1")` and `lit(1)` repr
    identically ("1") but must hash apart — a collision would let two
    semantically different plans share one compiled program."""
    if isinstance(e, ColumnRef):
        return ("col", e.name)
    if isinstance(e, Literal):
        return ("lit", repr(e.value))
    if isinstance(e, BinOp):
        return ("bin", e.op, _fp_expr(e.left), _fp_expr(e.right))
    if isinstance(e, UnaryOp):
        return ("un", e.op, _fp_expr(e.child))
    if isinstance(e, ScalarAgg):
        return ("agg", e.op, _fp_expr(e.child))
    if isinstance(e, IsNull):
        return ("isnull", e.negate, _fp_expr(e.child))
    if isinstance(e, (When, Coalesce)):
        return (type(e).__name__.lower(),
                *(_fp_expr(c) for c in e.children()))
    return ("expr", repr(e))


def _fp_value(v) -> object:
    if isinstance(v, Expr):
        return _fp_expr(v)
    if isinstance(v, tuple):
        return tuple(_fp_value(x) for x in v)
    return repr(v)


def _node_params(node: PlanNode) -> Tuple:
    """Canonical value tuple over the node's non-child parameters; exprs
    serialize type-tagged (`_fp_expr`), so the hash distinguishes a
    mutated literal — and a literal from a same-repr column ref — but not
    a rebuilt-identical plan."""
    params = []
    for f in dataclasses.fields(node):
        if f.name in _FP_SKIP_FIELDS:
            continue
        v = getattr(node, f.name)
        if isinstance(v, PlanNode):
            continue
        if isinstance(v, tuple) and v and isinstance(v[0], PlanNode):
            continue
        params.append((f.name, _fp_value(v)))
    return tuple(params)


def plan_fingerprint(plan: Plan) -> str:
    """Structural hash over the plan DAG: per node (kind, params, child
    indices in toposort order). The toposort is deterministic for a given
    structure, so two independently built identical plans — including the
    same subtree-sharing shape — hash equal."""
    nodes = plan.nodes
    index = {id(n): i for i, n in enumerate(nodes)}
    toks = [(n.kind, _node_params(n),
             tuple(index[id(c)] for c in n.children)) for n in nodes]
    return hashlib.sha256(repr(toks).encode()).hexdigest()[:16]


def _subtree_token_hash(node: PlanNode, child_fps) -> str:
    """THE per-node subtree-hash definition — the single point the
    store's record keys (subtree_fingerprints over the executed plan)
    and the estimator's consult keys (_Estimator._subtree_fp over the
    plan being optimized) both derive from; a second copy drifting would
    silently make observed stats never match."""
    toks = (node.kind, _node_params(node), tuple(child_fps))
    return hashlib.sha256(repr(toks).encode()).hexdigest()[:16]


def subtree_fingerprints(root: PlanNode) -> Dict[int, str]:
    """node-id -> structural hash of the subtree BELOW each node (kind,
    params, child subtree hashes — same token vocabulary as
    `plan_fingerprint`, same `_FP_SKIP_FIELDS` hint exclusions). Two
    occurrences of one operator subtree hash equal across plans and
    across runs, which is what lets the stats store (plan/stats.py)
    carry an observed output cardinality from an executed plan's node to
    the structurally identical node the optimizer is re-estimating on
    the next execution — and why a schema or parameter change (a stale
    fingerprint) can never match."""
    out: Dict[int, str] = {}
    for n in _toposort(root):
        out[id(n)] = _subtree_token_hash(
            n, (out[id(c)] for c in n.children))
    return out


# ---- report -----------------------------------------------------------------

RULE_NAMES = ("constant_folding", "predicate_pushdown", "limit_pushdown",
              "build_side", "column_pruning", "select_fusion",
              "scan_pruning", "exchange_planning")


# ---- pruning-conjunct extraction (shared with the executor's scan IO) -------

# comparison ops a row group's min/max range can prove empty
_PRUNE_OPS = ("<", "<=", ">", ">=", "==")
_FLIP_OP = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "==": "=="}


def split_conjuncts(e: Expr) -> List[Expr]:
    """Top-level AND conjuncts of a predicate (the predicate itself when
    its root is not `&`)."""
    if isinstance(e, BinOp) and e.op == "&":
        return split_conjuncts(e.left) + split_conjuncts(e.right)
    return [e]


def _as_comparison(e: Expr) -> Optional[Tuple[str, str, object]]:
    """`col <op> literal` (either orientation) as (name, op, value); None
    for any other shape — an OR, a column-column compare, arithmetic, a
    scalar aggregate — which min/max stats cannot prove anything about."""
    if not isinstance(e, BinOp) or e.op not in _PRUNE_OPS:
        return None
    l, r = e.left, e.right
    if isinstance(l, ColumnRef) and isinstance(r, Literal):
        return (l.name, e.op, r.value)
    if isinstance(r, ColumnRef) and isinstance(l, Literal):
        return (r.name, _FLIP_OP[e.op], l.value)
    return None


def pruning_conjuncts(e: Expr) -> List[Tuple[str, str, object]]:
    """The (column, op, literal) triples of `e`'s top-level AND conjuncts
    that row-group min/max statistics can evaluate. Pruning on this SUBSET
    of an AND is always conservative-exact (every extracted conjunct must
    hold for a row to survive the retained Filter); a non-conjunct shape —
    e.g. an OR at the top level — contributes nothing, so the scan_pruning
    rule declines rather than over-prunes."""
    out = []
    for c in split_conjuncts(e):
        cmp = _as_comparison(c)
        if cmp is not None:
            out.append(cmp)
    return out


@dataclasses.dataclass
class OptimizeReport:
    """What the pipeline did to one plan — surfaced by explain(optimized=
    True), PlanResult.optimizer, and the bench JSONL `rules_fired` field."""
    rules: Dict[str, int]
    passes: int = 0
    pruned_columns: int = 0        # columns dropped (scan/project/insert)
    pruned_bytes_est: int = 0      # est rows x 8B per dropped column
    source_fingerprint: str = ""
    fingerprint: str = ""
    fell_back: bool = False
    # precise fall-back diagnostic (analysis/verifier.py): which rule
    # produced the invalid rewrite, which node, which invariant —
    # surfaced by summary(), PlanResult.optimizer and the bench JSONL
    # instead of the bare fell_back flag
    fallback: Optional[Dict] = None
    # distributed planning (exchange_planning rule, docs/distributed.md):
    # Exchange insertions per kind, elisions (a boundary the partitioning
    # already satisfied), and the final plan's per-node sharding specs
    exchanges: Dict[str, int] = dataclasses.field(default_factory=dict)
    exchanges_elided: int = 0
    sharding: Dict[str, str] = dataclasses.field(default_factory=dict)
    # adaptive execution (plan/stats.py, docs/adaptive.md): per rule
    # firing, WHERE the cardinalities behind a build-side or
    # exchange-mode choice came from — "<node label>/<rule>" ->
    # "<decision> (hint | observed:<run count> | default)". "observed"
    # means the stats store's recorded subtree cardinality drove the
    # estimate; "hint" an `est_rows` scan hint; "default" bound table
    # sizes / structural guesses. Trajectory numbers and explain output
    # must never silently mix cold and warm decisions.
    decision_sources: Dict[str, str] = dataclasses.field(
        default_factory=dict)
    # a stats-driven rewrite failed the verify_rewrite gate and the
    # pipeline re-ran statically (defensive — the same guards protect
    # both paths; see PlanExecutor._optimized)
    stats_reverted: bool = False

    def rules_fired(self) -> Dict[str, int]:
        return {k: v for k, v in self.rules.items() if v}

    def total_rewrites(self) -> int:
        return sum(self.rules.values())

    def stats_driven(self) -> bool:
        """Whether an observed-sourced decision actually CHANGED the
        plan: a build-side `swap` stamped from observed cardinalities,
        or an observed-driven exchange-mode pick (which only exists when
        exchange_planning placed boundaries). A `keep (observed:N)` is
        the static outcome confirmed by observations — not a rewrite —
        and must not trigger the executor's always-on verify_rewrite
        gate on every warm production run of any join-bearing plan.
        Exchange stamps are DELIBERATELY conservative the other way:
        telling an observed pick apart from the identical static one
        would need a parallel static estimate per join, so every
        observed exchange decision counts — the extra verify walk is
        proportionally small next to a distributed mesh execution."""
        for key, v in self.decision_sources.items():
            if "observed" not in v:
                continue
            if key.endswith("/exchange"):
                return True
            if key.endswith("/build_side") and v.startswith("swap"):
                return True
        return False

    def to_dict(self) -> Dict:
        return {"rules_fired": self.rules_fired(), "passes": self.passes,
                "pruned_columns": self.pruned_columns,
                "pruned_bytes_est": self.pruned_bytes_est,
                "fingerprint": self.fingerprint,
                "source_fingerprint": self.source_fingerprint,
                "fell_back": self.fell_back,
                "fallback": dict(self.fallback) if self.fallback else None,
                "exchanges": dict(self.exchanges),
                "exchanges_elided": self.exchanges_elided,
                "sharding": dict(self.sharding),
                "decision_sources": dict(self.decision_sources),
                "stats_driven": self.stats_driven(),
                "stats_reverted": self.stats_reverted}

    def summary(self) -> str:
        lines = [f"optimizer: {self.passes} pass(es), "
                 f"{self.total_rewrites()} rewrite(s)"
                 + (" [FELL BACK: re-validation failed, authored plan ran]"
                    if self.fell_back else "")]
        if self.fallback:
            lines.append(f"  fell back on rule={self.fallback.get('rule')} "
                         f"node={self.fallback.get('node')} "
                         f"invariant={self.fallback.get('invariant')}: "
                         f"{self.fallback.get('message')}")
        for name, n in self.rules_fired().items():
            lines.append(f"  {name}: {n}")
        if self.pruned_columns:
            lines.append(f"  pruned {self.pruned_columns} column(s) "
                         f"(~{self.pruned_bytes_est} bytes est)")
        if self.exchanges or self.exchanges_elided:
            placed = ", ".join(f"{k}={v}" for k, v in
                               sorted(self.exchanges.items()) if v)
            lines.append(f"  exchanges: {placed or 'none'}, "
                         f"{self.exchanges_elided} elided")
        if self.sharding:
            lines.append("  sharding:")
            for label, spec in self.sharding.items():
                lines.append(f"    {label}: {spec}")
        if self.decision_sources:
            lines.append("  decision sources"
                         + (" [STATS REVERTED: observed-driven rewrite "
                            "failed verify_rewrite, static decisions ran]"
                            if self.stats_reverted else "") + ":")
            for key, src in sorted(self.decision_sources.items()):
                lines.append(f"    {key}: {src}")
        lines.append(f"  fingerprint {self.source_fingerprint} -> "
                     f"{self.fingerprint}")
        return "\n".join(lines)


# ---- rewrite infrastructure -------------------------------------------------

def _with_children(node: PlanNode, kids: Tuple[PlanNode, ...]) -> PlanNode:
    if isinstance(node, HashJoin):
        return dataclasses.replace(node, left=kids[0], right=kids[1])
    if isinstance(node, Union):
        return dataclasses.replace(node, inputs=tuple(kids))
    if node.children:
        return dataclasses.replace(node, child=kids[0])
    return node


def _rewrite(root: PlanNode, fn, shared: Optional[set] = None) -> PlanNode:
    """Bottom-up memoized rewrite. `fn(node) -> replacement | None` runs on
    each node AFTER its children were rewritten; the memo keys on the
    original objects so DAG-shared subtrees rewrite once and stay shared.

    `shared` (the pass's shared-node id set) is kept LIVE: when a shared
    original is rebuilt with rewritten children, the rebuilt node's id
    joins the set — a parent-side guard checking `id(child) in shared`
    would otherwise pass on the fresh object and un-share the subtree."""
    memo: Dict[int, PlanNode] = {}

    def go(node: PlanNode) -> PlanNode:
        got = memo.get(id(node))
        if got is not None:
            return got
        kids = tuple(go(c) for c in node.children)
        if any(k is not c for k, c in zip(kids, node.children)):
            node2 = _with_children(node, kids)
        else:
            node2 = node
        if shared is not None and node2 is not node and id(node) in shared:
            shared.add(id(node2))
        out = fn(node2)
        memo[id(node)] = node2 if out is None else out
        return memo[id(node)]

    return go(root)


def _shared_ids(root: PlanNode) -> set:
    """ids of nodes referenced by >1 parent — rules that restructure a
    parent/child pair must skip these or the subtree would un-share."""
    counts: Dict[int, int] = {}
    for n in _toposort(root):
        for c in n.children:
            counts[id(c)] = counts.get(id(c), 0) + 1
    return {i for i, c in counts.items() if c > 1}


class _Schemas:
    """Lazy output-schema resolver usable on any node, old or freshly
    rewritten. Unresolvable subtrees (scan without declared schema and no
    binding) resolve to None and schema-dependent rules skip them."""

    def __init__(self, bound: Optional[Dict[str, Tuple[str, ...]]] = None):
        self.bound = dict(bound or {})
        self.memo: Dict[int, Optional[Tuple[str, ...]]] = {}

    def of(self, node: PlanNode) -> Optional[Tuple[str, ...]]:
        got = self.memo.get(id(node), _Schemas)
        if got is not _Schemas:
            return got
        if isinstance(node, Scan):
            base = self.bound.get(node.source, node.schema)
            s = None if base is None else node.apply_projection(base)
        else:
            kids = [self.of(c) for c in node.children]
            s = (None if any(k is None for k in kids)
                 else tuple(node.output_names(kids)))
        self.memo[id(node)] = s
        return s


# estimate-source severity lattice: a decision that consumed ANY observed
# cardinality is stats-driven; certified bounds and hints outrank
# structural defaults (a certified bound is SOUND but loose, a hint is
# the author's guess at the actual — both lose to observations)
_SRC_RANK = {"default": 0, "certified": 1, "hint": 2, "observed": 3}


class _Estimator:
    """Row-count estimates, bottom-up. OBSERVED subtree cardinalities
    from the stats store (plan/stats.py) win for interior nodes; bound
    table sizes win at scans; `est_rows` scan hints fill in; where the
    static chain has nothing at all, the resource certifier's sound
    rows-hi bound (analysis/footprint.py) fills in LAST before None
    propagates (rules skip). Selectivity guesses are crude on purpose —
    only the build_side and exchange rules consume them, both behind
    margins. Alongside each estimate the SOURCE is tracked ("observed" /
    "hint" / "certified" / "default", plus the observed run count or the
    certified bound) so rule firings can stamp their decision source on
    the report."""

    def __init__(self, bound_rows: Optional[Dict[str, int]] = None,
                 stats=None, backend: Optional[str] = None, cert=None):
        # scan source -> rows of the bound table; "filter:<subtree
        # fingerprint>" -> rows a filter keeps of a small bound table,
        # where the meshed executor counted it before planning exchanges
        # (PlanExecutor._counted_filters)
        self.bound = dict(bound_rows or {})
        self.stats = stats          # plan/stats.StatsStore or None
        self.backend = backend
        self.cert = cert            # node -> Optional[int] certified rows hi
        self.memo: Dict[int, Optional[float]] = {}
        self.src: Dict[int, Tuple[str, Optional[int]]] = {}
        self._subfp: Dict[int, str] = {}

    def of(self, node: PlanNode) -> Optional[float]:
        got = self.memo.get(id(node), _Estimator)
        if got is not _Estimator:
            return got
        e, src, runs = self._compute(node)
        self.memo[id(node)] = e
        if e is not None:
            self.src[id(node)] = (src, runs)
        return e

    def source_of(self, *nodes: PlanNode) -> str:
        """Rendered decision source over the nodes whose estimates fed
        one rule decision: the severity-max of their sources, with the
        smallest observed run count when observed (a decision is only as
        warm as its coldest observation) and the largest certified bound
        when certified (the loosest proof the decision leaned on)."""
        best, runs, bnd = "default", None, None
        for n in nodes:
            s, r = self.src.get(id(n), ("default", None))
            if _SRC_RANK[s] > _SRC_RANK[best]:
                best = s
            if s == "observed" and r is not None:
                runs = r if runs is None else min(runs, r)
            if s == "certified" and r is not None:
                bnd = r if bnd is None else max(bnd, r)
        if best == "observed":
            return f"observed:{runs}"
        if best == "certified":
            return f"certified:{bnd}"
        return best

    def _subtree_fp(self, node: PlanNode) -> str:
        got = self._subfp.get(id(node))
        if got is None:
            got = _subtree_token_hash(
                node, (self._subtree_fp(c) for c in node.children))
            self._subfp[id(node)] = got
        return got

    def _observed(self, node: PlanNode) -> Optional[Tuple[int, int]]:
        if isinstance(node, Filter) and isinstance(node.child, Scan):
            rows = self.bound.get("filter:" + self._subtree_fp(node))
            if rows is not None:
                return rows, 1
        if self.stats is None or self.backend is None:
            return None
        return self.stats.observed_rows(self.backend,
                                        self._subtree_fp(node))

    def _certified(self, node: PlanNode) -> Optional[int]:
        """The resource certifier's sound rows-hi bound for this node, or
        None (no certifier wired, or the subtree is unbounded). Last
        resort before the estimate chain gives up: a hi bound is a LOOSE
        stand-in for a cardinality, but rules behind margins prefer it
        over skipping the decision entirely (docs/analysis.md)."""
        if self.cert is None:
            return None
        return self.cert(node)

    def _compute(self, node: PlanNode
                 ) -> Tuple[Optional[float], str, Optional[int]]:
        if isinstance(node, Scan):
            v = self.bound.get(node.source)
            if v is not None:
                return float(v), "default", None
            obs = self._observed(node)
            if obs is not None:
                return float(obs[0]), "observed", obs[1]
            if node.est_rows is not None:
                return float(node.est_rows), "hint", None
            c = self._certified(node)
            if c is not None:
                return float(c), "certified", c
            return None, "default", None
        obs = self._observed(node)
        if obs is not None:
            return float(obs[0]), "observed", obs[1]
        kids = [self.of(c) for c in node.children]
        if any(k is None for k in kids):
            c = self._certified(node)
            if c is not None:
                return float(c), "certified", c
            return None, "default", None
        src, runs = "default", None
        for c in node.children:
            s, r = self.src.get(id(c), ("default", None))
            if _SRC_RANK[s] > _SRC_RANK[src]:
                src = s
            if s == "observed" and r is not None:
                runs = r if runs is None else min(runs, r)
        if isinstance(node, (Filter, FusedSelect)):
            return 0.5 * kids[0], src, runs
        if isinstance(node, (Project, Exchange, Sort, Window)):
            return kids[0], src, runs
        if isinstance(node, Limit):
            return min(float(node.n), kids[0]), src, runs
        if isinstance(node, TopK):
            return min(float(node.n), kids[0]), src, runs
        if isinstance(node, Union):
            return sum(kids), src, runs
        if isinstance(node, HashJoin):
            if node.how == "full_outer":
                return sum(kids), src, runs     # each side's rows at least
            if node.how in PAIRING_JOINS:
                # (an outer join holds its left rows at least: the same)
                return max(kids), src, runs
            return 0.5 * kids[0], src, runs
        if isinstance(node, HashAggregate):
            if not node.keys:
                return 1.0, src, runs
            return max(1.0, kids[0] / 10.0), src, runs   # distinct guess
        return (kids[0] if kids else None), src, runs


# ---- rules ------------------------------------------------------------------
# Each rule: (root, ctx) -> (root', hits). ctx carries schemas/estimates/
# shared-ids computed fresh for the pass, plus the report for prune stats.

class _Ctx:
    def __init__(self, root, bound, bound_rows, report,
                 float_inputs=False, streaming=frozenset(),
                 stats=None, backend=None, input_dtypes=None):
        self.root = root
        self.bound = bound
        self.bound_rows = bound_rows
        self.input_dtypes = input_dtypes
        self._cert = None               # lazy footprint cert over `root`
        self.schemas = _Schemas(bound)
        self.est = _Estimator(bound_rows, stats, backend,
                              cert=self.cert_rows_hi)
        self.shared = _shared_ids(root)
        self.report = report
        self.float_inputs = float_inputs
        self.streaming = streaming      # scan sources bound to streaming
        #                                 (parquet) sources this execution

    def _cert_map(self):
        """Resource-certifier bounds over this pass's root
        (analysis/footprint.py), computed on first consult only — most
        rule invocations never ask. Keyed by node id over the CURRENT
        root's toposort, so estimator misses and the exchange rule's
        byte-legality proof read the same walk."""
        if self._cert is None:
            from ..analysis.footprint import certify_nodes
            self._cert = certify_nodes(
                _toposort(self.root), bound=self.bound,
                bound_rows=self.bound_rows,
                input_dtypes=self.input_dtypes)
        return self._cert

    def cert_rows_hi(self, node: PlanNode) -> Optional[int]:
        b = self._cert_map().get(id(node))
        return None if b is None else b.rows_hi

    def cert_out_bytes_hi(self, node: PlanNode) -> Optional[int]:
        b = self._cert_map().get(id(node))
        return None if b is None else b.out_bytes_hi


def _rule_constant_folding(root, ctx):
    hits = [0]

    def fn(node):
        if isinstance(node, Filter):
            p = fold(node.predicate)
            if isinstance(p, Literal):
                hits[0] += 1
                if bool(p.value):
                    return node.child              # Filter(true): drop
                return Limit(node.child, 0)        # Filter(false): empty
            if p is not node.predicate:
                hits[0] += 1
                return dataclasses.replace(node, predicate=p)
            return None
        if isinstance(node, FusedSelect):
            p = fold(node.predicate)
            exprs = tuple((n, fold(e)) for n, e in node.exprs)
            changed = (p is not node.predicate or
                       any(e is not o for (_, e), (_, o)
                           in zip(exprs, node.exprs)))
            if isinstance(p, Literal):
                hits[0] += 1
                child = (node.child if bool(p.value)
                         else Limit(node.child, 0))
                return Project(child, exprs)
            if changed:
                hits[0] += 1
                return FusedSelect(node.child, p, exprs)
            return None
        if isinstance(node, Project):
            exprs = tuple((n, fold(e)) for n, e in node.exprs)
            if any(e is not o for (_, e), (_, o) in zip(exprs, node.exprs)):
                hits[0] += 1
                return dataclasses.replace(node, exprs=exprs)
        return None

    return _rewrite(root, fn), hits[0]


def _rule_predicate_pushdown(root, ctx):
    hits = [0]

    def fn(node):
        if not isinstance(node, Filter):
            return None
        child, p = node.child, node.predicate
        if id(child) in ctx.shared:
            return None    # restructuring would un-share the subtree
        if isinstance(child, Project):
            if any(has_scalar_agg(e) for _, e in child.exprs):
                # the filter below would change the row set the project's
                # scalar aggregate reduces over — same hazard (and guard)
                # as limit_pushdown's Project branch
                return None
            mapping = dict(child.exprs)
            refs = p.references()
            # substitute only through cheap projections: re-evaluating a
            # computed expression twice would trade bytes for FLOPs
            if refs <= set(mapping) and all(
                    isinstance(mapping[r], (ColumnRef, Literal))
                    for r in refs):
                hits[0] += 1
                pushed = Filter(child.child, substitute(p, mapping))
                return dataclasses.replace(child, child=pushed)
            return None
        if isinstance(child, Union) and not has_scalar_agg(p):
            hits[0] += 1
            return Union(tuple(Filter(i, p) for i in child.inputs))
        if isinstance(child, Window) and not has_scalar_agg(p):
            # below a window only what keeps or drops WHOLE partitions: a
            # predicate over partition keys alone (every row of a
            # partition holds the same keys, a NULL key included). One
            # that reads an order key, a value or a function's column
            # would change the frames of the rows it keeps
            if p.references() <= set(child.partition_by):
                hits[0] += 1
                return dataclasses.replace(
                    child, child=Filter(child.child, p))
            return None
        if isinstance(child, HashJoin) and not has_scalar_agg(p):
            refs = p.references()
            ls = ctx.schemas.of(child.left)
            rs = ctx.schemas.of(child.right)
            if child.how == "inner" and rs is not None and refs <= set(rs):
                # NOT below an outer join's null-supplying side (the right
                # of `left_outer`, either of `full_outer`): over the
                # join's output the predicate drops the null-extended rows
                # (or keeps them alone: `is_null`, a `when`, a `coalesce`
                # can be TRUE over a null); below that side it would turn
                # the dropped matches INTO null-extended rows. An inner
                # join supplies no nulls, so a null-aware predicate passes
                # below it like any other
                hits[0] += 1
                return dataclasses.replace(
                    child, right=Filter(child.right, p))
            if child.how != "full_outer" \
                    and ls is not None and refs <= set(ls):
                # inner, left_outer: left-only columns (a left outer join
                # keeps or drops a left row's outputs together, and never
                # nulls them, so a null-aware predicate commutes too);
                # semi/anti: output IS the left
                # schema, so a row filter always commutes to the left side
                hits[0] += 1
                return dataclasses.replace(child, left=Filter(child.left, p))
        return None

    return _rewrite(root, fn, ctx.shared), hits[0]


def _rule_limit_pushdown(root, ctx):
    hits = [0]

    def fn(node):
        if not isinstance(node, Limit):
            return None
        c = node.child
        if id(c) in ctx.shared:
            return None
        if isinstance(c, Limit):
            hits[0] += 1
            return Limit(c.child, min(node.n, c.n))
        if isinstance(c, Project) and not any(
                has_scalar_agg(e) for _, e in c.exprs):
            hits[0] += 1
            return dataclasses.replace(c, child=Limit(c.child, node.n))
        if isinstance(c, Sort):
            hits[0] += 1
            return TopK(c.child, c.keys, c.ascending, node.n)
        return None

    return _rewrite(root, fn, ctx.shared), hits[0]


def _order_safe_ids(root: PlanNode) -> set:
    """ids of nodes whose output ROW ORDER is unobservable: every path to
    the root passes through a HashAggregate (whose output order depends on
    keys, not input order) via operators that merely propagate rows.
    Swapping a join reorders its output rows, so the build_side rule only
    fires inside these regions — result parity stays row-for-row exact.
    (Sort is NOT a pass-through: a stable sort exposes input order on key
    ties; Limit/TopK take the first n rows, observably.)"""
    nodes = _toposort(root)
    parents: Dict[int, List[PlanNode]] = {}
    for n in nodes:
        for c in n.children:
            parents.setdefault(id(c), []).append(n)
    pass_through = (Filter, FusedSelect, Project, HashJoin, Union, Exchange)
    safe: Dict[int, bool] = {}
    for n in reversed(nodes):             # parents before children
        ps = parents.get(id(n), [])
        safe[id(n)] = bool(ps) and all(
            isinstance(p, HashAggregate)
            or (isinstance(p, pass_through) and safe[id(p)])
            for p in ps)
    return {i for i, v in safe.items() if v}


def _rule_build_side(root, ctx):
    hits = [0]
    if ctx.float_inputs:
        # floating-point sums/means are not associative: the aggregate
        # above absorbs the ROW reorder but not the fp reduction-order
        # change on m:n joins (within-group pair enumeration flips), so
        # bit-exact parity only holds for exact (integer/bool) inputs —
        # skip the rule entirely when any bound input carries floats
        return root, 0
    if any(isinstance(n, HashAggregate)
           and any(o == "mean" for _, o, _ in n.aggs)
           for n in _toposort(root)):
        # mean accumulates in float64 even over integer inputs (and its
        # output stays float for anything above), so a mean anywhere in
        # the plan reintroduces the fp reorder-exactness problem
        return root, 0
    safe = _order_safe_ids(root)
    memo: Dict[int, PlanNode] = {}

    def go(n: PlanNode) -> PlanNode:      # custom recursion: the safety
        got = memo.get(id(n))             # set keys on ORIGINAL node ids
        if got is not None:
            return got
        kids = tuple(go(c) for c in n.children)
        node2 = (_with_children(n, kids)
                 if any(k is not c for k, c in zip(kids, n.children)) else n)
        # inner alone: a `left_outer` join with its sides exchanged is a
        # right outer join, another answer, and a `full_outer` join's
        # output order (the left join's rows, then the lonely right rows)
        # and column order are its sides'
        if (isinstance(n, HashJoin) and n.how == "inner"
                and id(n) in safe):
            le = ctx.est.of(n.left)
            re_ = ctx.est.of(n.right)
            ls = ctx.schemas.of(n.left)
            rs = ctx.schemas.of(n.right)
            # 2x hysteresis: swap only on a clear margin so the rule is
            # stable (the swapped join's sides never re-qualify)
            if None not in (le, re_, ls, rs):
                swap = le * 2 < re_
                # decision provenance (docs/adaptive.md): which estimate
                # tier fed this choice — re-stamped each pass, so the
                # fixpoint pass (where warm observed stats have become
                # visible through the converged subtree shapes) wins
                ctx.report.decision_sources[f"{n.label}/build_side"] = (
                    f"{'swap' if swap else 'keep'} "
                    f"({ctx.est.source_of(n.left, n.right)})")
                if swap:
                    hits[0] += 1
                    swapped = HashJoin(node2.right, node2.left,
                                       n.right_keys, n.left_keys,
                                       how="inner", row_cap=n.row_cap)
                    order = tuple(ls) + tuple(rs)  # restore authored order
                    node2 = Project(swapped,
                                    tuple((nm, col(nm)) for nm in order))
        memo[id(n)] = node2
        return node2

    return go(root), hits[0]


def _rule_select_fusion(root, ctx):
    hits = [0]

    def fn(node):
        if (isinstance(node, Filter) and isinstance(node.child, Filter)
                and id(node.child) not in ctx.shared
                and not has_scalar_agg(node.predicate)):
            # inner predicate first is irrelevant for a row-wise AND; a
            # scalar-agg outer predicate reduces over the FILTERED rows,
            # so it must not move over the inner filter
            inner = node.child
            hits[0] += 1
            return Filter(inner.child, inner.predicate & node.predicate)
        if (isinstance(node, Project) and isinstance(node.child, Filter)
                and id(node.child) not in ctx.shared):
            f = node.child
            hits[0] += 1
            return FusedSelect(f.child, f.predicate, node.exprs)
        return None

    return _rewrite(root, fn, ctx.shared), hits[0]


# width-sensitive operators: a dead column crossing one of these edges is
# materialized/sorted/shuffled, so a zero-copy select pays for itself
_NARROW_PARENTS = (HashJoin, HashAggregate, Sort, TopK, Exchange, Window)


def _rule_column_pruning(root, ctx):
    nodes = _toposort(root)
    schemas = {id(n): ctx.schemas.of(n) for n in nodes}
    if any(s is None for s in schemas.values()):
        return root, 0                    # unresolved subtree: skip the pass
    required: Dict[int, set] = {}
    extra: Dict[int, set] = {}     # union-equalization floor (see below)
    edge_req: Dict[Tuple[int, int], set] = {}

    def req_of(n):
        return required[id(n)] | extra.get(id(n), set())

    def push(parent, i, req):
        edge_req[(id(parent), i)] = req
        required[id(parent.children[i])] |= req

    # Recompute until stable: Union inputs must all narrow to the SAME
    # schema (positional contract), but a DAG-shared input can pick up
    # extra requirements from parents OUTSIDE the union — equalize every
    # union's inputs to their union-of-requirements and re-propagate.
    # Requirements only grow, so this terminates well inside the bound.
    for _ in range(len(nodes) + 1):
        required = {id(n): set() for n in nodes}
        edge_req.clear()
        required[id(root)] = set(schemas[id(root)])
        # reversed toposort = parents before children: each node's
        # required set is complete (over all parents) when we reach it
        for n in reversed(nodes):
            req = req_of(n)
            if isinstance(n, Filter):
                push(n, 0, set(req) | n.predicate.references())
            elif isinstance(n, (Project, FusedSelect)):
                kept = [e for name, e in n.exprs if name in req] or \
                       [n.exprs[0][1]]
                r = set().union(*[e.references() for e in kept])
                if isinstance(n, FusedSelect):
                    r |= n.predicate.references()
                if not r:                 # all-literal: keep a row carrier
                    r = {schemas[id(n.children[0])][0]}
                push(n, 0, r)
            elif isinstance(n, HashJoin):
                ls = schemas[id(n.left)]
                rs = schemas[id(n.right)]
                if n.how in PAIRING_JOINS:
                    push(n, 0, (req & set(ls)) | set(n.left_keys))
                    push(n, 1, (req & set(rs)) | set(n.right_keys))
                else:
                    push(n, 0, set(req) | set(n.left_keys))
                    push(n, 1, set(n.right_keys))
            elif isinstance(n, HashAggregate):
                # (a DISTINCT has no aggregate to keep)
                kept = [a for a in n.aggs if a[2] in req] or list(n.aggs[:1])
                r = set(n.keys) | {c for c, o, _ in kept if o != "size"}
                if not r:                 # global size-only aggregate
                    r = {schemas[id(n.children[0])][0]}
                push(n, 0, r)
            elif isinstance(n, (Sort, TopK)):
                push(n, 0, set(req) | set(n.keys))
            elif isinstance(n, Window):
                # the keys, the kept functions' inputs, and what passes
                # through to a reader above
                kept = [f for f in n.functions if f[0] in req] \
                    or list(n.functions[:1])
                made = {f[0] for f in n.functions}
                push(n, 0, (set(req) - made) | set(n.partition_by)
                     | set(n.order_by) | {c for _, _, c in kept})
            elif isinstance(n, Exchange):
                push(n, 0, set(req) | set(n.keys))
            elif isinstance(n, (Limit, Union)):
                for i in range(len(n.children)):
                    push(n, i, set(req))
        stable = True
        for n in nodes:
            if isinstance(n, Union):
                eq = set().union(*[req_of(c) for c in n.children])
                for c in n.children:
                    if req_of(c) != eq:
                        extra.setdefault(id(c), set()).update(eq)
                        stable = False
        if stable:
            break

    hits = [0]
    rep = ctx.report

    def note_pruned(n_cols, est_rows):
        hits[0] += 1
        rep.pruned_columns += n_cols
        if est_rows is not None:
            rep.pruned_bytes_est += int(
                n_cols * est_rows * _EST_BYTES_PER_CELL)

    memo: Dict[int, PlanNode] = {}

    def go(n: PlanNode) -> PlanNode:
        got = memo.get(id(n))
        if got is not None:
            return got
        kids = [go(c) for c in n.children]
        if isinstance(n, _NARROW_PARENTS):
            for i, (orig_c, new_c) in enumerate(zip(n.children, kids)):
                if isinstance(new_c, Exchange):
                    continue    # narrow below it: Exchange is pass-through,
                    # and a Project in between would break the distributed
                    # HashAggregate-on-Exchange lowering
                r = edge_req[(id(n), i)]
                cs = ctx.schemas.of(new_c)
                if cs is None or not (set(cs) - r):
                    continue
                keep = tuple(c for c in cs if c in r)
                note_pruned(len(cs) - len(keep), ctx.est.of(orig_c))
                kids[i] = Project(new_c,
                                  tuple((c, ColumnRef(c)) for c in keep))
        node2 = (_with_children(n, tuple(kids))
                 if any(k is not c for k, c in zip(kids, n.children)) else n)
        req = req_of(n)
        if isinstance(n, Scan):
            cur = schemas[id(n)]
            keep = tuple(c for c in cur if c in req) or (cur[0],)
            if keep != tuple(cur):
                note_pruned(len(cur) - len(keep), ctx.est.of(n))
                node2 = dataclasses.replace(node2, projection=keep)
        elif isinstance(n, (Project, FusedSelect)):
            kept = tuple((name, e) for name, e in n.exprs if name in req) \
                or (n.exprs[0],)
            if len(kept) < len(n.exprs):
                note_pruned(len(n.exprs) - len(kept), ctx.est.of(n))
                node2 = dataclasses.replace(node2, exprs=kept)
        elif isinstance(n, HashAggregate):
            kept = tuple(a for a in n.aggs if a[2] in req) or n.aggs[:1]
            if len(kept) < len(n.aggs):
                note_pruned(len(n.aggs) - len(kept), ctx.est.of(n))
                node2 = dataclasses.replace(node2, aggs=kept)
        elif isinstance(n, Window):
            # a function nobody reads goes (one stays: the node's rows and
            # their order are its output too)
            kept = tuple(f for f in n.functions if f[0] in req) \
                or n.functions[:1]
            if len(kept) < len(n.functions):
                note_pruned(len(n.functions) - len(kept), ctx.est.of(n))
                node2 = dataclasses.replace(node2, functions=kept)
        memo[id(n)] = node2
        return node2

    return go(root), hits[0]


def _rule_scan_pruning(root, ctx):
    """Filter/FusedSelect directly over a streaming-source Scan: lower the
    min/max-provable AND-conjuncts of the predicate into `Scan.predicate`
    for row-group pruning. PRUNING-ONLY: the Filter/FusedSelect stays
    above for exact row semantics; a row group is skipped at scan time
    only when footer statistics prove the lowered conjuncts match nothing
    (io/parquet.select_row_groups). Predicates with no provable top-level
    conjunct — an OR at the root, column-column compares, scalar
    aggregates — lower nothing: extracting from inside an OR would
    over-prune rows the retained Filter still wants."""
    hits = [0]

    def fn(node):
        if not isinstance(node, (Filter, FusedSelect)):
            return None
        child = node.child
        if not isinstance(child, Scan) or child.predicate is not None:
            return None
        if child.parquet is None and child.source not in ctx.streaming:
            return None     # table-bound scan: nothing to prune at IO time
        if id(child) in ctx.shared:
            # a shared scan feeds OTHER parents that did not author this
            # filter — pruning it would starve them of rows
            return None
        safe = [c for c in split_conjuncts(node.predicate)
                if _as_comparison(c) is not None]
        if not safe:
            return None
        pred = safe[0]
        for c in safe[1:]:
            pred = BinOp("&", pred, c)
        hits[0] += 1
        return _with_children(
            node, (dataclasses.replace(child, predicate=pred),))

    return _rewrite(root, fn, ctx.shared), hits[0]


_RULES = (
    ("constant_folding", _rule_constant_folding),
    ("predicate_pushdown", _rule_predicate_pushdown),
    ("limit_pushdown", _rule_limit_pushdown),
    ("build_side", _rule_build_side),
    ("column_pruning", _rule_column_pruning),
    ("select_fusion", _rule_select_fusion),
    ("scan_pruning", _rule_scan_pruning),
)


# ---- exchange planning (distributed tier, docs/distributed.md) --------------

def mesh_local_reason(nodes) -> Optional[Tuple[str, str]]:
    """(label, why) of the first node that keeps a WHOLE plan on one chip
    although its executor has a mesh, or None. An outer join has no
    lowering in plan/distributed.py's walk (`left_outer` has a shard-local
    kernel in parallel/relational.py, `full_outer` none); a walk that met
    one half way would run it through the one-chip fallback above a
    gather, so the plan is not put on the mesh at all, and the optimize
    report says so under `<label>/mesh`. A null-aware expression
    (`is_null`, `when`, `coalesce`) keeps its plan local the same way: the
    walk evaluates value and validity of every other expression
    (`_eval`), and these have not run over shards. A `Window` has no
    lowering in the walk either (its partitions would need an exchange by
    the partition keys and a sort a shard)."""
    for n in nodes:
        if isinstance(n, HashJoin) and n.how in OUTER_JOINS:
            return n.label, (f"local ({n.how} has no distributed "
                             "lowering: the whole plan runs on one chip)")
        if isinstance(n, Window):
            return n.label, ("local (a window has no distributed "
                             "lowering: the whole plan runs on one chip)")
        if any(null_aware(e) for e in _node_exprs(n)):
            return n.label, ("local (a null-aware expression has no "
                             "distributed lowering: the whole plan runs "
                             "on one chip)")
    return None


def _node_exprs(n: PlanNode) -> Tuple[Expr, ...]:
    """The expressions a node evaluates."""
    out = ()
    if isinstance(n, (Filter, FusedSelect)):
        out += (n.predicate,)
    if isinstance(n, (Project, FusedSelect)):
        out += tuple(e for _, e in n.exprs)
    return out


def _statically_distributable(n: PlanNode, float_inputs: bool) -> bool:
    """Whether a node kind CAN run on the mesh — the static half of the
    gate (the executor re-checks runtime properties like column dtypes and
    gathers gracefully when they fail). Limit has no distributed form;
    `mean` and any-float inputs disable aggregates (the exchange
    accumulates partials in exact int64). A keyless aggregate reduces on
    the mesh (an all-reduce of per-shard partials)."""
    if isinstance(n, Limit):
        return False
    if mesh_local_reason((n,)) is not None:
        return False
    if isinstance(n, HashAggregate):
        if any(o == "mean" for _, o, _ in n.aggs):
            return False
        if float_inputs:
            return False
        if not n.aggs:
            return False    # a DISTINCT: the fused two-phase program
            #                 merges partial aggregates, and there are none
    return True


def _plan_exchanges(root: PlanNode, ctx: "_Ctx", n_peers: int):
    """Post-fixpoint distributed planning: walk the DAG bottom-up tracking
    each node's hash-partitioning property (plan/distributed.transfer_part
    — the SAME rule the runtime rels follow) and insert the Exchange
    boundaries the mesh execution needs:

    - each shuffle-join side gets Exchange(hash, its keys) unless the
      side is already partitioned by exactly that key tuple (ELIDED);
    - a join whose build (right) side estimate is at or below
      `config.broadcast_rows()` — and no larger than the probe side —
      gets Exchange(broadcast) instead: the small side replicates, the
      probe side never moves (est_rows-driven, Spark's
      autoBroadcastJoinThreshold shape);
    - a keyed HashAggregate gets Exchange(hash, group keys) below it
      (the executor FUSES the pair into the two-phase partial-agg
      program) unless the input partitioning already co-locates every
      group — a subset of the group keys suffices — in which case the
      boundary is elided and the aggregate merges shard-locally;
    - sharded relations flowing into an operator with NO distributed
      form — and the plan root — get Exchange(gather): the only
      hops off the mesh, visible in explain().

    Returns (new root, insertions); fills report.exchanges/
    exchanges_elided/sharding."""
    from .. import config
    from .distributed import part_satisfies, transfer_part
    report = ctx.report
    nodes = _toposort(root)
    if any(ctx.schemas.of(n) is None for n in nodes):
        return root, 0
    thresh = config.broadcast_rows()
    stats = {"hash": 0, "broadcast": 0, "gather": 0}
    elided = [0]
    sharded: Dict[int, bool] = {}
    part: Dict[int, frozenset] = {}
    memo: Dict[int, PlanNode] = {}
    gathers: Dict[int, PlanNode] = {}   # one gather per shared child

    def add_exchange(child: PlanNode, keys, how: str) -> PlanNode:
        if how == "gather" and id(child) in gathers:
            return gathers[id(child)]
        stats[how] += 1
        ex = Exchange(child, tuple(keys), how=how)
        part[id(ex)] = transfer_part(ex, [part[id(child)]])
        sharded[id(ex)] = how != "gather"
        if how == "gather":
            gathers[id(child)] = ex
        return ex

    def go(n: PlanNode) -> PlanNode:
        got = memo.get(id(n))
        if got is not None:
            return got
        kids = [go(c) for c in n.children]
        on_mesh = _statically_distributable(n, ctx.float_inputs) and (
            isinstance(n, Scan) or (bool(kids)
                                    and all(sharded[id(k)] for k in kids)))
        if not on_mesh:
            # graceful boundary: sharded children collect here
            kids = [add_exchange(k, (), "gather") if sharded[id(k)] else k
                    for k in kids]
        elif isinstance(n, HashJoin):
            l_new, r_new = kids
            le = ctx.est.of(n.left)
            re_ = ctx.est.of(n.right)
            row_ok = (re_ is not None and re_ <= thresh
                      and (le is None or re_ <= le))
            # broadcast LEGALITY is a proven byte bound
            # (analysis/footprint.py, docs/analysis.md): the certified
            # build-side hi must fit config.broadcast_bytes() — the row
            # estimate stays the cost heuristic, but a mis-estimated
            # side whose certified bytes exceed the ceiling never
            # replicates onto every peer. Unbounded sides (strings,
            # unbound scans) keep the row heuristic alone.
            bytes_hi = ctx.cert_out_bytes_hi(n.right)
            bc_bytes = config.broadcast_bytes()
            byte_ok = bytes_hi is None or bytes_hi <= bc_bytes
            broadcast = row_ok and byte_ok
            # decision provenance, same vocabulary as build_side: what
            # kind of estimate picked the exchange mode for this join —
            # plus the byte proof (or veto) when the certifier bounded
            # the build side
            note = ("" if bytes_hi is None else
                    f"; certified:{bytes_hi}B"
                    f"{'<=' if byte_ok else '>'}{bc_bytes}B")
            report.decision_sources[f"{n.label}/exchange"] = (
                f"{'broadcast' if broadcast else 'shuffle'} "
                f"({ctx.est.source_of(n.left, n.right)}{note})")
            if broadcast:
                r_new = add_exchange(r_new, (), "broadcast")
            else:
                if tuple(n.left_keys) in part[id(l_new)]:
                    elided[0] += 1
                else:
                    l_new = add_exchange(l_new, n.left_keys, "hash")
                if tuple(n.right_keys) in part[id(r_new)]:
                    elided[0] += 1
                else:
                    r_new = add_exchange(r_new, n.right_keys, "hash")
            kids = [l_new, r_new]
        elif isinstance(n, HashAggregate) and n.keys:
            (c_new,) = kids
            if isinstance(c_new, Exchange) and c_new.how == "hash":
                pass                    # authored boundary, keep it
            elif part_satisfies(part[id(c_new)], n.keys):
                elided[0] += 1          # input already co-locates groups
            else:
                kids = [add_exchange(c_new, n.keys, "hash")]
        node2 = (_with_children(n, tuple(kids))
                 if any(k is not c for k, c in zip(kids, n.children)) else n)
        sharded[id(node2)] = on_mesh
        part[id(node2)] = (transfer_part(
            node2, [part[id(k)] for k in node2.children])
            if on_mesh else frozenset())
        memo[id(n)] = node2
        return node2

    new_root = go(root)
    if sharded[id(new_root)]:
        new_root = add_exchange(new_root, (), "gather")   # the sink

    for node in _toposort(new_root):
        if isinstance(node, Exchange) and node.how != "identity":
            if node.how == "gather":
                spec = "local (gather)"
            elif node.how == "broadcast":
                spec = f"replicated@{n_peers}"
            else:
                spec = f"hash[{','.join(node.keys)}]@{n_peers}"
        elif not sharded.get(id(node), False):
            spec = "local"
        elif part.get(id(node)):
            keys = min(part[id(node)])
            spec = f"hash[{','.join(keys)}]@{n_peers}"
        else:
            spec = f"rows@{n_peers}"
        report.sharding[node.label] = spec
    report.exchanges = stats
    report.exchanges_elided = elided[0]
    return new_root, sum(stats.values())


# ---- fall-back diagnostics (analysis/verifier.py, docs/analysis.md) ---------

def _plan_error(root: PlanNode, bound=None) -> Optional[PlanValidationError]:
    """Re-validate a rewritten root; the schema error (None when clean).
    Plan construction routes through the static verifier, so the error
    carries structured violations naming the invariant and node. `bound`
    matters: a Scan with no declared schema resolves only against the
    bound tables, so without it an invalid rewrite over such a plan
    validates vacuously here and detonates later inside a DIFFERENT
    rule's schema resolution — the victim, not the culprit."""
    try:
        p = Plan(root)
        if bound:
            p.resolve_schemas(bound)
    except PlanValidationError as e:
        return e
    return None


def _diagnose(rule: str, err: PlanValidationError) -> Dict:
    """The (rule, node, invariant, message) fall-back record. Verifier
    errors carry structured violations; a bare PlanValidationError falls
    back to parsing the leading `Kind#id:` label convention."""
    violations = getattr(err, "violations", None)
    if violations:
        v = violations[0]
        return {"rule": rule, "node": v.node, "invariant": v.invariant,
                "message": v.message}
    msg = str(err)
    head = msg.split(":", 1)[0]
    node = head if "#" in head and " " not in head else ""
    return {"rule": rule, "node": node, "invariant": "schema",
            "message": msg}


def _fall_back(plan: Plan, report: OptimizeReport):
    """Discard the rewrite and run the authored plan. The report must
    describe what RAN, so the discarded rewrite's counts are zeroed: a
    parity gate reading rules_fired/pruned_columns would otherwise
    celebrate rewrites that never executed. `report.fallback` (set by the
    caller) survives — it describes why the rewrite was discarded."""
    report.fell_back = True
    report.rules = {name: 0 for name in RULE_NAMES}
    report.pruned_columns = 0
    report.pruned_bytes_est = 0
    report.exchanges = {}
    report.exchanges_elided = 0
    report.sharding = {}
    report.decision_sources = {}
    report.fingerprint = report.source_fingerprint
    return plan, report


def _attribute_fallback(plan: Plan, bound, bound_rows, float_inputs,
                        streaming, mesh_peers,
                        err: PlanValidationError,
                        stats=None, backend=None,
                        input_dtypes=None) -> Dict:
    """Post-hoc attribution for the validate-or-fall-back net: re-run the
    pipeline from the authored root, re-validating after every rule that
    rewrites, to name the rule/node/invariant that produced the invalid
    DAG. Only runs on the (defensively impossible) fall-back path, so the
    duplicated rule work costs nothing in the common case. `stats`/
    `backend`/`input_dtypes` replay the SAME adaptive estimates and
    certified bounds the failing pipeline consumed — attribution must
    reproduce the rewrite it is naming."""
    scratch = OptimizeReport(rules={name: 0 for name in RULE_NAMES})
    root = plan.root
    for _ in range(MAX_PASSES):
        pass_hits = 0
        for name, rule in _RULES:
            ctx = _Ctx(root, bound, bound_rows, scratch, float_inputs,
                       streaming, stats, backend, input_dtypes)
            try:
                new_root, n = rule(root, ctx)
            except PlanValidationError as bad:
                return _diagnose(name, bad)   # the rule itself blew up
            if new_root is not root:
                bad = _plan_error(new_root, bound)
                if bad is not None:
                    return _diagnose(name, bad)
            root = new_root
            pass_hits += n
        if not pass_hits:
            break
    if mesh_peers is not None and mesh_peers > 1:
        ctx = _Ctx(root, bound, bound_rows, scratch, float_inputs,
                   streaming, stats, backend, input_dtypes)
        try:
            new_root, _ = _plan_exchanges(root, ctx, mesh_peers)
        except PlanValidationError as bad:
            return _diagnose("exchange_planning", bad)
        bad = _plan_error(new_root, bound)
        if bad is not None:
            return _diagnose("exchange_planning", bad)
    return _diagnose("unknown", err)


# ---- pipeline ---------------------------------------------------------------

def optimize(plan: Plan,
             bound: Optional[Dict[str, Tuple[str, ...]]] = None,
             bound_rows: Optional[Dict[str, int]] = None,
             max_passes: int = MAX_PASSES,
             float_inputs: bool = False,
             streaming_sources=frozenset(),
             mesh_peers: Optional[int] = None,
             verify_rules: bool = False,
             stats=None,
             backend: Optional[str] = None,
             input_dtypes: Optional[Dict[str, Dict]] = None
             ) -> Tuple[Plan, OptimizeReport]:
    """Run the rule pipeline to fixpoint over `plan`. `bound` maps scan
    source -> actual column names and `bound_rows` -> actual row counts
    (and "filter:<subtree fingerprint>" -> the rows a filter keeps of a
    small bound table, where the caller counted them; execute() passes
    both; explain-time callers may pass neither and the schema/estimate-
    dependent rules degrade gracefully). `float_inputs`
    disables the build_side rule (execute() sets it when any bound column
    is floating point — fp reductions are not reorder-exact).
    `streaming_sources` names the scans bound to streaming (parquet)
    sources this execution — the scan_pruning rule fires only for those
    (a Scan carrying its own `parquet` binding qualifies regardless).
    `mesh_peers` (the meshed eager executor passes its mesh width) runs
    the `exchange_planning` rule once AFTER the fixpoint: Exchange(hash|
    broadcast|gather) boundaries are inserted/elided for the distributed
    tier (docs/distributed.md) — after, because the logical rules must
    not thrash against the physical boundary nodes they'd have to move
    through. `verify_rules` (the executor passes
    `config.verify_plans()`, on in tests) re-validates EVERY rule's
    output as it lands instead of only net-validating the pipeline's end
    state — the first invalid rewrite falls back immediately with a
    precise (rule, node, invariant) diagnostic in `report.fallback`.
    `stats` (a plan/stats.StatsStore) + `backend` make the estimator
    observation-driven (docs/adaptive.md): recorded subtree
    cardinalities for `backend` override the static estimate chain, and
    every build-side/exchange decision stamps its source on
    `report.decision_sources`. With stats=None (the
    SPARK_RAPIDS_TPU_STATS=off path) decisions are byte-identical to
    the static pipeline. `input_dtypes` (source -> {column: DType})
    enables the resource certifier's BYTE bounds
    (analysis/footprint.py): broadcast-join legality becomes a proven
    byte ceiling (`SPARK_RAPIDS_TPU_BROADCAST_BYTES`) and estimator
    dead-ends fall back to certified rows-hi bounds with a
    `certified:<bound>` decision source.
    Returns the optimized Plan (the SAME object when nothing fired) +
    the report."""
    report = OptimizeReport(rules={name: 0 for name in RULE_NAMES})
    report.source_fingerprint = plan.fingerprint
    streaming = frozenset(streaming_sources)
    root = plan.root
    try:
        for p in range(max_passes):
            pass_hits = 0
            for name, rule in _RULES:
                ctx = _Ctx(root, bound, bound_rows, report, float_inputs,
                           streaming, stats, backend, input_dtypes)
                new_root, n = rule(root, ctx)
                if verify_rules and new_root is not root:
                    # post-optimize assertion, per rule: every rule's
                    # output must re-validate — the first invalid rewrite
                    # names itself instead of hiding behind the
                    # end-of-pipeline net
                    bad = _plan_error(new_root, bound)
                    if bad is not None:
                        report.passes = p + 1
                        report.fallback = _diagnose(name, bad)
                        return _fall_back(plan, report)
                root = new_root
                report.rules[name] += n
                pass_hits += n
            report.passes = p + 1
            if not pass_hits:
                break
        meshed = mesh_peers is not None and mesh_peers > 1
        stays = mesh_local_reason(_toposort(root)) if meshed else None
        if stays is not None:
            report.decision_sources[f"{stays[0]}/mesh"] = stays[1]
        elif meshed:
            ctx = _Ctx(root, bound, bound_rows, report, float_inputs,
                       streaming, stats, backend, input_dtypes)
            new_root, n = _plan_exchanges(root, ctx, mesh_peers)
            if verify_rules and new_root is not root:
                bad = _plan_error(new_root, bound)
                if bad is not None:
                    report.fallback = _diagnose("exchange_planning", bad)
                    return _fall_back(plan, report)
            root = new_root
            report.rules["exchange_planning"] += n
    except PlanValidationError as err:
        # an invalid mid-pipeline rewrite can detonate inside a LATER
        # rule's schema resolution (not just at the end-of-pipeline
        # re-validation) — that too is a fall-back, not a query failure,
        # and _attribute_fallback re-runs rule-by-rule to name the
        # culprit rather than the victim
        report.fallback = _attribute_fallback(
            plan, bound, bound_rows, float_inputs, streaming, mesh_peers,
            err, stats, backend, input_dtypes)
        return _fall_back(plan, report)
    if root is plan.root:
        report.fingerprint = report.source_fingerprint
        return plan, report
    try:
        opt = Plan(root)
        if bound:
            # declared schemas alone under-validate scans bound only at
            # execute(); the fall-back net must catch what execution would
            opt.resolve_schemas(bound)
    except PlanValidationError as err:
        # defensive: a rewrite produced an invalid DAG — run the authored
        # plan rather than failing the query, with the culprit rule/node/
        # invariant attributed post-hoc (analysis/verifier.py vocabulary)
        report.fallback = _attribute_fallback(
            plan, bound, bound_rows, float_inputs, streaming, mesh_peers,
            err, stats, backend, input_dtypes)
        return _fall_back(plan, report)
    report.fingerprint = opt.fingerprint
    return opt, report


def explain_optimized(plan: Plan,
                      bound: Optional[Dict[str, Tuple[str, ...]]] = None,
                      bound_rows: Optional[Dict[str, int]] = None) -> str:
    """Authored tree, optimized tree, and the per-rule rewrite summary —
    the `explain(plan, optimized=True)` rendering."""
    opt, report = optimize(plan, bound, bound_rows)
    return "\n".join(["== authored ==", plan.explain(), "",
                      "== optimized ==", opt.explain(), "",
                      report.summary()])
