"""Property-based plan fuzzer: seeded random DAGs over all 12 node kinds.

The verifier (analysis/verifier.py) machine-checks invariants; this module
machine-GENERATES the plans to check them on. A `FuzzCase` is a seeded
random operator DAG (Scan, Filter, Project, FusedSelect, HashJoin,
HashAggregate, Window, Sort, TopK, Limit, Union, Exchange — the full node set,
including the optimizer-produced kinds, authored directly) plus the bound
tables it runs over. Every case must satisfy five properties:

1. the authored plan VERIFIES (generator correctness — schema, typing and
   pruning layers clean);
2. the optimizer's rewrite verifies (`verify_rewrite`: schema preserved,
   swap legality, rule side conditions) and never falls back;
3. (small plans — which all of these are) the optimized and unoptimized
   EAGER executions agree bit-for-bit, compacted row for row; a case
   whose unoptimized run raises must raise the same error class
   optimized (semantics preserved means errors too);
4. the plan executed TWICE under a fresh per-case stats store
   (plan/stats.py) agrees bit-for-bit between the cold and warm runs,
   error class included — adaptivity (observed-cardinality build sides,
   cap seeding, kernel tie-breaks) may change *how*, never *what*;
5. the resource certifier (analysis/footprint.py) is SOUND and
   MONOTONE: for every operator of every successful execution —
   unoptimized, optimized, cold AND warm — the observed row count lies
   inside the certified `[lo, hi]` interval and the observed eager
   bytes stay at or under the certified byte bound; and the optimizer
   may only keep or tighten the root's certified bounds (a rewrite
   that loosens a proof is a bug even when results agree).

Determinism is a contract: `gen_case(seed)` builds the same DAG (same
fingerprint) and the same table bytes every time — `random.Random(seed)`
only, no global RNG, no time — so the premerge corpus (fixed seeds, see
ci/premerge.sh) is reproducible and a nightly failure replays from its
seed alone. CI knobs: `python -m spark_rapids_tpu.analysis.fuzz --start S
--count N [--max-ops K] [--no-exec] [--cpu]`; the nightly deep sweep
(ci/nightly.sh) runs 200 seeds from 1000 through this same entry point.
"""
from __future__ import annotations

import dataclasses
import random
from typing import Dict, List, Optional, Tuple

from ..plan.expr import (Expr, coalesce, col, is_not_null, is_null, lit,
                         scalar_max, scalar_min, scalar_sum, when)
from ..plan.nodes import (Exchange, Filter, FusedSelect, HashAggregate,
                          HashJoin, JOIN_TYPES, Limit, PAIRING_JOINS,
                          PlanNode, Scan, Sort, TopK,
                          Union, WINDOW_OPS, Window)

ALL_KINDS = ("Scan", "Filter", "Project", "FusedSelect", "HashJoin",
             "HashAggregate", "Window", "Sort", "TopK", "Limit", "Union",
             "Exchange")

_GLOBAL_AGGS = ("sum", "count", "size")      # empty-relation-safe
_KEYED_AGGS = ("sum", "count", "min", "max", "mean", "size")


@dataclasses.dataclass
class FuzzCase:
    seed: int
    plan: object                 # plan.builder.Plan
    tables: Dict[str, object]    # source -> columnar.Table
    kinds: Tuple[str, ...]       # node kinds present, for coverage stats


@dataclasses.dataclass
class FuzzResult:
    seed: int
    verified: bool = True
    optimized_verified: bool = True
    executed: bool = False
    parity: Optional[bool] = None
    # property 4 (docs/adaptive.md): cold-vs-warm bit-exact parity under
    # the stats store — adaptivity may change HOW, never WHAT (errors
    # included)
    adaptive_parity: Optional[bool] = None
    # property 5 (docs/analysis.md): certifier soundness (observed rows/
    # bytes inside the certified bounds, every op, every run) and
    # monotonicity (optimized root bound <= authored root bound)
    cert_sound: Optional[bool] = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return (self.verified and self.optimized_verified
                and self.error is None and self.parity is not False
                and self.adaptive_parity is not False
                and self.cert_sound is not False)


# ---- deterministic relation/expression generation ---------------------------

class _Rel:
    """Generator-side relation: the node plus its (name -> tag) schema,
    where tag is 'i' (int64), 'f' (float64) or 'b' (bool), and a crude
    row estimate to keep join products bounded."""

    __slots__ = ("node", "schema", "est")

    def __init__(self, node: PlanNode, schema: List[Tuple[str, str]],
                 est: float):
        self.node = node
        self.schema = list(schema)
        self.est = est

    def cols(self, tag=None) -> List[str]:
        return [n for n, t in self.schema if tag is None or t == tag]


def _gen_table(rng: random.Random, schema: List[Tuple[str, str]],
               n_rows: int):
    """Deterministic Table over the tagged schema. Int values are small
    (0..7) so joins and groupbys hit duplicates; floats are quarter-
    integers (exactly representable — parity comparisons stay exact)."""
    import jax.numpy as jnp
    import numpy as np
    from .. import dtypes
    from ..columnar import Column, Table
    cols, names = [], []
    for name, tag in schema:
        if tag == "i":
            data = np.asarray([rng.randrange(8) for _ in range(n_rows)],
                              dtype=np.int64)
            dt = dtypes.INT64
        elif tag == "f":
            data = np.asarray([rng.randrange(32) / 4.0
                               for _ in range(n_rows)], dtype=np.float64)
            dt = dtypes.FLOAT64
        else:
            data = np.asarray([rng.randrange(2) == 1
                               for _ in range(n_rows)], dtype=np.bool_)
            dt = dtypes.BOOL
        cols.append(Column(dtype=dt, length=n_rows, data=jnp.asarray(data)))
        names.append(name)
    return Table(cols, names=names)


def _gen_predicate(rng: random.Random, rel: _Rel, depth: int = 0) -> Expr:
    """Random boolean expression over the relation: comparisons of int/
    float columns against in-range literals, conjunctions/disjunctions/
    negations, the odd scalar-aggregate subquery."""
    numeric = rel.cols("i") + rel.cols("f")
    if not numeric:
        return lit(True)
    if depth < 2 and rng.random() < 0.35:
        op = rng.choice(("&", "|", "~"))
        a = _gen_predicate(rng, rel, depth + 1)
        if op == "~":
            return ~a
        b = _gen_predicate(rng, rel, depth + 1)
        return (a & b) if op == "&" else (a | b)
    name = rng.choice(numeric)
    c = col(name)
    cmp = rng.choice(("<", "<=", ">", ">=", "==", "!="))
    r = rng.random()
    if r < 0.12:
        sagg = rng.choice((scalar_max, scalar_min, scalar_sum))
        rhs: Expr = sagg(col(rng.choice(numeric)))
    elif r < 0.22:
        # null-aware: TRUE over a null (an outer join below supplies them)
        return rng.choice((is_null, is_not_null))(c)
    elif r < 0.27 and name in rel.cols("i"):
        c = coalesce(c, rng.randrange(8))
        rhs = lit(rng.randrange(8))
    else:
        is_f = name in rel.cols("f")
        rhs = lit(rng.randrange(32) / 4.0 if is_f else rng.randrange(8))
    return {"<": c < rhs, "<=": c <= rhs, ">": c > rhs, ">=": c >= rhs,
            "==": c == rhs, "!=": c != rhs}[cmp]


def _gen_exprs(rng: random.Random, rel: _Rel, fresh) -> Tuple[
        List[Tuple[str, Expr]], List[Tuple[str, str]]]:
    """Projection list: a random column subset (kept under their own
    names) plus up to one derived arithmetic column."""
    keep = [nt for nt in rel.schema if rng.random() < 0.75]
    if not keep:
        keep = [rng.choice(rel.schema)]
    exprs = [(n, col(n)) for n, _ in keep]
    schema = list(keep)
    numeric = rel.cols("i")
    if numeric and rng.random() < 0.5:
        name = fresh("d")
        a, b = rng.choice(numeric), rng.choice(numeric)
        op = rng.choice(("+", "-", "*"))
        e = {"+": col(a) + col(b), "-": col(a) - col(b),
             "*": col(a) * lit(rng.randrange(1, 4))}[op]
        exprs.append((name, e))
        schema.append((name, "i"))
    elif numeric and rng.random() < 0.3:
        name = fresh("w")
        a, b = rng.choice(numeric), rng.choice(numeric)
        exprs.append((name, when(_gen_predicate(rng, rel, 2), col(a),
                                 coalesce(col(b), 0))))
        schema.append((name, "i"))
    return exprs, schema


def gen_case(seed: int, *, max_ops: int = 8,
             allow_floats: bool = True) -> FuzzCase:
    """Build one deterministic random case. The generator composes only
    schema-correct operators (the property under test is the OPTIMIZER
    and the engine, not the builder's rejection paths), but draws from
    the full node vocabulary, including DAG-shared subtrees (self-union,
    shared join inputs)."""
    from ..plan.builder import Plan
    rng = random.Random(seed)
    counter = [0]

    def fresh(prefix: str) -> str:
        counter[0] += 1
        return f"{prefix}{counter[0]}"

    n_sources = rng.randrange(1, 4)
    tables: Dict[str, object] = {}
    rels: List[_Rel] = []
    for i in range(n_sources):
        src = f"s{i}"
        n_cols = rng.randrange(2, 5)
        schema = []
        for j in range(n_cols):
            r = rng.random()
            tag = ("f" if allow_floats and r < 0.18 else
                   "b" if r < 0.28 else "i")
            schema.append((f"{src}_c{j}", tag))
        n_rows = rng.randrange(6, 40)
        tables[src] = _gen_table(rng, schema, n_rows)
        # est_rows hint on some scans feeds the build_side rule
        est = n_rows if rng.random() < 0.5 else None
        rels.append(_Rel(Scan(src, tuple(n for n, _ in schema),
                              est_rows=est), schema, float(n_rows)))

    for _ in range(rng.randrange(3, max_ops + 1)):
        op = rng.choices(
            ("filter", "project", "fused", "aggregate", "sort", "topk",
             "limit", "union", "join", "exchange"),
            weights=(18, 14, 8, 12, 8, 5, 7, 7, 14, 7))[0]
        idx = rng.randrange(len(rels))
        rel = rels[idx]
        if op == "filter":
            pred = _gen_predicate(rng, rel)
            out = _Rel(Filter(rel.node, pred), rel.schema,
                       max(rel.est * 0.6, 1.0))
        elif op == "project":
            from ..plan.nodes import Project
            exprs, schema = _gen_exprs(rng, rel, fresh)
            out = _Rel(Project(rel.node, tuple(exprs)), schema, rel.est)
        elif op == "fused":
            exprs, schema = _gen_exprs(rng, rel, fresh)
            out = _Rel(FusedSelect(rel.node, _gen_predicate(rng, rel),
                                   tuple(exprs)), schema,
                       max(rel.est * 0.6, 1.0))
        elif op == "aggregate":
            numeric = rel.cols("i") + rel.cols("f")
            if not numeric:
                continue
            keyed = rel.cols("i") and rng.random() < 0.8
            keys = (tuple(rng.sample(rel.cols("i"),
                                     rng.randrange(1, min(3, len(
                                         rel.cols("i"))) + 1)))
                    if keyed else ())
            ops = _KEYED_AGGS if keys else _GLOBAL_AGGS
            aggs, schema = [], [(k, dict(rel.schema)[k]) for k in keys]
            for _ in range(rng.randrange(1, 3)):
                c = rng.choice(numeric)
                o = rng.choice(ops)
                name = fresh("a")
                aggs.append((c, o, name))
                tag = ("i" if o in ("count", "size") else
                       "f" if o == "mean" or dict(rel.schema)[c] == "f"
                       else "i")
                schema.append((name, tag))
            out = _Rel(HashAggregate(rel.node, keys, tuple(aggs)),
                       schema, max(rel.est / 4, 1.0) if keys else 1.0)
        elif op in ("sort", "topk"):
            sortable = rel.cols("i") + rel.cols("f")
            if not sortable:
                continue
            keys = tuple(rng.sample(sortable,
                                    rng.randrange(1, min(2, len(sortable))
                                                  + 1)))
            asc = tuple(rng.random() < 0.7 for _ in keys)
            if op == "sort":
                out = _Rel(Sort(rel.node, keys, asc), rel.schema, rel.est)
            else:
                out = _Rel(TopK(rel.node, keys, asc, rng.randrange(0, 12)),
                           rel.schema, 12.0)
        elif op == "limit":
            out = _Rel(Limit(rel.node, rng.randrange(0, 24)), rel.schema,
                       24.0)
        elif op == "union":
            # self-union through two different filters: same schema by
            # construction, and the child is DAG-SHARED (executes once)
            p1 = _gen_predicate(rng, rel)
            p2 = _gen_predicate(rng, rel)
            out = _Rel(Union((Filter(rel.node, p1),
                              Filter(rel.node, p2))), rel.schema,
                       rel.est * 1.2)
        elif op == "join":
            partners = [r for r in rels
                        if r is not rel and r.cols("i")
                        and not (set(r.cols()) & set(rel.cols()))]
            if not partners or not rel.cols("i"):
                continue
            other = rng.choice(partners)
            if rel.est * other.est > 4000:
                continue
            lk = (rng.choice(rel.cols("i")),)
            rk = (rng.choice(other.cols("i")),)
            how = rng.choices(JOIN_TYPES, weights=(3, 1, 1, 1, 1))[0]
            schema = (rel.schema + other.schema if how in PAIRING_JOINS
                      else list(rel.schema))
            est = (rel.est * other.est / 4 if how in PAIRING_JOINS
                   else rel.est * 0.6)
            if how == "left_outer":     # every left row comes out
                est = max(est, rel.est)
            elif how == "full_outer":   # and every right row
                est = max(est, rel.est + other.est)
            out = _Rel(HashJoin(rel.node, other.node, lk, rk, how=how),
                       schema, max(est, 1.0))
        else:   # exchange: hash on an int column, or the identity marker
            if rel.cols("i") and rng.random() < 0.7:
                out = _Rel(Exchange(rel.node,
                                    (rng.choice(rel.cols("i")),)),
                           rel.schema, rel.est)
            else:
                out = _Rel(Exchange(rel.node, ()), rel.schema, rel.est)
        rels[idx] = out

    # a Window over one of the relations, one case in four, drawn from a
    # generator of its own: the cases of every seed drawn before the node
    # existed stay what they were. Keys and values are integer columns
    # (nullable, with ties: rows that tie take the frame in the child's
    # order, which no rewrite may change below a window)
    wrng = random.Random(seed * 7919 + 47)
    if wrng.random() < 0.25:
        idx = wrng.randrange(len(rels))
        rel = rels[idx]
        ints = rel.cols("i")
        if ints:
            part = tuple(wrng.sample(ints, wrng.randrange(0, min(
                2, len(ints)) + 1)))
            order = (wrng.choice(ints),)
            fns = tuple((fresh("v"), wrng.choice(WINDOW_OPS),
                         wrng.choice(ints))
                        for _ in range(wrng.randrange(1, 3)))
            rels[idx] = _Rel(
                Window(rel.node, part, order, fns,
                       (wrng.random() < 0.7,)),
                rel.schema + [(n, "i") for n, _, _ in fns], rel.est)
            if wrng.random() < 0.7:     # mostly the plan's root
                rels = [rels[idx]]
    root = rng.choice(rels)
    plan = Plan(root.node)
    return FuzzCase(seed=seed, plan=plan, tables=dict(tables),
                    kinds=tuple(sorted({n.kind for n in plan.nodes})))


# ---- properties -------------------------------------------------------------

def _cert_soundness(case: FuzzCase, res, bound, input_dtypes,
                    input_nullable) -> Optional[str]:
    """Property 5's per-run half: certify the EXECUTED plan and hold
    every operator's observed metrics inside the certified bounds via
    the single-sourced inequality (`footprint.check_observed` — the
    nightly gate runs the SAME check). Returns the first violation as a
    string, None when sound."""
    from .footprint import certify, check_observed
    cert = certify(res.plan, bound=bound,
                   bound_rows={n: t.num_rows
                               for n, t in case.tables.items()},
                   input_dtypes=input_dtypes,
                   input_nullable=input_nullable)
    return check_observed(cert, res)


def _cert_monotonicity(case: FuzzCase, opt, bound, input_dtypes,
                       input_nullable) -> Optional[str]:
    """Property 5's rewrite half: the optimized plan's certified ROOT
    bounds must not exceed the authored plan's — every rule preserves or
    shrinks the relation it proves things about, so a looser optimized
    proof means a certifier or rule bug."""
    from .footprint import certify
    kw = dict(bound=bound,
              bound_rows={n: t.num_rows for n, t in case.tables.items()},
              input_dtypes=input_dtypes, input_nullable=input_nullable)
    a = certify(case.plan, **kw).root
    o = certify(opt, **kw).root
    if a.rows_hi is not None and (
            o.rows_hi is None or o.rows_hi > a.rows_hi):
        return (f"optimized root rows hi {o.rows_hi} exceeds authored "
                f"{a.rows_hi}")
    # None-after-finite is a LOOSENED proof, same as the rows branch: a
    # rewrite that makes the root's bytes uncertifiable weakens the
    # admission and broadcast-legality gates even when results agree
    if a.out_bytes_hi is not None and (
            o.out_bytes_hi is None or o.out_bytes_hi > a.out_bytes_hi):
        return (f"optimized root bytes hi {o.out_bytes_hi} exceeds "
                f"authored {a.out_bytes_hi}")
    return None


def run_case(case: FuzzCase, *, execute: bool = True) -> FuzzResult:
    """Check the five fuzz properties on one case (see module doc).
    Never raises for a property FAILURE (the result carries it); raises
    only on generator bugs like unbuildable plans."""
    from ..plan.executor import PlanExecutor, _input_has_floats
    from ..plan.optimizer import optimize
    from .verifier import verify, verify_rewrite
    res = FuzzResult(seed=case.seed)
    bound = {n: tuple(t.names) for n, t in case.tables.items()}
    input_dtypes = {
        n: {cn: c.dtype for cn, c in zip(t.names, t.columns)}
        for n, t in case.tables.items()}
    floats = any(_input_has_floats(t) for t in case.tables.values())

    rep = verify(case.plan, bound=bound, input_dtypes=input_dtypes,
                 float_inputs=floats)
    if not rep.ok:
        res.verified = False
        res.error = f"authored plan failed verify: {rep.violations[0]}"
        return res

    bound_rows = {n: t.num_rows for n, t in case.tables.items()}
    opt, report = optimize(case.plan, bound, bound_rows,
                           float_inputs=floats, verify_rules=True)
    if report.fell_back:
        res.optimized_verified = False
        res.error = f"optimizer fell back: {report.fallback}"
        return res
    rep = verify_rewrite(case.plan, opt, bound=bound,
                         input_dtypes=input_dtypes, float_inputs=floats,
                         report=report)
    if not rep.ok:
        res.optimized_verified = False
        res.error = f"optimized plan failed verify: {rep.violations[0]}"
        return res

    # property 5 (rewrite half): the optimizer may only keep or tighten
    # the root's certified bounds
    from .footprint import table_metadata
    _, input_nullable = table_metadata(case.tables)
    mono = _cert_monotonicity(case, opt, bound, input_dtypes,
                              input_nullable)
    if mono is not None:
        res.cert_sound = False
        res.error = f"cert monotonicity broke: {mono}"
        return res
    res.cert_sound = True

    if not execute:
        return res
    res.executed = True
    from ..plan import stats as stats_mod
    outs = {}
    cert_runs = []               # successful PlanResults for property 5
    # properties 1-3 measure the STATIC engine: scope adaptivity off, or
    # a premerge/nightly corpus run (no pytest conftest, stats default
    # ON) would record seed N's plans into the process-default store and
    # run later parity checks warm — a failing seed replayed standalone
    # would then see different optimizer decisions and not reproduce
    with stats_mod.scoped_store(None):
        for optimized in (False, True):
            ex = PlanExecutor(mode="eager", optimize=optimized)
            try:
                r = ex.execute(case.plan, dict(case.tables))
                outs[optimized] = ("ok", r.compact().to_pydict())
                cert_runs.append(r)
            except Exception as e:     # parity includes error parity
                outs[optimized] = ("err", type(e).__name__)
    res.parity = outs[False] == outs[True]
    if not res.parity:
        res.error = (f"eager parity broke: unoptimized={outs[False]!r} "
                     f"optimized={outs[True]!r}")
        return res

    # property 4: the same plan twice under a FRESH stats store — the
    # first run records, the second consumes (cap seeds, observed
    # cardinalities, kernel tie-breaks). Bit-exact parity, error class
    # included: adaptivity may change how a plan executes, never what it
    # returns (docs/adaptive.md). A fresh scoped store per case keeps
    # the corpus deterministic regardless of what ran before.
    runs = []
    # path="": never inherit SPARK_RAPIDS_TPU_STATS_PATH — a persisted
    # file would pre-warm the "cold" run and collect fuzz-plan garbage
    with stats_mod.scoped_store(stats_mod.StatsStore(capacity=32,
                                                     path="")):
        for _ in range(2):
            ex = PlanExecutor(mode="eager", optimize=True)
            try:
                r = ex.execute(case.plan, dict(case.tables))
                runs.append(("ok", r.compact().to_pydict()))
                cert_runs.append(r)
            except Exception as e:
                runs.append(("err", type(e).__name__))
    res.adaptive_parity = runs[0] == runs[1]
    if not res.adaptive_parity:
        res.error = (f"adaptive parity broke: cold={runs[0]!r} "
                     f"warm={runs[1]!r}")
        return res

    # property 5 (soundness half): every successful run — unoptimized,
    # optimized, cold and warm — stays inside the certified bounds of ITS
    # executed plan (cold and warm may have rewritten differently)
    for r in cert_runs:
        bad = _cert_soundness(case, r, bound, input_dtypes,
                              input_nullable)
        if bad is not None:
            res.cert_sound = False
            res.error = f"cert soundness broke: {bad}"
            return res
    return res


def run_corpus(seeds, *, execute: bool = True, max_ops: int = 8,
               verbose: bool = False) -> Dict:
    """Run gen+check over a seed list; summary dict with per-seed
    failures and the node-kind coverage of the corpus."""
    results: List[FuzzResult] = []
    kinds = set()
    for seed in seeds:
        case = gen_case(seed, max_ops=max_ops)
        kinds.update(case.kinds)
        r = run_case(case, execute=execute)
        results.append(r)
        if verbose:
            status = "ok" if r.ok else f"FAIL ({r.error})"
            print(f"  seed {seed}: {len(case.plan.nodes)} nodes "
                  f"[{', '.join(case.kinds)}] -> {status}")
    failures = [r for r in results if not r.ok]
    return {
        "cases": len(results),
        "executed": sum(1 for r in results if r.executed),
        "kinds_covered": tuple(sorted(kinds)),
        "failures": [{"seed": r.seed, "error": r.error} for r in failures],
    }


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="plan fuzzer: verify + optimize + eager-parity over "
                    "seeded random DAGs (docs/analysis.md)")
    ap.add_argument("--start", type=int, default=0)
    ap.add_argument("--count", type=int, default=24)
    ap.add_argument("--max-ops", type=int, default=8)
    ap.add_argument("--no-exec", action="store_true",
                    help="verify/optimize only (skip the parity runs)")
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU backend before jax initializes")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    seeds = range(args.start, args.start + args.count)
    summary = run_corpus(seeds, execute=not args.no_exec,
                         max_ops=args.max_ops, verbose=args.verbose)
    print(f"plan fuzz: {summary['cases']} case(s), "
          f"{summary['executed']} executed, kinds covered: "
          f"{', '.join(summary['kinds_covered'])}")
    if summary["failures"]:
        for f in summary["failures"]:
            print(f"  FAIL seed {f['seed']}: {f['error']}")
        return 1
    print("plan fuzz OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
