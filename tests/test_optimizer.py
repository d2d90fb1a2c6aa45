"""Rule-based plan optimizer (plan/optimizer.py): every rule individually,
the full pipeline on the four NDS plans with optimizer-on/off parity in
both executor tiers, idempotence, and fingerprint-keyed program reuse.

Parity chains: test_plan_nds.py already runs the NDS plans with the
optimizer ON (the default) against the pandas references of
examples/nds.py; here the OFF runs close the loop (on == off == pandas).
The full 4-query capped on/off matrix is `slow` (one XLA trace per
variant) and runs in ci/premerge.sh and ci/nightly.sh; the timed tier
keeps the cheaper eager matrix and one capped query.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, Table, dtypes
from spark_rapids_tpu.plan import (FusedSelect, Limit, PlanBuilder,
                                   PlanExecutor, Project, Scan, TopK,
                                   col, lit, optimize, plan_fingerprint,
                                   scalar_max)
from spark_rapids_tpu.plan.expr import Literal, fold, has_scalar_agg
from spark_rapids_tpu.plan.nodes import Filter, HashJoin


def _col(a, validity=None):
    a = np.asarray(a, dtype=np.int64)
    return Column(dtype=dtypes.INT64, length=len(a), data=jnp.asarray(a),
                  validity=None if validity is None
                  else jnp.asarray(validity, bool))


def _tables(n=2000, seed=0):
    rng = np.random.default_rng(seed)
    sales = Table([_col(rng.integers(0, 50, n)),
                   _col(rng.integers(1, 100, n)),
                   _col(rng.integers(0, 9, n))], names=["k", "v", "junk"])
    dims = Table([_col(np.arange(50)), _col(np.arange(50) % 3),
                  _col(np.arange(50) * 7)], names=["dk", "grp", "extra"])
    return sales, dims


def _kinds(plan):
    return [n.kind for n in plan.nodes]


def _run_pair(plan, inputs, mode="eager", caps=None):
    """(optimizer-on result, optimizer-off result) on fresh executors."""
    on = PlanExecutor(mode=mode, caps=caps, optimize=True).execute(
        plan, inputs)
    off = PlanExecutor(mode=mode, caps=caps, optimize=False).execute(
        plan, inputs)
    return on, off


# ---- expression constant folding (expr.fold) --------------------------------

class TestFold:
    def test_literal_arithmetic_and_comparisons(self):
        assert fold(lit(2) + lit(3)).value == 5
        assert fold(lit(2) * lit(3) - lit(1)).value == 5
        assert fold(lit(1) < lit(2)).value is True
        assert fold((lit(1) < lit(2)) & (lit(3) == lit(4))).value is False

    def test_bool_invert_matches_array_semantics(self):
        # python's ~True is -2; the jnp evaluation is logical not
        assert fold(~lit(True)).value is False
        assert fold(~lit(3)).value == ~3

    def test_partial_fold_keeps_column_refs(self):
        e = fold((lit(2) + lit(3)) * col("v"))
        assert isinstance(e.left, Literal) and e.left.value == 5
        assert e.right.references() == {"v"}

    def test_identity_when_nothing_folds(self):
        e = col("a") + col("b")
        assert fold(e) is e

    def test_int64_overflow_does_not_fold(self):
        # folded python arithmetic must keep matching runtime int64: a
        # result outside int64 stays unfolded (runtime wraps; a folded
        # out-of-range Literal would raise at evaluate instead)
        from spark_rapids_tpu.plan.expr import BinOp
        e = fold(lit(2 ** 62) + lit(2 ** 62))
        assert isinstance(e, BinOp)

    def test_scalar_agg_of_literal_never_folds(self):
        # over an all-dead capped relation, max(lit(5)) reduces to the
        # identity, not 5 — the aggregate depends on the live-row set
        from spark_rapids_tpu.plan.expr import ScalarAgg
        assert isinstance(fold(scalar_max(lit(5))), ScalarAgg)

    def test_has_scalar_agg(self):
        assert has_scalar_agg(lit(2) * scalar_max(col("v")))
        assert not has_scalar_agg(lit(2) * col("v"))


# ---- rule: constant folding + trivial predicates ----------------------------

class TestConstantFolding:
    def test_filter_true_drops(self):
        b = PlanBuilder()
        plan = b.scan("t", schema=["v"]).filter(lit(1) < lit(2)).build()
        opt, rep = optimize(plan)
        assert rep.rules["constant_folding"] >= 1
        assert "Filter" not in _kinds(opt)
        t = Table([_col([1, 2, 3])], names=["v"])
        on, off = _run_pair(plan, {"t": t})
        assert on.table.to_pydict() == off.table.to_pydict()

    def test_filter_false_short_circuits_to_empty(self):
        b = PlanBuilder()
        plan = (b.scan("t", schema=["v"])
                 .filter(col("v") > 0)
                 .filter(lit(1) > lit(2))
                 .build())
        opt, rep = optimize(plan)
        assert "Limit" in _kinds(opt)           # Filter(false) -> Limit(0)
        t = Table([_col([1, 2, 3])], names=["v"])
        on, off = _run_pair(plan, {"t": t})
        assert on.table.to_pydict() == off.table.to_pydict() == {"v": []}
        onc = PlanExecutor(mode="capped").execute(plan, {"t": t})
        assert onc.compact().to_pydict() == {"v": []}

    def test_literal_subtree_folds_inside_predicate(self):
        b = PlanBuilder()
        plan = (b.scan("t", schema=["v"])
                 .filter(col("v") > lit(2) + lit(3)).build())
        opt, rep = optimize(plan)
        assert rep.rules["constant_folding"] == 1
        f = next(n for n in opt.nodes if isinstance(n, Filter))
        assert "(v > 5)" in repr(f.predicate)


# ---- rule: predicate pushdown -----------------------------------------------

class TestPredicatePushdown:
    def test_below_project_rewrites_through_column_refs(self):
        b = PlanBuilder()
        plan = (b.scan("t", schema=["a", "v"])
                 .project({"a": col("a"), "w": col("v") * 2})
                 .filter(col("a") > 5)
                 .build())
        opt, rep = optimize(plan)
        assert rep.rules["predicate_pushdown"] == 1
        # pushed below, then fused: the filter runs against the scan
        assert _kinds(opt) == ["Scan", "FusedSelect"]
        t = Table([_col([3, 7, 9]), _col([1, 2, 3])], names=["a", "v"])
        on, off = _run_pair(plan, {"t": t})
        assert on.table.to_pydict() == off.table.to_pydict()

    def test_not_pushed_below_scalar_agg_projection(self):
        # pushing the filter below would shrink the row set the project's
        # scalar_sum reduces over: 100 (all rows) must not become 70
        from spark_rapids_tpu.plan import scalar_sum
        b = PlanBuilder()
        plan = (b.scan("t", schema=["k", "v"])
                 .project({"k": col("k"), "s": scalar_sum(col("v"))})
                 .filter(col("k") > 1)
                 .build())
        opt, rep = optimize(plan)
        assert rep.rules["predicate_pushdown"] == 0
        t = Table([_col([0, 1, 2, 3]), _col([10, 20, 30, 40])],
                  names=["k", "v"])
        on, off = _run_pair(plan, {"t": t})
        assert on.table.to_pydict() == off.table.to_pydict() \
            == {"k": [2, 3], "s": [100, 100]}

    def test_not_pushed_through_computed_projection(self):
        # w is a computed expr: substituting would re-evaluate it — skip
        b = PlanBuilder()
        plan = (b.scan("t", schema=["a", "v"])
                 .project({"w": col("v") * 2})
                 .filter(col("w") > 5)
                 .build())
        opt, rep = optimize(plan)
        assert rep.rules["predicate_pushdown"] == 0

    def test_below_union_copies_into_inputs(self):
        b = PlanBuilder()
        l = b.scan("l", schema=["v"])
        r = b.scan("r", schema=["v"])
        plan = l.union(r).filter(col("v") > 10).build()
        opt, rep = optimize(plan)
        assert rep.rules["predicate_pushdown"] == 1
        assert _kinds(opt).count("Filter") == 2   # one per union input
        inputs = {"l": Table([_col([5, 15])], names=["v"]),
                  "r": Table([_col([20, 5])], names=["v"])}
        on, off = _run_pair(plan, inputs)
        assert on.table.to_pydict() == off.table.to_pydict()

    def test_into_join_side(self):
        b = PlanBuilder()
        s = b.scan("s", schema=["k", "v"])
        d = b.scan("d", schema=["dk", "grp"])
        plan = (s.join(d, left_on="k", right_on="dk")
                 .filter(col("grp") == 1)        # right-side columns only
                 .filter(col("v") > 3)           # left-side columns only
                 .build())
        opt, rep = optimize(plan)
        assert rep.rules["predicate_pushdown"] >= 2
        join = next(n for n in opt.nodes if isinstance(n, HashJoin))
        assert any(isinstance(c, Filter) for c in (join.left, join.right)) \
            or any(isinstance(c, FusedSelect)
                   for c in (join.left, join.right))
        sales, dims = _tables(n=300)
        inputs = {"s": sales.select(["k", "v"]),
                  "d": dims.select(["dk", "grp"])}
        on, off = _run_pair(plan, inputs)
        assert on.table.to_pydict() == off.table.to_pydict()

    def test_shared_guard_survives_same_pass_child_rewrite(self):
        # the shared-node guard must hold even after the shared child was
        # rebuilt (fresh object id) earlier in the SAME pass: pushdown
        # rewrites the Filter(Union) BELOW the shared Project here, and
        # the Filter sitting ON the shared Project must still not push
        # through it — that would duplicate the shared projection
        b = PlanBuilder()
        u = b.scan("l", schema=["v"]).union(b.scan("r", schema=["v"]))
        inner = u.filter(col("v") > 0)        # rewritten below the share
        shared = inner.project({"v": col("v"), "w": col("v") * 2})
        plan = (shared.filter(col("v") > 5)
                .join(shared, left_on="v", right_on="v", how="left_semi")
                .build())
        opt, rep = optimize(plan)
        doubles = [n for n in opt.nodes if "(v * 2)" in n.describe()]
        assert len(doubles) == 1              # still ONE shared projection
        inputs = {"l": Table([_col([1, 6, -2])], names=["v"]),
                  "r": Table([_col([9, 4])], names=["v"])}
        on, off = _run_pair(plan, inputs)
        assert on.table.to_pydict() == off.table.to_pydict()

    def test_scalar_agg_predicate_never_moves_below_union(self):
        b = PlanBuilder()
        l = b.scan("l", schema=["v"])
        r = b.scan("r", schema=["v"])
        plan = (l.union(r)
                 .filter(col("v") >= scalar_max(col("v"))).build())
        opt, rep = optimize(plan)
        assert rep.rules["predicate_pushdown"] == 0
        inputs = {"l": Table([_col([5, 15])], names=["v"]),
                  "r": Table([_col([20, 5])], names=["v"])}
        on, off = _run_pair(plan, inputs)
        assert on.table.to_pydict() == off.table.to_pydict() == {"v": [20]}


# ---- rule: column pruning ---------------------------------------------------

class TestColumnPruning:
    def test_scan_narrows_and_bytes_shrink(self):
        sales, dims = _tables()
        b = PlanBuilder()
        s = b.scan("sales", schema=["k", "v", "junk"])
        d = b.scan("dims", schema=["dk", "grp", "extra"]) \
             .filter(col("grp") == 1)
        plan = (s.join(d, left_on="k", right_on="dk")
                 .aggregate(["grp"], [("v", "sum", "total")])
                 .build())
        opt, rep = optimize(plan, {"sales": ("k", "v", "junk"),
                                   "dims": ("dk", "grp", "extra")},
                            bound_rows={"sales": sales.num_rows,
                                        "dims": dims.num_rows})
        assert rep.pruned_columns >= 2 and rep.pruned_bytes_est > 0
        scans = [n for n in opt.nodes if isinstance(n, Scan)]
        assert {s.source: s.projection for s in scans} == {
            "sales": ("k", "v"), "dims": ("dk", "grp")}
        inputs = {"sales": sales, "dims": dims}
        on, off = _run_pair(plan, inputs)
        assert on.table.to_pydict() == off.table.to_pydict()
        scan_on = min(m["bytes_out"] for m in on.profile()
                      if m["kind"] == "Scan")
        scan_off = min(m["bytes_out"] for m in off.profile()
                       if m["kind"] == "Scan")
        assert scan_on < scan_off                 # junk never loaded

    def test_project_outputs_narrow(self):
        b = PlanBuilder()
        plan = (b.scan("t", schema=["a", "v"])
                 .project({"a": col("a"), "w": col("v") * 2,
                           "dead": col("v") * 3})
                 .aggregate(["a"], [("w", "sum", "s")])
                 .build())
        opt, rep = optimize(plan)
        proj = next(n for n in opt.nodes
                    if isinstance(n, (Project, FusedSelect)))
        assert [n for n, _ in proj.exprs] == ["a", "w"]
        t = Table([_col([1, 1, 2]), _col([10, 20, 30])], names=["a", "v"])
        on, off = _run_pair(plan, {"t": t})
        assert on.table.to_pydict() == off.table.to_pydict()

    def test_join_input_gets_narrowing_select(self):
        # the filter's predicate-only column must not cross the join
        b = PlanBuilder()
        s = b.scan("s", schema=["k", "v"])
        d = b.scan("d", schema=["dk", "grp", "extra"]) \
             .filter(col("extra") > 0)
        plan = (s.join(d, left_on="k", right_on="dk")
                 .aggregate(["k"], [("v", "sum", "t")]).build())
        opt, rep = optimize(plan)
        join = next(n for n in opt.nodes if isinstance(n, HashJoin))
        # right side narrowed to the join key: extra/grp die before the join
        from spark_rapids_tpu.plan.builder import Plan
        right_schema = Plan(join.right).schemas[id(join.right)]
        assert set(right_schema) == {"dk"}

    def test_shared_subtree_requirements_union(self):
        # a DAG-shared node serves BOTH parents: required columns union,
        # and the node stays shared after the rewrite
        b = PlanBuilder()
        t = b.scan("t", schema=["a", "u", "w", "junk"])
        shared = t.filter(col("a") > 0)
        left = shared.aggregate(["a"], [("u", "sum", "su")])
        right = shared.aggregate(["a"], [("w", "sum", "sw")])
        plan = left.join(right, left_on="a", right_on="a",
                         how="left_semi").build()
        opt, rep = optimize(plan)
        scan = next(n for n in opt.nodes if isinstance(n, Scan))
        assert scan.projection == ("a", "u", "w")   # junk pruned, u+w kept
        assert sum(isinstance(n, Filter) for n in opt.nodes) == 1  # shared
        tab = Table([_col([1, 1, 2]), _col([1, 2, 3]), _col([4, 5, 6]),
                     _col([0, 0, 0])], names=["a", "u", "w", "junk"])
        on, off = _run_pair(plan, {"t": tab})
        assert on.table.to_pydict() == off.table.to_pydict()

    def test_union_input_shared_elsewhere_keeps_schemas_equal(self):
        """A union input that is DAG-shared with another consumer picks up
        extra requirements; ALL union inputs must equalize to the same
        narrowed schema (positional contract) instead of falling back."""
        b = PlanBuilder()
        a = b.scan("a", schema=["k", "x", "junk", "junk2"])
        c2 = b.scan("c", schema=["k", "x", "junk", "junk2"])
        u = a.union(c2).aggregate(["k"], [("x", "sum", "s")])
        other = a.aggregate(["k"], [("junk", "sum", "j")])  # a needs junk
        plan = u.join(other, left_on="k", right_on="k",
                      how="left_semi").build()
        opt, rep = optimize(plan)
        assert not rep.fell_back
        assert rep.pruned_columns > 0           # junk2 still prunes
        scans = {n.source: n.projection for n in opt.nodes
                 if isinstance(n, Scan)}
        assert scans["a"] == scans["c"] == ("k", "x", "junk")
        t = lambda: Table([_col([1, 2, 1]), _col([5, 6, 7]),  # noqa: E731
                           _col([1, 1, 1]), _col([9, 9, 9])],
                          names=["k", "x", "junk", "junk2"])
        on, off = _run_pair(plan, {"a": t(), "c": t()})
        assert on.table.to_pydict() == off.table.to_pydict()

    def test_aggregate_drops_dead_aggs(self):
        b = PlanBuilder()
        plan = (b.scan("t", schema=["a", "v"])
                 .aggregate(["a"], [("v", "sum", "s"), ("v", "max", "dead")])
                 .project({"a": col("a"), "s": col("s")})
                 .build())
        opt, rep = optimize(plan)
        from spark_rapids_tpu.plan.nodes import HashAggregate
        agg = next(n for n in opt.nodes if isinstance(n, HashAggregate))
        assert [o[2] for o in agg.aggs] == ["s"]
        t = Table([_col([1, 1, 2]), _col([10, 20, 30])], names=["a", "v"])
        on, off = _run_pair(plan, {"t": t})
        assert on.table.to_pydict() == off.table.to_pydict()


# ---- rule: select fusion ----------------------------------------------------

class TestSelectFusion:
    def test_project_filter_fuses_both_tiers(self):
        b = PlanBuilder()
        plan = (b.scan("t", schema=["a", "v"])
                 .filter(col("a") > 2)
                 .project({"w": col("v") * 2})
                 .build())
        opt, rep = optimize(plan)
        assert rep.rules["select_fusion"] == 1
        assert _kinds(opt) == ["Scan", "FusedSelect"]
        t = Table([_col([1, 3, 5]), _col([10, 20, 30])], names=["a", "v"])
        on, off = _run_pair(plan, {"t": t})
        assert on.table.to_pydict() == off.table.to_pydict() \
            == {"w": [40, 60]}
        onc, offc = _run_pair(plan, {"t": t}, mode="capped")
        assert onc.compact().to_pydict() == offc.compact().to_pydict() \
            == {"w": [40, 60]}

    def test_adjacent_filters_merge(self):
        b = PlanBuilder()
        plan = (b.scan("t", schema=["a", "v"])
                 .filter(col("a") > 1).filter(col("v") < 25).build())
        opt, rep = optimize(plan)
        assert rep.rules["select_fusion"] == 1
        assert _kinds(opt).count("Filter") == 1
        t = Table([_col([1, 3, 5]), _col([10, 20, 30])], names=["a", "v"])
        on, off = _run_pair(plan, {"t": t})
        assert on.table.to_pydict() == off.table.to_pydict()

    def test_scalar_agg_in_projection_sees_filtered_rows(self):
        # FusedSelect must evaluate projection scalar aggs over the
        # FILTERED relation, exactly like Project(Filter) does
        b = PlanBuilder()
        plan = (b.scan("t", schema=["v"])
                 .filter(col("v") > 1)
                 .project({"m": scalar_max(col("v")), "v": col("v")})
                 .build())
        t = Table([_col([9, 1, 3])], names=["v"])
        on, off = _run_pair(plan, {"t": t})
        assert on.table.to_pydict() == off.table.to_pydict() \
            == {"m": [9, 9], "v": [9, 3]}

    def test_null_masks_survive_fusion(self):
        # validity buffers ride the fused gather untouched
        b = PlanBuilder()
        plan = (b.scan("t", schema=["a", "v"])
                 .filter(col("a") > 1)
                 .project({"v": col("v")})
                 .build())
        t = Table([_col([1, 2, 3, 4]),
                   _col([10, 20, 30, 40],
                        validity=[True, False, True, False])],
                  names=["a", "v"])
        on, off = _run_pair(plan, {"t": t})
        assert on.table.to_pydict() == off.table.to_pydict() \
            == {"v": [None, 30, None]}


# ---- rule: limit pushdown + TopK --------------------------------------------

class TestLimitPushdown:
    def test_sort_limit_becomes_topk(self):
        sales, _ = _tables()
        b = PlanBuilder()
        plan = (b.scan("sales", schema=["k", "v", "junk"])
                 .sort(["v", "k"], ascending=[False, True])
                 .limit(7).build())
        opt, rep = optimize(plan)
        assert rep.rules["limit_pushdown"] == 1
        assert any(isinstance(n, TopK) for n in opt.nodes)
        assert not any(isinstance(n, Limit) for n in opt.nodes)
        on, off = _run_pair(plan, {"sales": sales})
        assert on.table.to_pydict() == off.table.to_pydict()
        onc, offc = _run_pair(plan, {"sales": sales}, mode="capped")
        assert onc.compact().to_pydict() == offc.compact().to_pydict()

    def test_limit_pushes_below_rowwise_project(self):
        b = PlanBuilder()
        plan = (b.scan("t", schema=["v"])
                 .project({"w": col("v") * 2}).limit(2).build())
        opt, rep = optimize(plan)
        assert rep.rules["limit_pushdown"] == 1
        assert isinstance(opt.root, (Project, FusedSelect))  # Limit below
        t = Table([_col([1, 2, 3])], names=["v"])
        on, off = _run_pair(plan, {"t": t})
        assert on.table.to_pydict() == off.table.to_pydict() \
            == {"w": [2, 4]}

    def test_limit_never_crosses_scalar_agg_projection(self):
        b = PlanBuilder()
        plan = (b.scan("t", schema=["v"])
                 .project({"m": scalar_max(col("v"))}).limit(1).build())
        opt, rep = optimize(plan)
        assert rep.rules["limit_pushdown"] == 0
        t = Table([_col([1, 9, 3])], names=["v"])
        on, off = _run_pair(plan, {"t": t})
        assert on.table.to_pydict() == off.table.to_pydict() == {"m": [9]}

    def test_limit_limit_collapses(self):
        b = PlanBuilder()
        plan = b.scan("t", schema=["v"]).limit(5).limit(2).build()
        opt, rep = optimize(plan)
        limits = [n for n in opt.nodes if isinstance(n, Limit)]
        assert len(limits) == 1 and limits[0].n == 2


# ---- rule: build-side selection ---------------------------------------------

class TestBuildSide:
    # swapping reorders the join's output rows, so the rule only fires
    # under an order-absorbing HashAggregate (see _order_safe_ids) — every
    # case here aggregates above the join

    def _agg(self, joined):
        return joined.aggregate(["grp"], [("v", "sum", "total")])

    def test_swaps_when_left_is_much_smaller(self):
        sales, dims = _tables()
        b = PlanBuilder()
        d = b.scan("dims", schema=["dk", "grp", "extra"])
        s = b.scan("sales", schema=["k", "v", "junk"])
        # authored with the SMALL side on the left: the rule swaps and
        # restores the authored column order with a Project
        plan = self._agg(d.join(s, left_on="dk", right_on="k")).build()
        opt, rep = optimize(plan, bound_rows={"dims": 50, "sales": 2000})
        assert rep.rules["build_side"] == 1
        join = next(n for n in opt.nodes if isinstance(n, HashJoin))
        # the big side now probes (left); pruning may have narrowed the
        # scan, so look through an inserted select if present
        left = join.left
        while not isinstance(left, Scan):
            (left,) = left.children
        assert left.source == "sales"
        inputs = {"sales": sales, "dims": dims}
        on, off = _run_pair(plan, inputs)
        assert on.table.to_pydict() == off.table.to_pydict()

    def test_no_swap_when_join_order_is_observable(self):
        # the raw join IS the root: its row order is the result's order,
        # so the rule must not fire even with a huge estimate margin
        b = PlanBuilder()
        d = b.scan("dims", schema=["dk", "grp"], est_rows=10)
        s = b.scan("sales", schema=["k", "v"], est_rows=10_000)
        plan = d.join(s, left_on="dk", right_on="k").build()
        opt, rep = optimize(plan)
        assert rep.rules["build_side"] == 0

    def test_no_swap_without_clear_margin(self):
        b = PlanBuilder()
        l = b.scan("l", schema=["grp"], est_rows=100)
        r = b.scan("r", schema=["v"], est_rows=150)
        plan = self._agg(l.join(r, left_on="grp", right_on="v")
                         .project({"grp": col("grp"), "v": col("v")})) \
            .build()
        opt, rep = optimize(plan)
        assert rep.rules["build_side"] == 0

    def test_float_inputs_disable_swap_for_fp_exactness(self):
        # fp sums are not reorder-exact: with duplicate keys on BOTH join
        # sides, swapping flips the within-group pair enumeration and the
        # FLOAT64 sum differs in final ulps — execute() disables the rule
        # whenever any bound input column is floating point
        def fcol(a):
            a = np.asarray(a, dtype=np.float64)
            return Column(dtype=dtypes.FLOAT64, length=len(a),
                          data=jnp.asarray(a))
        small = Table([_col([0, 0]), _col([7, 7])], names=["sk", "g"])
        big = Table([_col([0, 0, 0, 0] + list(range(1, 40))),
                     fcol([7.148, -9.33e13, 0.459, -6.49e8] + [0.0] * 39)],
                    names=["bk", "v"])
        b = PlanBuilder()
        plan = (b.scan("small", schema=["sk", "g"])
                 .join(b.scan("big", schema=["bk", "v"]),
                       left_on="sk", right_on="bk")
                 .aggregate(["g"], [("v", "sum", "s")]).build())
        on, off = _run_pair(plan, {"small": small, "big": big})
        assert not on.optimizer["rules_fired"].get("build_side")
        assert on.table.to_pydict() == off.table.to_pydict()

    def test_float_gate_not_bypassed_by_cached_int_rewrite(self):
        # the rewrite cache keys on the float flag: a swap computed from
        # integer inputs must not be served to a float binding of the
        # same names and row counts
        def fcol(a):
            a = np.asarray(a, dtype=np.float64)
            return Column(dtype=dtypes.FLOAT64, length=len(a),
                          data=jnp.asarray(a))
        small = Table([_col([0, 0]), _col([7, 7])], names=["sk", "g"])
        big_i = Table([_col([0] * 4 + list(range(1, 40))),
                       _col(list(range(43)))], names=["bk", "v"])
        big_f = Table([big_i["bk"], fcol(np.arange(43))], names=["bk", "v"])
        b = PlanBuilder()
        plan = (b.scan("small", schema=["sk", "g"])
                 .join(b.scan("big", schema=["bk", "v"]),
                       left_on="sk", right_on="bk")
                 .aggregate(["g"], [("v", "sum", "s")]).build())
        ex = PlanExecutor()                     # ONE executor, shared cache
        r_int = ex.execute(plan, {"small": small, "big": big_i})
        assert r_int.optimizer["rules_fired"].get("build_side") == 1
        r_flt = ex.execute(plan, {"small": small, "big": big_f})
        assert not r_flt.optimizer["rules_fired"].get("build_side")

    def test_est_rows_hint_drives_swap_without_binding(self):
        b = PlanBuilder()
        l = b.scan("l", schema=["grp"], est_rows=10)
        r = b.scan("r", schema=["v"], est_rows=1000)
        plan = self._agg(l.join(r, left_on="grp", right_on="v")
                         .project({"grp": col("grp"), "v": col("v")})) \
            .build()
        opt, rep = optimize(plan)
        assert rep.rules["build_side"] == 1


# ---- full pipeline: the four NDS plans --------------------------------------

N = 2500


def _nds_cases():
    from examples.nds import (q3_inputs, q3_plan, q3_tables as bt3,
                              q5_inputs, q5_plan, q5_tables as bt5,
                              q23_inputs, q23_plan, q23_tables as bt23,
                              q72_inputs, q72_plan, q72_tables as bt72)
    return {
        "q3": (q3_plan, lambda: q3_inputs(*bt3(N, seed=7)), None),
        "q5": (q5_plan, lambda: q5_inputs(*bt5(N, seed=3)),
               {"key_cap": 2048}),
        "q23": (q23_plan, lambda: q23_inputs(*bt23(N, seed=11)),
                {"key_cap": 8192, "row_cap": N}),
        "q72": (q72_plan, lambda: q72_inputs(*bt72(N, seed=5)), None),
    }


def _eager_parity(q):
    mk_plan, mk_inputs, _ = _nds_cases()[q]
    plan, inputs = mk_plan(), mk_inputs()
    on, off = _run_pair(plan, inputs)
    assert on.table.to_pydict() == off.table.to_pydict()
    assert on.optimizer is not None and on.optimizer["rules_fired"]
    assert off.optimizer is None
    if q in ("q5", "q72"):
        assert on.optimizer["pruned_columns"] > 0


@pytest.mark.parametrize("q", ["q3", "q5"])
def test_nds_eager_parity_and_rules_fired(q):
    _eager_parity(q)


@pytest.mark.slow   # q23/q72 eager = many per-op dispatches x 4 runs; the
# nightly tier runs these
@pytest.mark.parametrize("q", ["q23", "q72"])
def test_nds_eager_parity_and_rules_fired_slow(q):
    _eager_parity(q)


@pytest.mark.parametrize("q", ["q3"])
def test_nds_capped_parity_on_vs_off(q):
    mk_plan, mk_inputs, caps = _nds_cases()[q]
    plan, inputs = mk_plan(), mk_inputs()
    on, off = _run_pair(plan, inputs, mode="capped", caps=caps)
    assert on.compact().to_pydict() == off.compact().to_pydict()


@pytest.mark.slow   # two whole-plan XLA traces per query: the timed tier
# covers q3 above and the nightly tier runs all 4
@pytest.mark.parametrize("q", ["q5", "q23", "q72"])
def test_nds_capped_parity_on_vs_off_slow(q):
    mk_plan, mk_inputs, caps = _nds_cases()[q]
    plan, inputs = mk_plan(), mk_inputs()
    on, off = _run_pair(plan, inputs, mode="capped", caps=caps)
    assert on.compact().to_pydict() == off.compact().to_pydict()


@pytest.mark.parametrize("q", ["q3", "q5", "q23", "q72"])
def test_nds_idempotent(q):
    mk_plan, _, _ = _nds_cases()[q]
    plan = mk_plan()
    once, r1 = optimize(plan)
    twice, r2 = optimize(once)
    assert once.fingerprint == twice.fingerprint
    assert r2.total_rewrites() == 0            # fixpoint reached in one run


# ---- fingerprints + program reuse -------------------------------------------

def _small_plan(b=None, c=11):
    b = b or PlanBuilder()
    s = b.scan("sales", schema=["k", "v", "junk"])
    d = b.scan("dims", schema=["dk", "grp", "extra"]) \
         .filter(col("grp") == 1)
    return (s.join(d, left_on="k", right_on="dk")
             .project({"grp": col("grp"), "rev": col("v") * lit(c)})
             .aggregate(["grp"], [("rev", "sum", "total")])
             .sort(["grp"]).build())


def test_fingerprint_stable_across_rebuilds_and_literal_sensitive():
    assert _small_plan().fingerprint == _small_plan().fingerprint
    assert plan_fingerprint(_small_plan()) != \
        plan_fingerprint(_small_plan(c=12))     # mutated literal -> miss


def test_rebuilt_plan_hits_jit_cache_mutated_literal_misses():
    sales, dims = _tables(n=600)
    inputs = {"sales": sales, "dims": dims}
    ex = PlanExecutor(mode="capped")
    ex.execute(_small_plan(), inputs)
    n_cached = len(ex._jit_cache)
    res = ex.execute(_small_plan(), inputs)     # independently rebuilt
    assert len(ex._jit_cache) == n_cached       # shared compiled program
    assert res.jit_cache_hits >= 1
    res2 = ex.execute(_small_plan(c=12), inputs)
    assert res2.jit_cache_hits == 0             # literal mutation: re-trace
    assert len(ex._jit_cache) > n_cached


def test_node_cap_overrides_share_programs_across_rebuilds():
    """Per-node cap overrides key on toposort indices, so a rebuilt plan
    with node-level row_cap/key_cap still hits the fingerprint-shared
    program cache and caps memo (labels differ between builds)."""
    sales, dims = _tables(n=600)
    inputs = {"sales": sales, "dims": dims}

    def mk():
        b = PlanBuilder()
        s = b.scan("sales", schema=["k", "v", "junk"])
        d = b.scan("dims", schema=["dk", "grp", "extra"]) \
             .filter(col("grp") == 1)
        return (s.join(d, left_on="k", right_on="dk", row_cap=4096)
                 .aggregate(["grp"], [("v", "sum", "t")], key_cap=64)
                 .build())

    ex = PlanExecutor(mode="capped")
    ex.execute(mk(), inputs)
    n_cached = len(ex._jit_cache)
    res = ex.execute(mk(), inputs)              # independently rebuilt
    assert res.jit_cache_hits >= 1
    assert len(ex._jit_cache) == n_cached


def test_caps_memo_shared_across_equivalent_plans():
    """Escalated caps memoize per FINGERPRINT: an equivalent plan built
    independently starts from the grown caps, no overflow re-climb."""
    sales, dims = _tables(n=600)
    inputs = {"sales": sales, "dims": dims}
    ex = PlanExecutor(mode="capped", caps={"row_cap": 64, "key_cap": 2},
                      max_cap_attempts=8)
    r1 = ex.execute(_small_plan(), inputs)
    assert r1.attempts > 1
    r2 = ex.execute(_small_plan(), inputs)      # rebuilt, same structure
    assert r2.attempts == 1
    assert r2.compact().to_pydict() == r1.compact().to_pydict()


# ---- switches + observability -----------------------------------------------

def test_env_off_switch(monkeypatch):
    sales, dims = _tables()
    monkeypatch.setenv("SPARK_RAPIDS_TPU_OPTIMIZER", "off")
    plan = _small_plan()
    res = PlanExecutor().execute(plan, {"sales": sales, "dims": dims})
    assert res.optimizer is None
    assert res.plan is plan                     # authored DAG executed
    assert len(res.metrics) == len(plan.nodes)
    monkeypatch.setenv("SPARK_RAPIDS_TPU_OPTIMIZER", "banana")
    with pytest.raises(ValueError, match="banana"):
        PlanExecutor()


def test_explain_optimized_shows_both_trees_and_summary():
    ex = PlanExecutor()
    plan = _small_plan()
    assert ex.explain(plan) == plan.explain()   # default: authored only
    txt = ex.explain(plan, optimized=True)
    assert "== authored ==" in txt and "== optimized ==" in txt
    assert "column_pruning" in txt and "fingerprint" in txt
    assert "sales [k, v]" in txt                # the pruned scan, rendered
    # with bound inputs, explain renders the EXACT rewrite execute() runs
    sales, dims = _tables()
    txt2 = ex.explain(plan, optimized=True,
                      inputs={"sales": sales, "dims": dims})
    assert "== optimized ==" in txt2 and "sales [k, v]" in txt2
    # ...including when that is NO rewrite (executor has the optimizer off)
    txt3 = PlanExecutor(optimize=False).explain(
        plan, optimized=True, inputs={"sales": sales, "dims": dims})
    assert "== optimized ==" not in txt3 and "disabled" in txt3


def test_profile_text_carries_optimizer_line():
    sales, dims = _tables()
    res = PlanExecutor().execute(_small_plan(),
                                 {"sales": sales, "dims": dims})
    txt = res.profile_text()
    assert "optimizer: rules_fired=" in txt and "pruned" in txt
