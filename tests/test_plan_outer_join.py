"""`left_outer` through the plan layer: `PlanBuilder` / `Rel.join(how=
"left_outer")` in the eager, the capped and the degraded walk against a
pandas `merge(how="left")` on seeded data, each optimizer rule that looks at
a join's type, the certifier's bounds, a mesh, and the fuzzer.

General semantics, not one query's: duplicates on both sides, null keys on
either side (a null key matches nothing, and a left row with one comes out
null-extended), an empty right side, every left row unmatched, right
payloads of every width a join carries, dead rows under `alive` in the
capped frame, and a `row_cap` that overflows and escalates.
"""
import numpy as np
import pandas as pd
import pytest

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import dtypes
from spark_rapids_tpu.columnar import Column, Table
from spark_rapids_tpu.plan import PlanBuilder, PlanExecutor, col
from spark_rapids_tpu.plan.nodes import (Filter, FusedSelect, HashJoin,
                                         Project, Scan)

TIERS = ("eager", "capped", "degraded")
MONEY = dtypes.decimal(15, 2)
WIDE = dtypes.decimal(25, 2)


def _column(values, dtype=dtypes.INT64) -> Column:
    return Column.from_pylist(list(values), dtype)


def _tables(case: str, seed: int = 41):
    """-> (left, right) as {column: python list (None = null)}, with the
    right payload's dtype."""
    rng = np.random.default_rng(seed)
    n_l, n_r = 40, 30
    lk = rng.integers(0, 12, n_l).tolist()       # duplicates on both sides
    rk = rng.integers(6, 18, n_r).tolist()
    lv = list(range(n_l))
    rv = [1000 + i for i in range(n_r)]
    payload = dtypes.INT64
    if case == "null_left_keys":
        lk = [None if i % 5 == 0 else k for i, k in enumerate(lk)]
    elif case == "null_right_keys":
        rk = [None if i % 4 == 0 else k for i, k in enumerate(rk)]
    elif case == "null_keys_both":
        lk = [None if i % 5 == 0 else k for i, k in enumerate(lk)]
        rk = [None if i % 4 == 0 else k for i, k in enumerate(rk)]
    elif case == "empty_right":
        rk, rv = [], []
    elif case == "all_unmatched":
        rk = [k + 100 for k in rk]
    elif case == "decimal64_payload":
        payload = MONEY
        rv = [v * 7 for v in rv]                  # unscaled: cents
        rv[3] = None                              # a null of its own
    elif case == "decimal128_payload":
        payload = WIDE
        rv = [v * 10 ** 20 + v for v in rv]       # past 64 bits
    else:
        assert case == "duplicates"
    return {"k": lk, "lv": lv}, {"rk": rk, "rv": rv}, payload


CASES = ("duplicates", "null_left_keys", "null_right_keys", "null_keys_both",
         "empty_right", "all_unmatched", "decimal64_payload",
         "decimal128_payload")


def _inputs(left, right, payload):
    return {"l": Table([_column(left["k"]), _column(left["lv"])],
                       names=["k", "lv"]),
            "r": Table([_column(right["rk"]), _column(right["rv"], payload)],
                       names=["rk", "rv"])}


def _pandas_left(left, right, lfilter=None, rfilter=None):
    """`merge(how="left")` with Spark's rule for null keys (pandas matches
    NaN to NaN: the right side's null-keyed rows go first). -> rows."""
    ldf = pd.DataFrame({c: pd.Series(v, dtype=object)
                        for c, v in left.items()})
    rdf = pd.DataFrame({c: pd.Series(v, dtype=object)
                        for c, v in right.items()})
    if lfilter is not None:
        ldf = ldf[ldf.apply(lfilter, axis=1).astype(bool)]
    if rfilter is not None and len(rdf):
        rdf = rdf[rdf.apply(rfilter, axis=1).astype(bool)]
    rdf = rdf[rdf["rk"].notna()]
    ldf = ldf.assign(_k=ldf["k"].map(lambda k: ("L", id(ldf)) if k is None
                                     else k))
    out = ldf.merge(rdf.assign(_k=rdf["rk"]), on="_k", how="left")
    out = out[["k", "lv", "rk", "rv"]].astype(object)
    out = out.where(out.notna(), None)
    return _sorted([tuple(r) for r in out.itertuples(index=False)])


def _sorted(rows):
    return sorted(rows, key=lambda r: tuple((v is None, str(v)) for v in r))


def _rows(res):
    t = res.compact()
    return _sorted(list(zip(*(t[n].to_pylist() for n in t.names))))


def _join_plan(below_left=None, below_right=None, above=None):
    b = PlanBuilder()
    left = b.scan("l", schema=["k", "lv"])
    right = b.scan("r", schema=["rk", "rv"])
    if below_left is not None:
        left = left.filter(below_left)
    if below_right is not None:
        right = right.filter(below_right)
    rel = left.join(right, left_on="k", right_on="rk", how="left_outer")
    if above is not None:
        rel = rel.filter(above)
    return rel.build()


def _run(tier: str, plan, inputs, **kw):
    if tier == "capped":
        return PlanExecutor(mode="capped", caps=kw.pop(
            "caps", {"row_cap": 512, "key_cap": 64}), **kw).execute(
                plan, inputs)
    ex = PlanExecutor(mode="eager", **kw)
    return ex.execute(plan, inputs, tier="cpu" if tier == "degraded"
                      else None)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("tier", TIERS)
def test_left_outer_equals_pandas(tier, case):
    left, right, payload = _tables(case)
    res = _run(tier, _join_plan(), _inputs(left, right, payload))
    want = _pandas_left(left, right)
    assert _rows(res) == want
    assert res.degraded is (tier == "degraded")
    # every left row came out, and the request counted its outer join
    unmatched = sum(1 for r in want if r[2] is None)
    assert len(want) >= len(left["k"]) and unmatched
    assert (res.outer_joins, res.outer_unmatched_rows) == (1, unmatched)
    if case.startswith("decimal"):
        assert res.table["rv"].dtype == payload
    # the null-supplying side's columns are nullable after the join
    assert res.table["rv"].validity is not None
    assert res.table["lv"].validity is None
    # the eager join says how it made each side (no tail: a left join)
    join = next(m for n, m in zip(res.plan.nodes, res.metrics.values())
                if isinstance(n, HashJoin))
    if tier == "capped":
        assert (join.left_out, join.right_out) == ("", "")
    else:
        assert join.left_out in ("as_is", "take")
        assert join.right_out in ("sparse", "nulls")
        assert (res.join_planes_gathered > 0) == (join.left_out == "take")
        assert res.join_slots_gathered \
            == res.join_planes_gathered * join.rows_out


@pytest.mark.parametrize("tier", TIERS)
def test_count_skips_the_null_extended_rows_and_size_does_not(tier):
    left, right, payload = _tables("duplicates")
    b = PlanBuilder()
    plan = (b.scan("l", schema=["k", "lv"])
            .join(b.scan("r", schema=["rk", "rv"]), left_on="k",
                  right_on="rk", how="left_outer")
            .aggregate(["k"], [("rv", "count", "matches"),
                               ("rv", "size", "rows")])
            .sort(["k"]).build())
    res = _run(tier, plan, _inputs(left, right, payload))
    per_key = {}
    for k, _, rk, _ in _pandas_left(left, right):
        c, s = per_key.get(k, (0, 0))
        per_key[k] = (c + (rk is not None), s + 1)
    assert _rows(res) == _sorted((k, c, s) for k, (c, s) in per_key.items())
    # the key whose rows are all null-extended counts 0 of them
    assert any(c == 0 and s > 0 for c, s in per_key.values())


@pytest.mark.parametrize("tier", ("eager", "capped"))
def test_dead_rows_on_either_side_stay_dead(tier):
    """Filters below both sides: in the capped frame their rows stay in
    place under `alive`; a dead left row emits nothing, a dead right row
    matches nothing."""
    left, right, payload = _tables("null_keys_both")
    plan = _join_plan(below_left=(col("lv") < 8) | (col("lv") > 19),
                      below_right=col("rv") < 1021)
    res = _run(tier, plan, _inputs(left, right, payload))
    want = _pandas_left(
        left, right, lfilter=lambda r: r["lv"] < 8 or r["lv"] > 19,
        rfilter=lambda r: r["rv"] < 1021)
    assert _rows(res) == want and len(want) >= 28
    assert res.outer_unmatched_rows == sum(1 for r in want if r[2] is None)


def test_a_row_cap_that_overflows_escalates():
    left, right, payload = _tables("duplicates")
    res = _run("capped", _join_plan(), _inputs(left, right, payload),
               caps={"row_cap": 8, "key_cap": 64})
    want = _pandas_left(left, right)
    assert res.attempts > 1 and res.caps["row_cap"] >= len(want)
    assert _rows(res) == want
    join = next(m for m in res.metrics.values() if m.kind == "HashJoin")
    assert join.escalations == res.attempts - 1
    assert join.unmatched_rows == res.outer_unmatched_rows > 0


def test_a_serving_session_runs_it():
    from spark_rapids_tpu.serving import ServingScheduler
    left, right, payload = _tables("null_left_keys")
    sched = ServingScheduler(PlanExecutor(mode="capped", caps={
        "row_cap": 512, "key_cap": 64}))
    try:
        session = sched.open_session("outer", quota_bytes=1 << 30)
        res = session.submit(_join_plan(), _inputs(left, right, payload)) \
            .result(timeout=300)
        assert _rows(res) == _pandas_left(left, right)
        session.close()
    finally:
        sched.close()


# ---- the optimizer's rules, each decided for `left_outer` -----------------------

def _optimized(plan, inputs, **kw):
    ex = PlanExecutor(mode="eager", **kw)
    res = ex.execute(plan, inputs)
    return res.plan, res


def _the_join(plan) -> HashJoin:
    (join,) = [n for n in plan.nodes if isinstance(n, HashJoin)]
    assert join.how == "left_outer"
    return join


def _filters_below(node) -> bool:
    seen, todo = False, [node]
    while todo:
        n = todo.pop()
        seen = seen or isinstance(n, (Filter, FusedSelect))
        todo.extend(n.children)
    return seen


@pytest.mark.parametrize("tier", ("eager", "capped"))
def test_a_right_side_predicate_above_the_join_stays_above_it(tier):
    """Below the null-supplying side it would turn the matches it drops
    into null-extended rows; above, it drops those rows."""
    left, right, payload = _tables("duplicates")
    inputs = _inputs(left, right, payload)
    plan = _join_plan(above=col("rv") > 1010)
    res = _run(tier, plan, inputs)
    join = _the_join(res.plan)
    assert not _filters_below(join.right) and not _filters_below(join.left)
    want = [r for r in _pandas_left(left, right)
            if r[3] is not None and r[3] > 1010]
    assert want and _rows(res) == _sorted(want)
    assert _rows(_run(tier, plan, inputs, optimize=False)) == _sorted(want)
    # what the wrong rule would have given is another answer
    wrong = [r for r in _pandas_left(left, right,
                                     rfilter=lambda r: r["rv"] > 1010)]
    assert _sorted(wrong) != _sorted(want)


@pytest.mark.parametrize("tier", TIERS)
def test_expressions_above_the_join_read_no_filler_under_a_null(tier):
    """Q13's join with a `Filter` and a `Project` over the null-supplying
    side's column above it. Until PR 43 an expression read the data buffer
    alone, and a null-extended row's `rv` is whatever the gather left
    under its null (it passes `rv < 1010`); Spark's answer: the comparison
    is null there and the row goes, `rv + 1` is null, `rv is null` true."""
    from spark_rapids_tpu.plan import is_null
    left, right, payload = _tables("duplicates")
    inputs = _inputs(left, right, payload)
    rows = _pandas_left(left, right)
    res = _run(tier, _join_plan(above=col("rv") < 1010), inputs)
    want = [r for r in rows if r[3] is not None and r[3] < 1010]
    assert want and _rows(res) == _sorted(want)
    assert any(r[3] is None for r in rows)      # rows the filler would pass
    b = PlanBuilder()
    plan = (b.scan("l", schema=["k", "lv"])
            .join(b.scan("r", schema=["rk", "rv"]), left_on="k",
                  right_on="rk", how="left_outer")
            .project({"lv": col("lv"), "next": col("rv") + 1,
                      "lonely": is_null(col("rv")),
                      "both": (col("rv") > 0) & (col("lv") >= 0)}).build())
    res = _run(tier, plan, inputs)
    assert _rows(res) == _sorted(
        (r[1], None if r[3] is None else r[3] + 1, r[3] is None,
         None if r[3] is None else True) for r in rows)
    assert res.table["next"].validity is not None
    assert res.table["lonely"].validity is None


def test_a_left_side_predicate_above_the_join_passes_below_it():
    left, right, payload = _tables("null_right_keys")
    inputs = _inputs(left, right, payload)
    plan = _join_plan(above=col("lv") >= 13)
    opt, res = _optimized(plan, inputs)
    join = _the_join(opt)
    assert res.optimizer["rules_fired"].get("predicate_pushdown", 0) >= 1
    assert _filters_below(join.left) and not _filters_below(join.right)
    want = [r for r in _pandas_left(left, right) if r[1] >= 13]
    assert _rows(res) == _sorted(want)
    assert _rows(_run("eager", plan, inputs, optimize=False)) \
        == _sorted(want)


def _under_an_aggregate(how: str):
    """A small left side against a large right one below an aggregate:
    where the build-side rule swaps an inner join."""
    b = PlanBuilder()
    return (b.scan("l", schema=["k", "lv"], est_rows=4)
            .join(b.scan("r", schema=["rk", "rv"], est_rows=4000),
                  left_on="k", right_on="rk", how=how)
            .aggregate(["k"], [("rv", "count", "n")]).build())


def test_the_sides_are_not_swapped():
    rng = np.random.default_rng(5)
    left = {"k": [1, 2, 3, 50], "lv": [0, 1, 2, 3]}
    right = {"rk": rng.integers(0, 6, 400).tolist(),
             "rv": list(range(400))}
    inputs = _inputs(left, right, dtypes.INT64)
    opt, res = _optimized(_under_an_aggregate("left_outer"), inputs)
    join = _the_join(opt)
    assert join.left_keys == ("k",) and join.right_keys == ("rk",)
    assert not res.optimizer["rules_fired"].get("build_side", 0)
    # the same plan with an inner join IS swapped: the rule saw the sizes
    _, inner = _optimized(_under_an_aggregate("inner"), inputs)
    assert inner.optimizer["rules_fired"].get("build_side", 0) == 1
    counts = dict(zip(res.table["k"].to_pylist(),
                      res.table["n"].to_pylist()))
    assert counts == {k: right["rk"].count(k) for k in left["k"]}
    assert counts[50] == 0


def test_the_right_key_survives_column_pruning():
    """Nothing above the join reads `rk` or `lv`: the left payload goes,
    the right key stays (the join matches on it)."""
    left, right, payload = _tables("duplicates")
    b = PlanBuilder()
    plan = (b.scan("l", schema=["k", "lv"])
            .join(b.scan("r", schema=["rk", "rv"]), left_on="k",
                  right_on="rk", how="left_outer")
            .aggregate(["k"], [("rv", "count", "n")]).build())
    inputs = _inputs(left, right, payload)
    opt, res = _optimized(plan, inputs)
    join = _the_join(opt)
    scans = {n.source: n for n in opt.nodes if isinstance(n, Scan)}
    assert scans["l"].projection == ("k",)
    assert scans["r"].projection in (None, ("rk", "rv"))
    assert set(opt.resolve_schemas({n: tuple(t.names) for n, t in
                                    inputs.items()})[id(join)]) \
        >= {"k", "rk", "rv"}
    off = _run("eager", plan, inputs, optimize=False)
    assert _rows(res) == _rows(off)


@pytest.mark.parametrize("case", CASES)
def test_results_equal_with_the_optimizer_on_and_off(case):
    left, right, payload = _tables(case)
    inputs = _inputs(left, right, payload)
    b = PlanBuilder()
    plan = (b.scan("l", schema=["k", "lv"]).filter(col("lv") >= 2)
            .join(b.scan("r", schema=["rk", "rv"]), left_on="k",
                  right_on="rk", how="left_outer")
            .filter(col("lv") < 37)
            .select(["k", "lv", "rv"]).build())
    on = _run("eager", plan, inputs)
    off = _run("eager", plan, inputs, optimize=False)
    assert _rows(on) == _rows(off)
    assert len(_rows(on)) >= sum(1 for v in left["lv"] if 2 <= v < 37)


# ---- the verifier and the certifier -----------------------------------------------

def test_the_verifier_types_both_sides_columns():
    from spark_rapids_tpu.analysis import verifier
    plan = _join_plan(above=col("rv") * 2 > col("lv"))
    rep = verifier.verify(plan, input_dtypes={
        "l": {"k": dtypes.INT64, "lv": dtypes.INT64},
        "r": {"rk": dtypes.INT64, "rv": dtypes.INT64}})
    assert rep.ok, rep.violations
    # the schema is the left side's columns, then the right side's
    assert tuple(plan.resolve_schemas({})[id(plan.root)]) \
        == ("k", "lv", "rk", "rv")


def test_an_unknown_join_type_is_refused_by_name():
    from spark_rapids_tpu.plan import PlanValidationError
    b = PlanBuilder()
    # (`full_outer` was the example until PR 43 made it a join type)
    with pytest.raises(PlanValidationError, match="right_outer"):
        b.scan("l", schema=["k"]).join(b.scan("r", schema=["rk"]),
                                       left_on="k", right_on="rk",
                                       how="right_outer")


def test_the_certifier_bounds_its_rows_from_below_by_the_left_sides():
    from spark_rapids_tpu.analysis import footprint
    left, right, payload = _tables("duplicates")
    inputs = _inputs(left, right, payload)
    plan = _join_plan()
    cert = footprint.certify(
        plan, bound={n: tuple(t.names) for n, t in inputs.items()},
        bound_rows={n: t.num_rows for n, t in inputs.items()},
        input_dtypes={n: {c: t[c].dtype for c in t.names}
                      for n, t in inputs.items()})
    i = plan.nodes.index(_the_join(plan))
    n_l, n_r = len(left["k"]), len(right["rk"])
    assert (cert.by_index[i].rows_lo, cert.by_index[i].rows_hi) \
        == (n_l, n_l * n_r)
    res = _run("eager", plan, inputs)
    assert n_l <= res.compact().num_rows <= n_l * n_r
    # an empty right side: every left row still comes out, once
    empty = footprint.certify(plan, bound_rows={"l": n_l, "r": 0})
    assert (empty.by_index[i].rows_lo, empty.by_index[i].rows_hi) \
        == (n_l, n_l)
    # an inner join's lower bound is 0: only the outer join holds its rows
    b = PlanBuilder()
    inner = b.scan("l", schema=["k", "lv"]).join(
        b.scan("r", schema=["rk", "rv"]), left_on="k",
        right_on="rk").build()
    assert footprint.certify(
        inner, bound_rows={"l": n_l, "r": n_r}).by_index[2].rows_lo == 0


def test_the_certifier_marks_the_right_sides_columns_nullable():
    """A keyed aggregate over a key that cannot be null has a group once
    it has a row; over the outer join's right columns it may have none
    the kernel keeps."""
    from spark_rapids_tpu.analysis import footprint
    b = PlanBuilder()
    joined = b.scan("l", schema=["k", "lv"]).join(
        b.scan("r", schema=["rk", "rv"]), left_on="k", right_on="rk",
        how="left_outer")
    not_null = {"l": {"k": False, "lv": False},
                "r": {"rk": False, "rv": False}}
    lo = {}
    for key in ("k", "rk"):
        plan = joined.aggregate([key], [("lv", "size", "n")]).build()
        cert = footprint.certify(plan, bound_rows={"l": 5, "r": 7},
                                 input_nullable=not_null)
        lo[key] = cert.by_index[len(plan.nodes) - 1].rows_lo
    assert lo == {"k": 1, "rk": 0}


# ---- a mesh, and the fuzzer ------------------------------------------------------

def test_under_a_mesh_the_plan_stays_local_and_says_why():
    left, right, payload = _tables("duplicates")
    inputs = _inputs(left, right, payload)
    ex = PlanExecutor(mode="eager", mesh=4)
    plan = _join_plan()
    res = ex.execute(plan, inputs)
    assert _rows(res) == _pandas_left(left, right)
    assert res.dist_ops == 0 and res.local_ops == 0
    why = res.optimizer["decision_sources"]
    (key,) = [k for k in why if k.endswith("/mesh")]
    assert key.startswith("HashJoin") and why[key].startswith("local") \
        and "left_outer" in why[key]
    assert not res.optimizer["exchanges"] and not res.optimizer["sharding"]
    assert "left_outer has no distributed lowering" in ex.explain(
        plan, optimized=True, inputs=inputs)
    # the same executor puts an inner join of the same tables on its mesh
    b = PlanBuilder()
    inner = b.scan("l", schema=["k", "lv"]).join(
        b.scan("r", schema=["rk", "rv"]), left_on="k", right_on="rk").build()
    assert ex.execute(inner, inputs).dist_ops > 0


def test_the_distributed_walk_never_lowers_it_as_another_join():
    """Handed to the SPMD walk all the same (the optimizer called without
    the executor), the join is no node of the mesh."""
    from spark_rapids_tpu.plan.optimizer import (_statically_distributable,
                                                 optimize)
    plan = _join_plan()
    assert not _statically_distributable(_the_join(plan), False)
    opt, report = optimize(plan, {"l": ("k", "lv"), "r": ("rk", "rv")},
                           {"l": 40, "r": 30}, mesh_peers=4)
    assert report.rules["exchange_planning"] == 0
    assert all(isinstance(n, (Scan, HashJoin, Project)) for n in opt.nodes)


def test_the_fuzzer_draws_left_outer_joins_and_they_hold():
    from spark_rapids_tpu.analysis.fuzz import gen_case, run_case
    drew = [s for s in range(300)
            if any(isinstance(n, HashJoin) and n.how == "left_outer"
                   for n in gen_case(s).plan.nodes)]
    assert len(drew) >= 3, drew
    for seed in drew[:3]:
        result = run_case(gen_case(seed))
        assert result.ok, (seed, result)
