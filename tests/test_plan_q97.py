"""TPC-DS q97 (`chipbench/plans/q97.py`) and what it forced, through
`PlanBuilder` and `PlanExecutor`: the template in every tier against the
plan file's plain pandas reference at the configuration's rehearsal size
(nulls in every key column); `full_outer` in the eager, the capped and the
degraded walk against `pandas.merge(how="outer")` on seeded data (SQL's
rule for null keys written out: pandas matches NaN to NaN); DISTINCT; each
optimizer rule that looks at a join's type or moves a predicate, decided
for `full_outer` and for a null-aware predicate above an outer join; the
certifier; a mesh; the fuzzer.
"""
import numpy as np
import pandas as pd
import pytest

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import dtypes
from spark_rapids_tpu.columnar import Column, Table
from spark_rapids_tpu.plan import (PlanBuilder, PlanExecutor, coalesce, col,
                                   is_not_null, is_null, when)
from spark_rapids_tpu.plan.nodes import (Filter, FusedSelect, HashAggregate,
                                         HashJoin, Project, Scan)

TIERS = ("eager", "capped")
SEEDS = (2 ** 31 + 43, 97, 4100000097)
EXACT = {"ordered_mismatch": 0, "rows_unmatched": 0}
MONEY = dtypes.decimal(15, 2)
WIDE = dtypes.decimal(25, 2)


# ---- the template at the rehearsal size ----------------------------------------

@pytest.fixture(scope="module")
def cell():
    from chipbench import harness
    return harness.Cell("q97.batch", tiny=True)


@pytest.fixture(scope="module")
def q97(cell):
    return cell.plan


@pytest.fixture(scope="module")
def draws(cell):
    """{seed: (the plan's inputs, the same tables as host arrays)}."""
    import jax
    from chipbench import harness, tpcds
    gen = cell.plan.batch_generator(cell.sizes, cell.batch)
    dims = cell.plan.dimensions(cell.sizes)
    out = {}
    for seed in SEEDS:
        drawn = gen(*harness.batch_keys(cell, seed, harness.TABLE_STREAM))
        inputs = {n: tpcds.table(c) for n, c in dims.items()}
        inputs.update({
            name: tpcds.table(cols, validity, cell.plan.COLUMNS[name])
            for name, (cols, validity) in drawn.items()})
        tables = {n: (c, {}) for n, c in dims.items()}
        tables.update(jax.device_get(drawn))
        out[seed] = (inputs, tables)
    return out


@pytest.fixture(scope="module")
def executors(cell):
    return {"eager": PlanExecutor(mode="eager"),
            "capped": PlanExecutor(mode="capped",
                                   caps=cell.plan.caps(cell.batch))}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("tier", TIERS + ("degraded",))
def test_q97_equals_the_plain_reference(cell, q97, draws, executors, tier,
                                        seed):
    from chipbench import check
    inputs, tables = draws[seed]
    ref = q97.reference(tables)
    if tier == "degraded":
        res = PlanExecutor(mode="eager").execute(q97.plan(), inputs,
                                                 tier="cpu")
    else:
        res = executors[tier].execute(q97.plan(), inputs)
    assert res.degraded is (tier == "degraded")
    assert check.compare(check.to_host(res), ref, q97.RESULT_COLUMNS,
                         q97.ORDERED) == EXACT
    # every key column held a null, and a NULL customer counts nowhere
    for name in ("store_sales", "catalog_sales"):
        assert all(c.validity is not None for c in inputs[name].columns)
    counts = q97.COUNTS
    answer = [int(ref[c].values[0]) for c in q97.RESULT_COLUMNS]
    assert answer[2] == counts["matched"] == cell.batch["matched_pairs"]
    assert answer[0] < counts["unmatched"] \
        and answer[1] < counts["unmatched_right"]
    # the request's full join, by the program's own counts and the
    # reference's
    assert (res.full_joins, res.full_unmatched_rows,
            res.full_unmatched_right_rows) == (
        1, counts["unmatched"], counts["unmatched_right"])
    assert (res.outer_joins, res.outer_unmatched_rows) == (0, 0)
    join = next(m for n, m in zip(res.plan.nodes, res.metrics.values())
                if isinstance(n, HashJoin) and n.how == "full_outer")
    assert join.rows_out == (counts["matched"] + counts["unmatched"]
                             + counts["unmatched_right"])
    # both sides hold distinct pairs and hardly match: the left columns go
    # out as they stand, the few matched slots are written into a null
    # frame, the lonely right rows are compacted; no frame-long gather
    if tier == "capped":
        assert (join.left_out, join.right_out) == ("", "")
    else:
        from spark_rapids_tpu.ops.gather import compaction_path
        assert counts["matched"] + counts["unmatched"] \
            == cell.batch["store_pairs"]
        assert (join.left_out, join.right_out) == (
            "as_is", "sparse/" + compaction_path(
                cell.batch["catalog_pairs"], counts["unmatched_right"]))
    assert (res.join_planes_gathered, res.join_slots_gathered) == (0, 0)
    # the two DISTINCTs: rows in, pairs out
    assert (res.group_rows, res.groups) == (
        cell.batch["store_date_rows"] + cell.batch["catalog_date_rows"],
        cell.batch["store_pairs"] + cell.batch["catalog_pairs"])


@pytest.mark.parametrize("control", ("inner", "left_outer", "null_equal",
                                     "null_as_value", "no_distinct"))
def test_a_control_fails_the_comparison(q97, draws, control):
    from chipbench import check
    _, tables = draws[SEEDS[0]]
    ref = q97.reference(tables)
    other = q97.reference(tables, control=control)
    got = {c: other[c].values for c in q97.RESULT_COLUMNS}
    numbers = check.compare(got, ref, q97.RESULT_COLUMNS, q97.ORDERED)
    assert numbers["ordered_mismatch"] > 0 and numbers["rows_unmatched"] > 0


def test_the_store_date_join_reads_its_large_side_once(cell, q97, draws,
                                                      executors):
    """360,000 store_sales rows against 366 days, one row in five passing:
    the small-side path answers (no sort join over the large side), and
    the join's left columns ride the survivors' compaction."""
    inputs, _ = draws[SEEDS[0]]
    res = executors["eager"].execute(q97.plan(), inputs)
    joins = {n.left_keys[0]: m for n, m in
             zip(res.plan.nodes, res.metrics.values())
             if isinstance(n, HashJoin) and n.how == "inner"}
    assert joins["ss_sold_date_sk"].kernel == "xla:lookup"
    assert joins["ss_sold_date_sk"].lookup_compares \
        == 366 * cell.batch["store_rows"]
    assert joins["ss_sold_date_sk"].rows_out == cell.batch["store_date_rows"]
    # the catalog side lies under the path's floor at this size
    assert joins["cs_sold_date_sk"].kernel != "xla:lookup"
    assert res.lookup_joins == 1


def test_the_second_eager_execution_lowers_nothing(q97, draws,
                                                   lowers_nothing_again):
    # another seed's arrays have the same shapes
    lowers_nothing_again(q97.plan(), draws[SEEDS[1]][0], draws[SEEDS[2]][0])


# ---- `full_outer` against pandas -------------------------------------------------

def _column(values, dtype=dtypes.INT64) -> Column:
    return Column.from_pylist(list(values), dtype)


def _tables(case: str, seed: int = 43):
    """-> (left, right) as {column: python list (None = null)} over a
    two-column key, and the payloads' dtype."""
    rng = np.random.default_rng(seed)
    n_l, n_r = 40, 30
    left = {"k1": rng.integers(0, 6, n_l).tolist(),     # duplicates on
            "k2": rng.integers(0, 3, n_l).tolist(),     # both sides
            "lv": list(range(n_l))}
    right = {"r1": rng.integers(3, 9, n_r).tolist(),
             "r2": rng.integers(0, 3, n_r).tolist(),
             "rv": [1000 + i for i in range(n_r)]}
    payload = dtypes.INT64
    nulled = lambda vs, every: [None if i % every == 0 else v
                                for i, v in enumerate(vs)]
    if case == "null_keys":
        left["k1"], left["k2"] = nulled(left["k1"], 5), nulled(left["k2"], 7)
        right["r1"], right["r2"] = (nulled(right["r1"], 4),
                                    nulled(right["r2"], 6))
    elif case == "empty_left":
        left = {c: [] for c in left}
    elif case == "empty_right":
        right = {c: [] for c in right}
    elif case == "both_empty":
        left, right = {c: [] for c in left}, {c: [] for c in right}
    elif case == "no_match":
        right["r1"] = [k + 100 for k in right["r1"]]
    elif case == "all_match_once":
        pairs = [(a, b) for a in range(6) for b in range(3)]
        left = {"k1": [p[0] for p in pairs], "k2": [p[1] for p in pairs],
                "lv": list(range(18))}
        right = {"r1": left["k1"][::-1], "r2": left["k2"][::-1],
                 "rv": list(range(1000, 1018))}
    elif case == "decimal64_payload":
        payload = MONEY
        left["lv"] = [v * 7 for v in left["lv"]]
        right["rv"] = [v * 7 for v in right["rv"]]
        right["rv"][3] = None                     # a null of its own
    elif case == "decimal128_payload":
        payload = WIDE
        left["lv"] = [v * 10 ** 20 + v for v in left["lv"]]
        right["rv"] = [v * 10 ** 20 + v for v in right["rv"]]
    elif case == "bool_payload":
        payload = dtypes.BOOL
        left["lv"] = [v % 2 == 0 for v in left["lv"]]
        right["rv"] = [v % 3 == 0 for v in right["rv"]]
    else:
        assert case == "duplicates"
    return left, right, payload


CASES = ("duplicates", "null_keys", "empty_left", "empty_right",
         "both_empty", "no_match", "all_match_once", "decimal64_payload",
         "decimal128_payload", "bool_payload")
NAMES = ["k1", "k2", "lv", "r1", "r2", "rv"]


def _inputs(left, right, payload):
    return {"l": Table([_column(left["k1"]), _column(left["k2"]),
                        _column(left["lv"], payload)],
                       names=["k1", "k2", "lv"]),
            "r": Table([_column(right["r1"]), _column(right["r2"]),
                        _column(right["rv"], payload)],
                       names=["r1", "r2", "rv"])}


def _sorted(rows):
    return sorted(rows, key=lambda r: tuple((v is None, str(v)) for v in r))


def _pandas_full(left, right, how="outer"):
    """`merge(how="outer")` under SQL's rule for null keys: a row with a
    null in either key column is taken out of its side before the merge
    (pandas would match NaN to NaN) and put back null-extended. -> rows."""
    ldf = pd.DataFrame({c: pd.Series(v, dtype=object)
                        for c, v in left.items()})
    rdf = pd.DataFrame({c: pd.Series(v, dtype=object)
                        for c, v in right.items()})
    l_null = ldf[["k1", "k2"]].isna().any(axis=1)
    r_null = rdf[["r1", "r2"]].isna().any(axis=1)
    out = ldf[~l_null].assign(_1=ldf.k1, _2=ldf.k2).merge(
        rdf[~r_null].assign(_1=rdf.r1, _2=rdf.r2), on=["_1", "_2"],
        how=how)
    parts = [out[NAMES]]
    if how in ("outer", "left"):
        parts.append(ldf[l_null].reindex(columns=NAMES))
    if how == "outer":
        parts.append(rdf[r_null].reindex(columns=NAMES))
    out = pd.concat(parts).astype(object)
    out = out.where(out.notna(), None)
    return _sorted([tuple(r) for r in out.itertuples(index=False)])


def _rows(res):
    t = res.compact()
    return _sorted(list(zip(*(t[n].to_pylist() for n in t.names))))


def _join_plan(how="full_outer", above=None, below_left=None,
               below_right=None):
    b = PlanBuilder()
    left = b.scan("l", schema=["k1", "k2", "lv"])
    right = b.scan("r", schema=["r1", "r2", "rv"])
    if below_left is not None:
        left = left.filter(below_left)
    if below_right is not None:
        right = right.filter(below_right)
    rel = left.join(right, left_on=["k1", "k2"], right_on=["r1", "r2"],
                    how=how)
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
@pytest.mark.parametrize("tier", TIERS + ("degraded",))
def test_full_outer_equals_pandas(tier, case):
    left, right, payload = _tables(case)
    res = _run(tier, _join_plan(), _inputs(left, right, payload))
    want = _pandas_full(left, right)
    assert _rows(res) == want
    assert res.degraded is (tier == "degraded")
    # every row of either side came out, and the request counted the join
    assert len(want) >= max(len(left["k1"]), len(right["r1"]))
    lonely_left = sum(1 for r in want if r[2] is not None and r[5] is None
                      and r[3] is None and r[4] is None)
    if case not in ("decimal64_payload",):      # (a payload null of its own)
        assert res.full_unmatched_rows == lonely_left
    assert res.full_joins == 1
    assert res.full_unmatched_rows + res.full_unmatched_right_rows \
        + sum(1 for r in want if r[2] is not None and r[5] is not None) \
        == len(want) or case == "decimal64_payload"
    if case.startswith("decimal") or case == "bool_payload":
        assert res.table["lv"].dtype == res.table["rv"].dtype == payload
    # the eager join says how it made each side: at these sizes the right
    # side never takes a frame-long gather, the left side only where a
    # left row emits more than one slot
    join = next(m for n, m in zip(res.plan.nodes, res.metrics.values())
                if isinstance(n, HashJoin))
    if tier == "capped":
        assert (join.left_out, join.right_out) == ("", "")
    else:
        assert join.left_out in ("as_is", "take")
        assert join.right_out.split("/")[0] in ("sparse", "nulls")
        assert (res.join_planes_gathered > 0) == (join.left_out == "take")
        assert res.join_slots_gathered == res.join_planes_gathered * (
            join.rows_out - res.full_unmatched_right_rows)


@pytest.mark.parametrize("tier", TIERS)
def test_dead_rows_on_either_side_stay_dead(tier):
    """Filters below both sides: in the capped frame their rows stay in
    place under `alive`; a dead row of either side neither matches nor
    comes out null-extended."""
    left, right, payload = _tables("null_keys")
    plan = _join_plan(below_left=(col("lv") < 8) | (col("lv") > 19),
                      below_right=col("rv") < 1021)
    res = _run(tier, plan, _inputs(left, right, payload))
    keep_l = [i for i, v in enumerate(left["lv"]) if v < 8 or v > 19]
    keep_r = [i for i, v in enumerate(right["rv"]) if v < 1021]
    want = _pandas_full({c: [v[i] for i in keep_l] for c, v in left.items()},
                        {c: [v[i] for i in keep_r] for c, v in right.items()})
    assert _rows(res) == want and len(want) >= len(keep_l)


def test_a_row_cap_that_overflows_escalates():
    left, right, payload = _tables("duplicates")
    res = _run("capped", _join_plan(), _inputs(left, right, payload),
               caps={"row_cap": 8, "key_cap": 64})
    assert res.attempts > 1 and _rows(res) == _pandas_full(left, right)


@pytest.mark.parametrize("tier", TIERS)
def test_distinct_groups_nulls_together(tier):
    left, _, payload = _tables("null_keys")
    inputs = _inputs(left, {"r1": [], "r2": [], "rv": []}, payload)
    plan = PlanBuilder().scan("l", schema=["k1", "k2", "lv"]) \
        .distinct(["k1", "k2"]).build()
    (agg,) = [n for n in plan.nodes if isinstance(n, HashAggregate)]
    assert agg.aggs == () and "distinct" in agg.describe()
    res = _run(tier, plan, {"l": inputs["l"]})
    want = _sorted(set(zip(left["k1"], left["k2"])))
    assert _rows(res) == want
    assert any(k1 is None for k1, _ in want) \
        and any(k2 is None for _, k2 in want)
    assert (res.group_rows, res.groups) == (len(left["k1"]), len(want))


# ---- expressions over the join's output ------------------------------------------

@pytest.mark.parametrize("tier", TIERS + ("degraded",))
def test_case_when_over_is_null_counts_each_side(tier):
    """q97's projection over the general join: a row counts as left-only,
    right-only or both by the validity of the two first key columns, and a
    null first key of its own counts nowhere."""
    left, right, payload = _tables("null_keys")
    b = PlanBuilder()
    plan = (b.scan("l", schema=["k1", "k2", "lv"])
            .join(b.scan("r", schema=["r1", "r2", "rv"]),
                  left_on=["k1", "k2"], right_on=["r1", "r2"],
                  how="full_outer")
            .project({
                "lo": when(is_not_null(col("k1")) & is_null(col("r1")), 1, 0),
                "ro": when(is_null(col("k1")) & is_not_null(col("r1")), 1, 0),
                "both": when(is_not_null(col("k1"))
                             & is_not_null(col("r1")), 1, 0),
                "rv_or": coalesce(col("rv"), col("lv"), -1)})
            .aggregate([], [("lo", "sum", "lo"), ("ro", "sum", "ro"),
                            ("both", "sum", "both"),
                            ("rv_or", "sum", "rv_or")]).build())
    res = _run(tier, plan, _inputs(left, right, payload))
    rows = _pandas_full(left, right)
    want = (sum(r[0] is not None and r[3] is None for r in rows),
            sum(r[0] is None and r[3] is not None for r in rows),
            sum(r[0] is not None and r[3] is not None for r in rows),
            sum(r[5] if r[5] is not None else r[2] if r[2] is not None
                else -1 for r in rows))
    assert _rows(res) == [want] and all(want[:3])
    # an engine that reads the data under a null counts every row as both
    assert want[2] < len(rows)


# ---- the optimizer's rules, each decided for `full_outer` ---------------------

def _the_join(plan, how="full_outer") -> HashJoin:
    (join,) = [n for n in plan.nodes if isinstance(n, HashJoin)]
    assert join.how == how
    return join


def _filters_below(node) -> bool:
    seen, todo = False, [node]
    while todo:
        n = todo.pop()
        seen = seen or isinstance(n, (Filter, FusedSelect))
        todo.extend(n.children)
    return seen


@pytest.mark.parametrize("side", ("left", "right"))
@pytest.mark.parametrize("tier", TIERS)
def test_no_predicate_passes_below_either_side_of_a_full_join(tier, side):
    """Both sides supply nulls: below one, the predicate would turn the
    matches it drops into null-extended rows of the other."""
    left, right, payload = _tables("duplicates")
    inputs = _inputs(left, right, payload)
    above = col("lv") >= 13 if side == "left" else col("rv") > 1010
    plan = _join_plan(above=above)
    res = _run(tier, plan, inputs)
    join = _the_join(res.plan)
    assert not _filters_below(join.left) and not _filters_below(join.right)
    at, least = (2, 13) if side == "left" else (5, 1011)
    want = [r for r in _pandas_full(left, right)
            if r[at] is not None and r[at] >= least]
    assert want and _rows(res) == _sorted(want)
    assert _rows(_run(tier, plan, inputs, optimize=False)) == _sorted(want)


@pytest.mark.parametrize("how", ("left_outer", "full_outer"))
@pytest.mark.parametrize("tier", TIERS)
def test_is_null_of_the_null_supplying_side_stays_above_the_join(tier, how):
    """`r1 is null` above an outer join keeps the null-extended rows (the
    anti join's shape); below the right side it would keep the right
    rows whose key is null, which match nothing."""
    left, right, payload = _tables("null_keys")
    inputs = _inputs(left, right, payload)
    plan = _join_plan(how=how, above=is_null(col("r1")))
    res = _run(tier, plan, inputs)
    join = _the_join(res.plan, how)
    assert not _filters_below(join.right) and not _filters_below(join.left)
    want = [r for r in _pandas_full(
        left, right, "outer" if how == "full_outer" else "left")
        if r[3] is None]
    assert want and _rows(res) == _sorted(want)
    assert _rows(_run(tier, plan, inputs, optimize=False)) == _sorted(want)
    assert any(r[4] is not None for r in want) == (how == "full_outer")


def test_a_null_aware_predicate_passes_below_an_inner_join():
    """An inner join supplies no nulls: `coalesce(rv, 0) > 1010` over its
    right side commutes with it like any other predicate."""
    left, right, payload = _tables("duplicates")
    right["rv"] = [None if i % 3 == 0 else v
                   for i, v in enumerate(right["rv"])]
    inputs = _inputs(left, right, payload)
    plan = _join_plan(how="inner", above=coalesce(col("rv"), 0) > 1010)
    res = PlanExecutor(mode="eager").execute(plan, inputs)
    join = _the_join(res.plan, "inner")
    assert res.optimizer["rules_fired"].get("predicate_pushdown", 0) >= 1
    assert _filters_below(join.right)
    off = _run("eager", plan, inputs, optimize=False)
    assert _rows(res) == _rows(off) and _rows(res)


def test_the_sides_are_not_swapped_and_both_keys_survive_pruning():
    rng = np.random.default_rng(5)
    left = {"k1": [1, 2, 3, 50], "k2": [0, 0, 0, 0], "lv": [0, 1, 2, 3]}
    right = {"r1": rng.integers(0, 6, 400).tolist(), "r2": [0] * 400,
             "rv": list(range(400))}
    inputs = _inputs(left, right, dtypes.INT64)
    b = PlanBuilder()
    plan = (b.scan("l", schema=["k1", "k2", "lv"], est_rows=4)
            .join(b.scan("r", schema=["r1", "r2", "rv"], est_rows=4000),
                  left_on=["k1", "k2"], right_on=["r1", "r2"],
                  how="full_outer")
            .aggregate(["k1"], [("rv", "count", "n")]).build())
    res = PlanExecutor(mode="eager").execute(plan, inputs)
    join = _the_join(res.plan)
    assert join.left_keys == ("k1", "k2") and join.right_keys == ("r1", "r2")
    assert not res.optimizer["rules_fired"].get("build_side", 0)
    scans = {n.source: n for n in res.plan.nodes if isinstance(n, Scan)}
    assert scans["l"].projection == ("k1", "k2")        # `lv` is pruned
    assert set(scans["r"].projection or ("r1", "r2", "rv")) \
        == {"r1", "r2", "rv"}
    counts = dict(zip(res.table["k1"].to_pylist(),
                      res.table["n"].to_pylist()))
    assert counts[50] == 0 and counts[1] == right["r1"].count(1)
    # the lonely right rows group under a NULL k1, and `count` counts them
    assert counts[None] == sum(1 for k in right["r1"] if k not in (1, 2, 3))


@pytest.mark.parametrize("case", CASES)
def test_results_equal_with_the_optimizer_on_and_off(case):
    left, right, payload = _tables(case)
    inputs = _inputs(left, right, payload)
    b = PlanBuilder()
    plan = (b.scan("l", schema=["k1", "k2", "lv"]).filter(is_not_null(
                col("k2")))
            .join(b.scan("r", schema=["r1", "r2", "rv"]),
                  left_on=["k1", "k2"], right_on=["r1", "r2"],
                  how="full_outer")
            .filter(is_null(col("k1")) | is_not_null(col("rv")))
            .select(["k1", "lv", "rv"]).build())
    on = _run("eager", plan, inputs)
    off = _run("eager", plan, inputs, optimize=False)
    assert _rows(on) == _rows(off)


# ---- the verifier and the certifier -----------------------------------------------

def test_the_verifier_types_the_new_expressions():
    from spark_rapids_tpu.analysis import verifier
    types = {"l": {"k1": dtypes.INT64, "k2": dtypes.INT64, "lv": MONEY},
             "r": {"r1": dtypes.INT64, "r2": dtypes.INT64, "rv": MONEY}}
    good = _join_plan(above=when(is_null(col("r1")), col("k1") > 2,
                                 coalesce(col("k2"), 0) == 1))
    assert verifier.verify(good, input_dtypes=types).ok
    assert tuple(good.resolve_schemas({})[id(good.root)]) == tuple(NAMES)
    # one result column has one type
    b = PlanBuilder()
    mixed = b.scan("l", schema=["k1", "k2", "lv"]).project(
        {"x": when(col("k1") > 2, col("k2") > 1, col("k1"))}).build()
    rep = verifier.verify(mixed, input_dtypes=types)
    assert [v.invariant for v in rep.violations] == ["typing.branch-type-mismatch"]
    # a decimal beside another decimal type states its cast
    wide = b.scan("l", schema=["k1", "k2", "lv"]).project(
        {"x": coalesce(col("lv"), col("lv") * col("lv"))}).build()
    rep = verifier.verify(wide, input_dtypes=types)
    assert [v.invariant for v in rep.violations] == ["typing.decimal-not-lowered"]
    # a `when`'s condition is a predicate
    rep = verifier.verify(b.scan("l", schema=["k1", "k2", "lv"]).project(
        {"x": when(col("k1"), 1, 0)}).build(), input_dtypes=types)
    assert [v.invariant for v in rep.violations] == ["typing.predicate-not-bool"]


def test_the_certifier_bounds_rows_and_marks_both_sides_nullable():
    from spark_rapids_tpu.analysis import footprint
    plan = _join_plan()
    i = plan.nodes.index(_the_join(plan))
    cert = footprint.certify(plan, bound_rows={"l": 40, "r": 30})
    # at least the longer side, at most the sum plus the pairs
    assert (cert.by_index[i].rows_lo, cert.by_index[i].rows_hi) \
        == (40, 40 + 30 + 40 * 30)
    empty = footprint.certify(plan, bound_rows={"l": 0, "r": 30})
    assert (empty.by_index[i].rows_lo, empty.by_index[i].rows_hi) == (30, 30)
    # a keyed aggregate over a key that cannot be null has a group once it
    # has a row; after the full join either side's key can be null
    b = PlanBuilder()
    joined = b.scan("l", schema=["k1", "k2", "lv"]).join(
        b.scan("r", schema=["r1", "r2", "rv"]), left_on=["k1", "k2"],
        right_on=["r1", "r2"], how="full_outer")
    not_null = {"l": dict.fromkeys(["k1", "k2", "lv"], False),
                "r": dict.fromkeys(["r1", "r2", "rv"], False)}
    lo = {}
    for name, rel in (("k1", joined), ("r1", joined),
                      ("is_null", joined.project({"x": is_null(col("k1"))})),
                      ("coalesce", joined.project(
                          {"x": coalesce(col("k1"), col("r1"), 0)})),
                      ("when", joined.project(
                          {"x": when(is_null(col("k1")), col("r1"), 1)}))):
        key = name if name in ("k1", "r1") else "x"
        p = rel.aggregate([key], [(key, "size", "n")]).build()
        cert = footprint.certify(p, bound_rows={"l": 5, "r": 7},
                                 input_nullable=not_null)
        lo[name] = cert.by_index[len(p.nodes) - 1].rows_lo
    assert lo == {"k1": 0, "r1": 0, "is_null": 1, "coalesce": 1, "when": 0}


# ---- a mesh, and the fuzzer ------------------------------------------------------

def test_under_a_mesh_the_plan_stays_local_and_says_why():
    left, right, payload = _tables("duplicates")
    inputs = _inputs(left, right, payload)
    ex = PlanExecutor(mode="eager", mesh=4)
    plan = _join_plan()
    res = ex.execute(plan, inputs)
    assert _rows(res) == _pandas_full(left, right)
    assert res.dist_ops == 0 and res.local_ops == 0
    why = res.optimizer["decision_sources"]
    (key,) = [k for k in why if k.endswith("/mesh")]
    assert key.startswith("HashJoin") and why[key].startswith("local") \
        and "full_outer" in why[key]
    assert "full_outer has no distributed lowering" in ex.explain(
        plan, optimized=True, inputs=inputs)
    # a null-aware expression keeps its plan local too, by name
    b = PlanBuilder()
    aware = b.scan("l", schema=["k1", "k2", "lv"]).filter(
        is_not_null(col("k1")) & (col("lv") > 3)).build()
    res = ex.execute(aware, {"l": inputs["l"]})
    why = res.optimizer["decision_sources"]
    (key,) = [k for k in why if k.endswith("/mesh")]
    assert "null-aware expression" in why[key] and res.dist_ops == 0
    assert len(_rows(res)) == sum(v > 3 for v in left["lv"])


def test_a_nullable_column_under_a_mesh_is_read_with_its_validity():
    """A plain predicate over a nullable column runs ON the mesh: the
    SPMD walk keeps the rows where it is TRUE, and a projection carries
    the validity."""
    left, _, payload = _tables("null_keys")
    inputs = {"l": _inputs(left, {"r1": [], "r2": [], "rv": []},
                           payload)["l"]}
    b = PlanBuilder()
    plan = (b.scan("l", schema=["k1", "k2", "lv"]).filter(col("k1") >= 2)
            .project({"lv": col("lv"), "twice": col("k2") * 2}).build())
    res = PlanExecutor(mode="eager", mesh=4).execute(plan, inputs)
    assert res.dist_ops > 0
    want = _sorted((lv, None if k2 is None else 2 * k2)
                   for k1, k2, lv in zip(left["k1"], left["k2"], left["lv"])
                   if k1 is not None and k1 >= 2)
    assert _rows(res) == want and any(t is None for _, t in want)


def test_the_distributed_walk_lowers_neither():
    from spark_rapids_tpu.plan.optimizer import (_statically_distributable,
                                                 mesh_local_reason, optimize)
    plan = _join_plan()
    assert not _statically_distributable(_the_join(plan), False)
    opt, report = optimize(plan, {"l": ("k1", "k2", "lv"),
                                  "r": ("r1", "r2", "rv")},
                           {"l": 40, "r": 30}, mesh_peers=4)
    assert report.rules["exchange_planning"] == 0
    assert all(isinstance(n, (Scan, HashJoin, Project)) for n in opt.nodes)
    distinct = PlanBuilder().scan("l", schema=["k1"]).distinct(["k1"]).build()
    assert mesh_local_reason(distinct.nodes) is None
    assert not _statically_distributable(distinct.root, False)


def test_the_fuzzer_draws_the_new_join_and_expressions_and_they_hold():
    from spark_rapids_tpu.analysis.fuzz import gen_case, run_case
    from spark_rapids_tpu.plan.expr import Coalesce, IsNull, When
    from spark_rapids_tpu.plan.optimizer import _node_exprs

    def holds(e, kind):
        return isinstance(e, kind) or any(holds(c, kind)
                                          for c in e.children())
    drew = {"full_outer": [], IsNull: [], When: [], Coalesce: []}
    for s in range(300):
        nodes = gen_case(s).plan.nodes
        if any(isinstance(n, HashJoin) and n.how == "full_outer"
               for n in nodes):
            drew["full_outer"].append(s)
        for kind in (IsNull, When, Coalesce):
            if any(holds(e, kind) for n in nodes for e in _node_exprs(n)):
                drew[kind].append(s)
    assert all(len(v) >= 3 for v in drew.values()), drew
    for seed in sorted({s for v in drew.values() for s in v[:3]}):
        result = run_case(gen_case(seed))
        assert result.ok, (seed, result)
