"""TPC-H Q18 (`chipbench/plans/tpch_q18.py`) through `PlanBuilder` and
`PlanExecutor` in both tiers against the plan file's plain numpy
reference: a decimal group-by an order, a `Filter` over its DECIMAL128
sum, a semi join, two joins, a five-key group-by with a DECIMAL64 key and
the top 100. A few thousand orders, so the HAVING's QUANTITY (a
substitution parameter of the query) is 250 here: at 300 an order in
26,000 passes. A second eager execution of the plan lowers nothing.
"""
import numpy as np
import pytest

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import dtypes
from spark_rapids_tpu.plan import PlanExecutor

TIERS = ("eager", "capped")
SEEDS = (2 ** 31 + 34, 77)
QUANTITY = 250
SIZES = {"customer_rows": 1000, "dsdgen_seed": 19980802}
BATCH = {"orders_rows": 4000, "lineitem_rows": 16044}
EXACT = {"ordered_mismatch": 0, "rows_unmatched": 0}


def _draw(seed):
    """-> (the plan's inputs, the same tables as host arrays)."""
    import jax
    from chipbench import tpcds
    from chipbench.plans import tpch_q18 as q18
    gen = q18.batch_generator(SIZES, BATCH)
    drawn = gen(tpcds.run_key(SIZES["dsdgen_seed"], 0),
                tpcds.run_key(seed, 1))
    dims = q18.dimensions(SIZES)
    inputs = {n: tpcds.table(c) for n, c in dims.items()}
    for name, (cols, _) in drawn.items():
        inputs[name] = tpcds.table(cols, {}, q18.COLUMNS[name])
    tables = {n: (c, {}) for n, c in dims.items()}
    tables.update(jax.device_get(drawn))
    return inputs, tables


@pytest.fixture(scope="module")
def q18():
    from chipbench.plans import tpch_q18
    return tpch_q18


@pytest.fixture(scope="module")
def draws():
    return {seed: _draw(seed) for seed in SEEDS}


@pytest.fixture(scope="module")
def executors(q18):
    return {"eager": PlanExecutor(mode="eager"),
            "capped": PlanExecutor(mode="capped", caps=q18.caps(BATCH))}


def _compare(q18, res, ref):
    from chipbench import check
    return check.compare(check.to_host(res), ref, q18.RESULT_COLUMNS,
                         q18.ORDERED)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("tier", TIERS)
def test_q18_equals_the_plain_reference(q18, draws, executors, tier, seed):
    inputs, tables = draws[seed]
    ref = q18.reference(tables, quantity=QUANTITY)
    res = executors[tier].execute(q18.plan(QUANTITY), inputs)
    assert res.degraded is False and res.decimal_overflow_rows == 0
    assert 5 < len(ref) <= q18.LIMIT
    assert _compare(q18, res, ref) == EXACT
    total = res.table["sum_qty"].dtype
    assert total.kind == dtypes.Kind.DECIMAL128 \
        and (total.precision, total.scale) == q18.SUM_MONEY
    price = res.table["o_totalprice"].dtype
    assert price.kind == dtypes.Kind.DECIMAL64 \
        and (price.precision, price.scale) == q18.MONEY
    # the keyed aggregates of the request: every lineitem row into a group
    # an order, then the large orders' rows into a group each
    rows, groups = q18.COUNTS["subquery"]
    assert (rows, groups) == (BATCH["lineitem_rows"], BATCH["orders_rows"])
    assert res.group_rows == rows + q18.COUNTS["outer"][0]
    assert res.groups == groups + q18.COUNTS["outer"][1]
    assert res.group_slots == (res.groups if tier == "eager"
                               else 2 * BATCH["orders_rows"])


@pytest.mark.parametrize("floor", ["the_modules", "under_the_rehearsal"])
def test_q18_eager_joins_take_the_small_side_path(q18, draws, monkeypatch,
                                                  floor):
    """At the cell's size the few orders the HAVING keeps meet 15 M orders,
    1.5 M customers and 60 M lines, and each of the three joins compares
    instead of sorting (ops/join.py). The rehearsal's tables lie under the
    path's floor, so none takes it; with the floor under them all three
    do, `lookup_compares` is the kept orders times the three large sides,
    and the answer is the reference's either way."""
    from spark_rapids_tpu.ops import join_lookup
    if floor == "under_the_rehearsal":
        monkeypatch.setattr(join_lookup, "LOOKUP_LARGE",
                            SIZES["customer_rows"])
    inputs, tables = draws[SEEDS[0]]
    res = PlanExecutor(mode="eager").execute(q18.plan(QUANTITY), inputs)
    assert _compare(q18, res, q18.reference(tables, quantity=QUANTITY)) \
        == EXACT
    joins = [m for m in res.metrics.values() if m.kind == "HashJoin"]
    assert len(joins) == 3
    if floor == "the_modules":
        assert BATCH["lineitem_rows"] < join_lookup.LOOKUP_LARGE
        assert (res.lookup_joins, res.lookup_compares) == (0, 0)
        assert all(m.kernel == "xla:hash_join" for m in joins)
        return
    kept = joins[0].rows_out            # the semi join: the large orders
    assert 5 < kept < 100
    large_sides = (BATCH["orders_rows"] + SIZES["customer_rows"]
                   + BATCH["lineitem_rows"])
    assert (res.lookup_joins, res.lookup_compares) == (3, kept * large_sides)
    assert [m.kernel for m in joins] == ["xla:lookup"] * 3
    assert [m.lookup_compares for m in joins] == [
        kept * BATCH["orders_rows"], kept * SIZES["customer_rows"],
        kept * BATCH["lineitem_rows"]]


@pytest.mark.parametrize("control", ["float64", "having_ge", "ascending"])
def test_q18_reference_controls_fail_the_comparison(q18, draws, control):
    """Each of the reference's wrong forms, put in the program's place,
    differs from it: a float64 engine that truncates its casts (5% of the
    prices come back a cent short), a HAVING of `>=` (the orders that sum
    to QUANTITY exactly), an ascending price order."""
    from chipbench import check
    _, tables = draws[SEEDS[0]]
    # one order of the fixed draw sums to 250 exactly
    ref = q18.reference(tables, quantity=QUANTITY)
    other = q18.reference(tables, control=control, quantity=QUANTITY)
    got = {c: other[c].values for c in q18.RESULT_COLUMNS}
    n = check.compare(got, ref, q18.RESULT_COLUMNS, q18.ORDERED)
    assert any(n[k] > lim for k, lim in check.LIMITS.items()), n


@pytest.mark.parametrize("tier", TIERS)
def test_q18_where_no_order_passes_the_having(q18, draws, executors, tier):
    """Seven lines of at most 50 never sum past 350: the filter leaves no
    group, the joins no row, and the answer is empty in both tiers."""
    inputs, tables = draws[SEEDS[0]]
    ref = q18.reference(tables, quantity=350)
    res = executors[tier].execute(q18.plan(350), inputs)
    assert len(ref) == 0 and res.degraded is False
    assert _compare(q18, res, ref) == EXACT


def test_q18_key_cap_overflows_and_escalates(q18, draws):
    """A key cap under the 4,000 groups of the subquery's aggregate: the
    program reports the overflow, the executor runs it again at a larger
    cap, and the answer is the reference's."""
    inputs, tables = draws[SEEDS[1]]
    ex = PlanExecutor(mode="capped", caps=dict(q18.caps(BATCH), key_cap=1500))
    res = ex.execute(q18.plan(QUANTITY), inputs)
    assert res.attempts > 1 and res.caps["key_cap"] >= BATCH["orders_rows"]
    assert _compare(q18, res, q18.reference(tables, quantity=QUANTITY)) \
        == EXACT


def test_the_second_eager_execution_lowers_nothing(q18, draws,
                                                   lowers_nothing_again):
    # another seed's orders have the same keys and quantities, so the
    # counts its programs are compiled for are the same
    lowers_nothing_again(q18.plan(QUANTITY), draws[SEEDS[0]][0],
                         draws[SEEDS[1]][0])


def test_q18_generator_is_dbgens_shape(q18, draws):
    """Lines an order, quantities and order keys do not move with --seed;
    the orders' payloads do."""
    (_, a), (_, b) = (draws[s] for s in SEEDS)
    li_a, li_b = a["lineitem"][0], b["lineitem"][0]
    for name in q18.COLUMNS["lineitem"]:
        assert np.array_equal(li_a[name], li_b[name]), name
    assert np.array_equal(a["orders"][0]["o_orderkey"],
                          b["orders"][0]["o_orderkey"])
    for name in ("o_custkey", "o_orderdate", "o_totalprice"):
        assert not np.array_equal(a["orders"][0][name],
                                  b["orders"][0][name]), name
    keys = np.asarray(li_a["l_orderkey"])
    assert keys.size == BATCH["lineitem_rows"] and (np.diff(keys) >= 0).all()
    _, lines = np.unique(keys, return_counts=True)
    assert lines.size == BATCH["orders_rows"]
    assert lines.min() == 1 and lines.max() == q18.MAX_LINES
    assert ((keys - 1) % 32 < 8).all()          # 8 keys used of every 32
    qty = np.asarray(li_a["l_quantity"])
    assert qty.min() == 100 and qty.max() == 5000 and (qty % 100 == 0).all()
    cust = np.asarray(a["orders"][0]["o_custkey"])
    assert (cust % 3 != 0).all() and 1 <= cust.min() \
        and cust.max() <= SIZES["customer_rows"]
    dates = np.asarray(a["orders"][0]["o_orderdate"])
    assert q18.ORDER_FIRST <= dates.min() and dates.max() <= q18.ORDER_LAST
