"""TPC-H Q13 (`chipbench/plans/tpch_q13.py`) through `PlanBuilder` and
`PlanExecutor` in both tiers against the plan file's plain numpy reference,
at the configuration's rehearsal size: `customer LEFT OUTER` the orders
that survive the comment's predicate, the count of non-null order keys a
customer, the count of customers a count, the query's order. The controls
of the cell's comparison fail it, and a second eager execution of the plan
lowers nothing.
"""
import pytest

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu.plan import PlanExecutor
from spark_rapids_tpu.plan.nodes import FusedSelect, HashJoin

TIERS = ("eager", "capped")
SEEDS = (2 ** 31 + 41, 77, 4100000007)
CONTROLS = ("inner", "count_star", "filter_above")
EXACT = {"ordered_mismatch": 0, "rows_unmatched": 0}


@pytest.fixture(scope="module")
def cell():
    from chipbench import harness
    return harness.Cell("q13.batch", tiny=True)


@pytest.fixture(scope="module")
def q13(cell):
    return cell.plan


@pytest.fixture(scope="module")
def draws(cell):
    """{seed: (the plan's inputs, the same tables as host arrays)}."""
    import jax
    from chipbench import harness, tpcds
    gen = cell.plan.batch_generator(cell.sizes, cell.batch)
    out = {}
    for seed in SEEDS:
        drawn = gen(*harness.batch_keys(cell, seed, harness.TABLE_STREAM))
        out[seed] = ({name: tpcds.table(cols, {}, cell.plan.COLUMNS[name])
                      for name, (cols, _) in drawn.items()},
                     dict(jax.device_get(drawn)))
    return out


@pytest.fixture(scope="module")
def executors(cell):
    return {"eager": PlanExecutor(mode="eager"),
            "capped": PlanExecutor(mode="capped",
                                   caps=cell.plan.caps(cell.batch))}


def _compare(q13, got: dict, ref):
    from chipbench import check
    return check.compare(got, ref, q13.RESULT_COLUMNS, q13.ORDERED)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("tier", TIERS)
def test_q13_equals_the_plain_reference(cell, q13, draws, executors, tier,
                                        seed):
    from chipbench import check
    inputs, tables = draws[seed]
    ref = q13.reference(tables)
    res = executors[tier].execute(q13.plan(), inputs)
    assert res.degraded is False
    assert _compare(q13, check.to_host(res), ref) == EXACT
    assert len(ref) == cell.batch["count_groups"]
    # the row that exists only because unmatched left rows survive
    assert ref["c_count"].values[0] == 0 \
        and ref["custdist"].values[0] == cell.batch["unmatched_customers"]
    # the request's outer join, by the program's own count and the
    # reference's
    assert (res.outer_joins, res.outer_unmatched_rows) \
        == (1, q13.COUNTS["unmatched"])
    join = next(m for n, m in zip(res.plan.nodes, res.metrics.values())
                if isinstance(n, HashJoin))
    assert join.rows_out == q13.COUNTS["matched"] + q13.COUNTS["unmatched"]
    # a customer's orders fan out and most slots match: the left column
    # is gathered through its map (one plane of the output's length);
    # `c_custkey` is distinct, so every order has one slot at most and the
    # right side's two columns ride one sort to their slots (PR 45)
    if tier == "eager":
        assert (join.left_out, join.right_out) == ("take", "sort")
        assert (res.join_planes_gathered, res.join_slots_gathered) \
            == (1, join.rows_out)
    else:
        assert (join.left_out, join.right_out) == ("", "")
        assert res.join_planes_gathered == 0
    assert (res.group_rows, res.groups) == (
        join.rows_out + cell.batch["customer_rows"],
        cell.batch["customer_rows"] + cell.batch["count_groups"])


@pytest.mark.parametrize("control", CONTROLS)
def test_a_control_fails_the_comparison(q13, draws, control):
    _, tables = draws[SEEDS[0]]
    ref = q13.reference(tables)
    other = q13.reference(tables, control=control)
    got = {c: other[c].values for c in q13.RESULT_COLUMNS}
    numbers = _compare(q13, got, ref)
    assert numbers["ordered_mismatch"] > 0 and numbers["rows_unmatched"] > 0
    assert 0 not in other["c_count"].values.tolist()


def test_the_predicate_stays_below_the_null_supplying_side(q13, draws,
                                                           executors):
    """The executed plan filters `orders` before the join, as Spark plans
    the right-only conjunct of the `ON` clause, and nothing above it."""
    inputs, _ = draws[SEEDS[0]]
    res = executors["eager"].execute(q13.plan(), inputs)
    (join,) = [n for n in res.plan.nodes if isinstance(n, HashJoin)]
    assert join.how == "left_outer"
    assert isinstance(join.right, FusedSelect) \
        and join.right.predicate.references() == {"o_special"}
    assert [n.kind for n in res.plan.nodes].count("FusedSelect") == 1
    assert not any(n.kind == "Filter" for n in res.plan.nodes)


def test_the_second_eager_execution_lowers_nothing(q13, draws,
                                                   lowers_nothing_again):
    # another seed's arrays have the same shapes
    lowers_nothing_again(q13.plan(), draws[SEEDS[1]][0], draws[SEEDS[2]][0])
