"""NDS q3/q5/q23/q72 through the plan engine, each plan in BOTH tiers —
eager (per-operator dispatch) and capped (one XLA program,
plan-granularity cap escalation) — against the pandas reference of
examples/nds.py, which shares no line with the engine. The eager q3 of
the cell `q3.share`, at its rehearsal size, lowers nothing when it runs
a second time."""
import json

import pandas as pd
import pytest

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import faultinj
from spark_rapids_tpu.plan import PlanExecutor

from examples import nds

# 15k keeps this file inside the timed tier-1 budget now that every
# executor run also optimizes (and capped runs trace the larger rewritten
# DAGs); parity at this N exercises the same shapes and assertions
N = 15_000

# query -> (seed, capped-tier caps, presentation-sort columns)
QUERIES = {
    "q3": (7, {}, ["d_year", "revenue"]),
    "q5": (3, {"key_cap": 2048}, ["channel", "id"]),
    "q23": (11, {"key_cap": 8192, "row_cap": N}, ["total"]),
    "q72": (5, {}, ["cnt", "i_item_sk", "w_warehouse_sk", "d_week"]),
}


def bound(query, n, seed):
    """-> (plan, inputs) of one query over its generated tables."""
    tables = getattr(nds, f"{query}_tables")(n, seed)
    return (getattr(nds, f"{query}_plan")(),
            getattr(nds, f"{query}_inputs")(*tables))


def assert_matches(res, ref, ordered):
    got = res.compact() if res.mode == "capped" else res.table
    assert list(got.names) == list(ref.columns)
    nds.assert_rows_equal(pd.DataFrame(got.to_pydict()), ref, ordered,
                          res.mode)


@pytest.mark.parametrize("mode", ["eager", "capped"])
@pytest.mark.parametrize("query", list(QUERIES))
def test_nds_plan_matches_pandas(query, mode):
    seed, caps, ordered = QUERIES[query]
    ref = getattr(nds, f"{query}_reference")(N, seed)
    res = PlanExecutor(mode=mode, caps=caps).execute(*bound(query, N, seed))
    assert_matches(res, ref, ordered)
    if query == "q23":
        assert ref.total[0] > 0           # the HAVING clauses selected rows
    if query == "q72" and mode == "capped":
        assert res.attempts == 1          # default caps fit: no escalation
    if query == "q3":
        # per-operator metrics are real numbers, in both tiers. Metrics
        # cover the EXECUTED plan (res.plan) — the optimizer rewrites the
        # authored tree (e.g. pruning q3's unused item columns), so node
        # counts differ
        prof = {m["label"]: m for m in res.profile()}
        assert len(prof) == len(res.plan.nodes)
        agg = next(m for m in prof.values() if m["kind"] == "HashAggregate")
        assert agg["rows_out"] == len(ref)
        assert agg["bytes_out"] > 0
        if mode == "eager":
            assert res.optimizer is not None and res.optimizer["rules_fired"]
            join1 = next(m for m in res.profile() if m["kind"] == "HashJoin")
            assert join1["wall_ms"] is not None and join1["wall_ms"] > 0


def test_the_second_eager_execution_of_q3_share_lowers_nothing(
        lowers_nothing_again):
    from chipbench import harness, tpcds
    cell = harness.Cell("q3.share", tiny=True)
    q3 = cell.plan
    gen = q3.batch_generator(cell.sizes, cell.batch)
    inputs = []
    # a resident cell's join keys are the configuration's draw: another
    # seed moves the prices and the rows' order, not a count
    for seed in (77, 4100000007):
        drawn = gen(*harness.batch_keys(cell, seed, harness.TABLE_STREAM))
        inputs.append({n: tpcds.table(c)
                       for n, c in q3.dimensions(cell.sizes).items()})
        inputs[-1].update(
            {name: tpcds.table(cols, {}, q3.COLUMNS[name])
             for name, (cols, _) in drawn.items()})
    lowers_nothing_again(q3.plan(), *inputs)


def test_nds_q23_plan_subquery_reuse():
    res = PlanExecutor().execute(*bound("q23", N, 11))
    # the two HAVING subqueries are SHARED DAG nodes: both sides reuse the
    # same Aggregate/Filter objects, so the executor ran each exactly once
    kinds = [m.kind for m in res.metrics.values()]
    assert kinds.count("HashAggregate") == 2 + 2 + 1  # freq, best, 2 side
    #                                                  totals, grand total


def test_nds_q3_plan_cap_escalation():
    """Tiny caps on the real q3 shape: the plan executor escalates every
    capacity geometrically (SplitAndRetry at plan granularity) and the
    result still matches — never truncated output."""
    # small n: each escalation attempt re-traces the whole plan at the new
    # caps, so the data size prices the test's compile bill
    ex = PlanExecutor(mode="capped", caps={"row_cap": 128, "key_cap": 16},
                      max_cap_attempts=10)
    res = ex.execute(*bound("q3", 5_000, 7))
    assert res.attempts > 1
    assert res.caps["row_cap"] > 128 and res.caps["key_cap"] > 16
    assert_matches(res, nds.q3_reference(5_000, 7), ["d_year", "revenue"])
    escal = [m.escalations for m in res.metrics.values()
             if m.kind in ("HashJoin", "HashAggregate")]
    assert all(e == res.attempts - 1 for e in escal)


def test_nds_q3_plan_injected_fault_retries(tmp_path):
    """An injected operator fault on the NDS plan surfaces as a plan-level
    retry (bounded re-run, correct result), not corruption."""
    cfg = tmp_path / "faultinj.json"
    cfg.write_text(json.dumps({"computeFaults": {
        "plan.HashAggregate": {"percent": 100, "injectionType": 1,
                               "interceptionCount": 1}}}))
    faultinj.install(str(cfg))
    try:
        res = PlanExecutor().execute(*bound("q3", 5_000, 7))
    finally:
        faultinj.uninstall()
    assert_matches(res, nds.q3_reference(5_000, 7), ["d_year", "revenue"])
    agg = next(m for m in res.metrics.values()
               if m.kind == "HashAggregate")
    assert agg.retries == 1
