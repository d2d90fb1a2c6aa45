"""TPC-DS q51 (`chipbench/plans/q51.py`) and what it forced, through
`PlanBuilder` and `PlanExecutor`: the template in both tiers against the
plan file's plain pandas reference at the configuration's rehearsal size
(limits 0, three seeds); every control differs from it; each optimizer
rule decided for `Window` (the predicate that may pass below one and the
one that may not, pruning, what never reorders across it); the certifier;
a mesh keeps the plan local and names the node; the fuzzer draws it.
"""
import numpy as np
import pytest

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import dtypes
from spark_rapids_tpu.columnar import Column, Table
from spark_rapids_tpu.plan import PlanBuilder, PlanExecutor, col, is_null
from spark_rapids_tpu.plan.nodes import (Filter, FusedSelect, HashJoin,
                                         Project, Scan, TopK, Window)

TIERS = ("eager", "capped")
SEEDS = (2 ** 31 + 47, 51, 4100000051)
EXACT = {"ordered_mismatch": 0, "rows_unmatched": 0}


# ---- the template at the rehearsal size ----------------------------------------

@pytest.fixture(scope="module")
def cell():
    from chipbench import harness
    return harness.Cell("q51.batch", tiny=True)


@pytest.fixture(scope="module")
def q51(cell):
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
@pytest.mark.parametrize("tier", TIERS)
def test_q51_equals_the_plain_reference(cell, q51, draws, executors, tier,
                                        seed):
    from chipbench import check
    inputs, tables = draws[seed]
    ref = q51.reference(tables)
    res = executors[tier].execute(q51.plan(), inputs)
    assert res.degraded is False
    assert check.compare(check.to_host(res), ref, q51.RESULT_COLUMNS,
                         q51.ORDERED) == EXACT
    assert len(ref) == 100
    # the answer's NULLs are there: a day the web channel did not sell the
    # item still carries the web total so far
    carried = (ref["web_sales_null"].values == 1) \
        & (ref["web_cumulative"].values > 0)
    assert carried.any() and (ref["store_sales_null"].values == 1).any()
    # the fixed draw's counts, by the reference and by the configuration
    counts, batch = q51.COUNTS, cell.batch
    assert {k: counts[k] for k in ("store_date_rows", "web_date_rows",
                                   "store_groups", "web_groups",
                                   "join_rows", "filter_rows")} \
        == {k: batch[k] for k in ("store_date_rows", "web_date_rows",
                                  "store_groups", "web_groups", "join_rows",
                                  "filter_rows")}
    assert counts["matched"] == batch["matched_pairs"]
    # the request's three windows, by the program's own counts
    assert (res.windows, res.window_rows) == (3, counts["window_rows"])
    assert res.full_joins == 1
    assert (res.group_rows, res.groups) == (
        batch["store_date_rows"] + batch["web_date_rows"],
        batch["store_groups"] + batch["web_groups"])
    if tier == "eager":
        assert res.window_partitions == counts["window_partitions"]
        # the channels' windows read a sorted group-by's output on its own
        # keys and sort nothing; the one above the full join sorts
        took = [m.window_sorted for n, m in zip(res.plan.nodes,
                                                res.metrics.values())
                if isinstance(n, Window)]
        assert sorted(took) == ["child", "child", "sort"]


@pytest.mark.parametrize("control", ("no_partition", "null_as_zero",
                                     "no_carry", "restart_at_null", "inner",
                                     "bfloat16"))
def test_a_control_fails_the_comparison(q51, draws, control):
    from chipbench import check
    from chipbench.control import bf16
    _, tables = draws[SEEDS[0]]
    ref = q51.reference(tables)
    other = (q51.reference(tables, lossy=bf16) if control == "bfloat16"
             else q51.reference(tables, control=control))
    got = {c: other[c].values for c in q51.RESULT_COLUMNS}
    numbers = check.compare(got, ref, q51.RESULT_COLUMNS, q51.ORDERED)
    assert any(numbers[k] > lim for k, lim in check.LIMITS.items()), numbers


def test_the_reference_against_a_second_computation(q51, draws):
    """Python dicts and loops, None for a null: sums by (item, day), a
    running total an item, the union of the two channels' days, a carried
    maximum a side, the comparison."""
    _, tables = draws[SEEDS[1]]
    ref = q51.reference(tables)
    dd = tables["date_dim"][0]
    seq = np.asarray(dd["d_month_seq"])
    day_of = {int(k): int(d) for k, d, s in zip(
        np.asarray(dd["d_date_sk"]), np.asarray(dd["d_date"]), seq)
        if 1200 <= s <= 1211}
    assert len(day_of) == 366
    cume = {}
    for name in ("web_sales", "store_sales"):
        cols, validity = tables[name]
        date, item, price = ([v if ok else None for v, ok in zip(
            np.asarray(cols[n]).tolist(), np.asarray(validity[n]).tolist())]
            for n in q51.COLUMNS[name])
        sums = {}
        for d, i, p in zip(date, item, price):
            if d in day_of and i is not None:
                key = (i, day_of[d])
                sums.setdefault(key, None)
                if p is not None:
                    sums[key] = p if sums[key] is None else sums[key] + p
        total, last, out = None, None, {}
        for key in sorted(sums):
            if key[0] != last:
                total, last = None, key[0]
            if sums[key] is not None:
                total = sums[key] if total is None else total + sums[key]
            out[key] = total
        cume[name] = out
    web, store = cume["web_sales"], cume["store_sales"]
    rows, last = [], None
    for key in sorted(set(web) | set(store)):
        if key[0] != last:
            web_top = store_top = None
            last = key[0]
        w, s = web.get(key), store.get(key)
        if w is not None:
            web_top = w if web_top is None else max(web_top, w)
        if s is not None:
            store_top = s if store_top is None else max(store_top, s)
        if web_top is not None and store_top is not None \
                and web_top > store_top:
            rows.append((*key, w or 0, int(w is None), s or 0,
                         int(s is None), web_top, store_top))
    assert len(rows) == q51.COUNTS["filter_rows"]
    got = list(zip(*(ref[c].values.tolist() for c in q51.RESULT_COLUMNS)))
    assert got == rows[:100]


def test_the_generator_holds_its_fixed_counts_whatever_the_seed(cell, q51,
                                                                draws):
    from chipbench import harness
    for seed, (_, tables) in draws.items():
        for name, rows in (("store_sales", cell.batch["store_rows"]),
                           ("web_sales", cell.batch["web_rows"])):
            cols, validity = tables[name]
            date, item, price = (np.asarray(cols[c])
                                 for c in q51.COLUMNS[name])
            assert date.shape == item.shape == price.shape == (rows,)
            assert (item % cell.sizes["ranks"]
                    == cell.sizes["rank"] + 1).all()
            assert price.min() >= 1 and price.max() <= q51.MAX_PRICE
            d, i, p = (np.asarray(validity[c]) for c in q51.COLUMNS[name])
            assert i.all()                  # an item key is never null
            assert 0.04 < 1 - d.mean() < 0.05 and 0.04 < 1 - p.mean() < 0.05
    a, b = (t for _, t in list(draws.values())[:2])
    for name in ("store_sales", "web_sales"):
        assert all((np.asarray(a[name][0][c]) != np.asarray(b[name][0][c]))
                   .any() for c in q51.COLUMNS[name])
    wrong = q51.batch_generator(
        cell.sizes, dict(cell.batch, matched_pairs=1))
    with pytest.raises(ValueError, match="the configuration states"):
        wrong(*harness.batch_keys(cell, 3, harness.TABLE_STREAM))


def test_byte_functions_against_hand_counts(q51):
    from chipbench import tpcds
    batch = {"store_rows": 3000, "web_rows": 700, "store_groups": 600,
             "web_groups": 200, "join_rows": 750}
    assert q51.fact_rows(batch) == 3700
    # 27 B a fact row (three int64 and their validity bytes), 24 a day,
    # eight int64 a result row
    assert q51.least_bytes(batch, {}, 100) \
        == 3700 * 27 + tpcds.N_DATES * 24 + 100 * 64
    # a channel's window: three columns read, one written, 9 B a cell; the
    # maxima: four read, two written
    want = (600 + 200) * 4 * 9 + 750 * 6 * 9
    assert q51.window_bytes(batch, {}, dict(batch)) == want
    q51.COUNTS.clear()
    assert q51.window_bytes(batch, {}) == want


def test_the_second_eager_execution_lowers_nothing(q51, draws,
                                                   lowers_nothing_again):
    # another seed's arrays have the same shapes AND the same counts
    lowers_nothing_again(q51.plan(), draws[SEEDS[1]][0], draws[SEEDS[2]][0])


# ---- each optimizer rule, decided for `Window` -------------------------------------

def _column(values) -> Column:
    return Column.from_pylist(list(values), dtypes.INT64)


def _inputs(seed: int = 51):
    rng = np.random.default_rng(seed)
    n = 120
    nulled = lambda vs, every: [None if i % every == 0 else int(v)
                                for i, v in enumerate(vs)]
    t = {"k": nulled(rng.integers(0, 6, n), 11),
         "o": rng.integers(0, 30, n).tolist(),
         "v": nulled(rng.integers(-50, 50, n), 4),
         "u": rng.integers(0, 9, n).tolist()}
    return {"t": Table([_column(t[c]) for c in t], list(t))}, t


def _window_plan(above=None, functions=(("run", "sum", "v"),
                                        ("top", "max", "v")), select=None):
    rel = (PlanBuilder().scan("t", schema=["k", "o", "v", "u"])
           .window(list(functions), partition_by=["k"], order_by=["o", "u"]))
    if above is not None:
        rel = rel.filter(above)
    if select is not None:
        rel = rel.select(select)
    return rel.build()


def _rows(res):
    t = res.compact()
    return list(zip(*(t[n].to_pylist() for n in t.names)))


def _the_window(plan) -> Window:
    (w,) = [n for n in plan.nodes if isinstance(n, Window)]
    return w


def _filters_below(node) -> bool:
    seen, todo = False, [node]
    while todo:
        n = todo.pop()
        seen = seen or isinstance(n, (Filter, FusedSelect))
        todo.extend(n.children)
    return seen


def _run(tier, plan, inputs, **kw):
    if tier == "capped":
        return PlanExecutor(mode="capped", caps=dict(row_cap=256,
                                                     key_cap=256),
                            **kw).execute(plan, inputs)
    return PlanExecutor(mode="eager", **kw).execute(plan, inputs)


@pytest.mark.parametrize("tier", TIERS)
def test_a_predicate_over_partition_keys_alone_passes_below(tier):
    """It keeps or drops whole partitions, a NULL key's included."""
    inputs, _ = _inputs()
    for above in (col("k") >= 3, is_null(col("k"))):
        plan = _window_plan(above=above)
        res = _run(tier, plan, inputs)
        assert res.optimizer["rules_fired"].get("predicate_pushdown", 0) >= 1
        w = _the_window(res.plan)
        assert _filters_below(w.child)
        off = _run(tier, plan, inputs, optimize=False)
        assert _rows(res) == _rows(off) and _rows(res)


@pytest.mark.parametrize("reads", ("order_key", "value", "function",
                                   "partition_and_function"))
@pytest.mark.parametrize("tier", TIERS)
def test_a_predicate_that_reads_anything_else_stays_above(tier, reads):
    """Below the window it would change the frames of the rows it keeps
    (q51's `web_cumulative > store_cumulative` reads two functions)."""
    inputs, _ = _inputs()
    above = {"order_key": col("o") > 10, "value": col("v") > 0,
             "function": col("top") > col("run"),
             "partition_and_function": (col("k") >= 2) & (col("run") > 0)
             }[reads]
    plan = _window_plan(above=above)
    res = _run(tier, plan, inputs)
    assert not _filters_below(_the_window(res.plan).child)
    off = _run(tier, plan, inputs, optimize=False)
    assert _rows(res) == _rows(off) and _rows(res)


def test_pruning_keeps_keys_and_inputs_and_drops_an_unread_function():
    inputs, _ = _inputs()
    plan = _window_plan(select=["k", "top"])
    res = PlanExecutor(mode="eager").execute(plan, inputs)
    w = _the_window(res.plan)
    assert w.functions == (("top", "max", "v"),)        # `run` is not read
    (scan,) = [n for n in res.plan.nodes if isinstance(n, Scan)]
    # partition, order and the kept function's input stay
    assert scan.projection is None          # all four: nothing to narrow
    off = PlanExecutor(mode="eager", optimize=False).execute(plan, inputs)
    assert _rows(res) == _rows(off)
    # a column nobody reads above and the window does not need goes
    b = PlanBuilder()
    narrow = (b.scan("t", schema=["k", "o", "v", "u"])
              .window([("top", "max", "v")], partition_by=["k"],
                      order_by=["o"]).select(["top"]).build())
    res = PlanExecutor(mode="eager").execute(narrow, inputs)
    (scan,) = [n for n in res.plan.nodes if isinstance(n, Scan)]
    assert scan.projection == ("k", "o", "v")
    # a window whose every function is unread keeps one: its rows and
    # their order are its output too
    bare = (b.scan("t2", schema=["k", "o", "v", "u"])
            .window([("a", "sum", "v"), ("b", "max", "u")],
                    partition_by=["k"], order_by=["o"]).select(["k"])
            .build())
    res = PlanExecutor(mode="eager").execute(bare, {"t2": inputs["t"]})
    assert len(_the_window(res.plan).functions) == 1


def test_nothing_reorders_across_a_window():
    """A limit stays above it, a build-side swap below it is refused (rows
    that tie take the frame in the child's order), and Limit(Sort) above
    it still fuses."""
    from spark_rapids_tpu.plan.optimizer import _order_safe_ids
    inputs, t = _inputs()
    b = PlanBuilder()
    small = b.scan("s", schema=["sk"], est_rows=3)
    plan = (small.join(b.scan("t", schema=["k", "o", "v", "u"],
                              est_rows=4000), left_on="sk", right_on="k")
            .window([("run", "sum", "v")], partition_by=["k"],
                    order_by=["o"])
            .aggregate(["k"], [("run", "max", "m")]).build())
    (join,) = [n for n in plan.nodes if isinstance(n, HashJoin)]
    assert id(join) not in _order_safe_ids(plan.root)
    res = PlanExecutor(mode="eager").execute(
        plan, {"s": Table([_column([1, 2, 3])], ["sk"]), "t": inputs["t"]})
    assert not res.optimizer["rules_fired"].get("build_side", 0)
    limited = (PlanBuilder().scan("t", schema=["k", "o", "v", "u"])
               .window([("run", "sum", "v")], partition_by=["k"],
                       order_by=["o", "u"])
               .sort(["k", "o", "u"]).limit(7).build())
    res = PlanExecutor(mode="eager").execute(limited, inputs)
    kinds = [type(n) for n in res.plan.nodes]
    assert kinds == [Scan, Window, TopK]
    off = PlanExecutor(mode="eager", optimize=False).execute(limited, inputs)
    assert _rows(res) == _rows(off) and len(_rows(res)) == 7


@pytest.mark.parametrize("tier", TIERS)
def test_results_equal_with_the_optimizer_on_and_off(q51, draws, tier,
                                                     executors, cell):
    inputs, _ = draws[SEEDS[0]]
    on = executors[tier].execute(q51.plan(), inputs)
    kw = dict(caps=q51.caps(cell.batch)) if tier == "capped" else {}
    off = PlanExecutor(mode=tier, optimize=False, **kw).execute(
        q51.plan(), inputs)
    assert _rows(on) == _rows(off) and len(_rows(on)) == 100
    # q51's last filter reads two functions and stays above its window
    windows = [n for n in on.plan.nodes if isinstance(n, Window)]
    top = max(windows, key=lambda n: len(n.functions))
    assert isinstance(top.child, Project) \
        and isinstance(top.child.child, HashJoin)


@pytest.mark.parametrize("tier", TIERS)
def test_a_filter_on_is_not_null_drops_the_columns_mask(tier):
    """`where k is not null` (q51's `ws_item_sk is not null`): the rows
    kept hold no NULL in `k`, so the filter's output carries no validity
    mask for it, a fused select's neither, and the sorts above carry no
    null rank for the key; another column keeps its mask, and so does `k`
    under a predicate that proves nothing of it."""
    from spark_rapids_tpu.plan import is_not_null
    from spark_rapids_tpu.plan.expr import not_null_columns
    inputs, t = _inputs()
    scan = lambda: PlanBuilder().scan("t", schema=["k", "o", "v", "u"])
    kept = [r for r in zip(t["k"], t["o"], t["v"], t["u"])
            if r[0] is not None and r[3] > 2]
    for plan in (scan().filter(is_not_null(col("k")) & (col("u") > 2))
                 .build(),
                 scan().filter(is_not_null(col("k")) & (col("u") > 2))
                 .select(["k", "o", "v", "u"]).build()):
        res = _run(tier, plan, inputs)
        assert res.table["k"].validity is None
        assert res.table["v"].validity is not None
        assert _rows(res) == kept
    res = _run(tier, scan().filter(is_null(col("k")) | (col("u") > 2))
               .build(), inputs)
    assert res.table["k"].validity is not None
    assert not_null_columns(is_not_null(col("a")) & (col("b") > 1)
                            & is_not_null(col("c") + 1)) == {"a"}
    assert not_null_columns(is_not_null(col("a")) | (col("b") > 1)) == set()
    # the group-by above sorts by the key alone: no null-rank operand
    plan = (scan().filter(is_not_null(col("k")))
            .aggregate(["k"], [("v", "sum", "s")]).build())
    res = _run(tier, plan, inputs)
    assert res.table["k"].validity is None
    sums = {}
    for k, v in zip(t["k"], t["v"]):
        if k is not None:
            sums.setdefault(k, None)
            if v is not None:
                sums[k] = v if sums[k] is None else sums[k] + v
    assert dict(_rows(res)) == sums
    # and the certifier knows: a group once there is a row
    from spark_rapids_tpu.analysis import footprint
    cert = footprint.certify(
        plan, bound_rows={"t": 120},
        input_nullable={"t": dict.fromkeys(["k", "o", "v", "u"], True)})
    (f,) = [i for i, n in enumerate(plan.nodes) if isinstance(n, Filter)]
    assert cert.by_index[f].rows_lo == 0        # the filter may keep none


# ---- the certifier, a mesh, the fuzzer ------------------------------------------

def test_the_certifier_bounds_a_window():
    from spark_rapids_tpu.analysis import footprint
    plan = _window_plan()
    i = plan.nodes.index(_the_window(plan))
    types = {"t": dict.fromkeys(["k", "o", "v", "u"], dtypes.INT64)}
    cert = footprint.certify(plan, bound_rows={"t": 120},
                             input_dtypes=types)
    # a row in, a row out; six columns of 9 B
    assert (cert.by_index[i].rows_lo, cert.by_index[i].rows_hi) == (120, 120)
    assert cert.by_index[i].row_bytes == 6 * 9
    # a running sum is nullable where its input is, a count never: a keyed
    # aggregate over the function's column has a group once it has a row
    # only where the column cannot be null
    lo = {}
    for name, fn, nullable in (("sum_nullable", "sum", True),
                               ("sum_not_null", "sum", False),
                               ("count", "count", True)):
        p = (PlanBuilder().scan("t", schema=["k", "o", "v", "u"])
             .window([("f", fn, "v")], partition_by=["k"], order_by=["o"])
             .aggregate(["f"], [("f", "size", "n")]).build())
        c = footprint.certify(
            p, bound_rows={"t": 5}, input_dtypes=types,
            input_nullable={"t": {"k": True, "o": False, "v": nullable,
                                  "u": False}})
        lo[name] = c.by_index[len(p.nodes) - 1].rows_lo
    assert lo == {"sum_nullable": 0, "sum_not_null": 1, "count": 1}


def test_under_a_mesh_the_plan_stays_local_and_names_the_node():
    from spark_rapids_tpu.plan.optimizer import (_statically_distributable,
                                                 mesh_local_reason, optimize)
    inputs, _ = _inputs()
    plan = _window_plan()
    ex = PlanExecutor(mode="eager", mesh=4)
    res = ex.execute(plan, inputs)
    one = PlanExecutor(mode="eager").execute(plan, inputs)
    assert _rows(res) == _rows(one)
    assert res.dist_ops == 0 and res.local_ops == 0
    why = res.optimizer["decision_sources"]
    (key,) = [k for k in why if k.endswith("/mesh")]
    assert key.startswith("Window") and why[key].startswith("local") \
        and "a window has no distributed lowering" in why[key]
    assert "a window has no distributed lowering" in ex.explain(
        plan, optimized=True, inputs=inputs)
    assert mesh_local_reason(plan.nodes)[0] == plan.root.label
    assert not _statically_distributable(plan.root, False)
    opt, report = optimize(plan, {"t": ("k", "o", "v", "u")}, {"t": 120},
                           mesh_peers=4)
    assert report.rules["exchange_planning"] == 0
    # a capped executor with a mesh refuses the node by name
    with pytest.raises(Exception, match="Window"):
        PlanExecutor(mode="capped", mesh=4).execute(plan, inputs)


def test_the_fuzzer_draws_the_node_and_it_holds():
    from spark_rapids_tpu.analysis.fuzz import ALL_KINDS, gen_case, run_case
    assert "Window" in ALL_KINDS
    drew = [s for s in range(120) if "Window" in gen_case(s).kinds]
    assert len(drew) >= 10, drew
    for seed in drew[:6]:
        result = run_case(gen_case(seed))
        assert result.ok, (seed, result)
