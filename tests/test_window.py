"""The `Window` operator: the kernel (`ops/window.py`) and the node through
`PlanBuilder` / `PlanExecutor`, eager and capped, against a plain Python
reference on seeded data. General semantics, not one query's: nulls in
values, in partition keys and in order keys; descending order; an empty
table, one partition, partitions of one row, an all-NULL partition, a
partition whose first values are NULL; int64 and DECIMAL64 values, a
decimal `sum` into DECIMAL128 against Python integers; frames long enough
for the two-level scans; and every frame, function and type the kernel
does not lower, refused by its name.

The reference sorts rows by (partition keys, order keys, input position)
with Spark's null order and walks them once; the engine's output order is
that order too (nothing is scattered back), so the comparison is row for
row.
"""
import numpy as np
import pytest

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import dtypes
from spark_rapids_tpu.columnar import Column, Table
from spark_rapids_tpu.ops import window as window_ops
from spark_rapids_tpu.ops.scans import SCAN_BLOCK, running_in_runs
from spark_rapids_tpu.plan import PlanBuilder, PlanExecutor, col
from spark_rapids_tpu.plan.builder import PlanValidationError

MONEY = dtypes.decimal(15, 2)       # DECIMAL64
SMALL = dtypes.decimal(7, 2)        # DECIMAL32
WIDE = dtypes.decimal(25, 2)        # DECIMAL128
ALL_FUNCTIONS = [("s", "sum", "v"), ("lo", "min", "v"), ("hi", "max", "v"),
                 ("c", "count", "v")]


# ---- the plain reference ---------------------------------------------------

def _sort_key(row, partition_by, order_by, ascending, position):
    key = []
    for k in partition_by:              # any fixed order: NULL first
        key.append((row[k] is not None, row[k] or 0))
    for k, asc in zip(order_by, ascending):
        v = row[k]
        # Spark's default: NULL first ascending, last descending
        key.append((v is not None, v or 0) if asc else (v is None, -(v or 0)))
    return key + [position]


def _as_long(v: int) -> int:
    return (v + 2 ** 63) % 2 ** 64 - 2 ** 63


def reference(rows, partition_by, order_by, ascending, functions,
              wrap: bool = True):
    """`rows`: [{column: value or None}] -> the rows in (partition, order)
    order, each with one more entry a function. SQL's rule: a NULL value
    is skipped; sum / min / max are NULL until the partition's first
    value; count never is. `wrap`: a bigint sum wraps like a Java long
    (Spark, non-ANSI); a decimal's does not."""
    order = sorted(range(len(rows)), key=lambda i: _sort_key(
        rows[i], partition_by, order_by, ascending, i))
    out, state, current = [], None, object()
    for i in order:
        row = dict(rows[i])
        part = tuple(row[k] for k in partition_by)
        if part != current:
            current, state = part, {n: None for n, _, _ in functions}
            state.update({("count", n): 0 for n, _, _ in functions})
        for name, op, c in functions:
            v = row[c]
            if v is not None:
                state["count", name] += 1
                acc = state[name]
                state[name] = v if acc is None else (
                    (_as_long(acc + v) if wrap else acc + v) if op == "sum"
                    else min(acc, v) if op == "min" else max(acc, v))
            row[name] = state["count", name] if op == "count" else state[name]
        out.append(row)
    return out


# ---- data ----------------------------------------------------------------------

def _nulled(values, rng, share):
    return [None if rng.random() < share else v for v in values]


def _case(case: str, seed: int = 47):
    """-> (rows, partition_by, order_by, ascending, value dtype)."""
    rng = np.random.default_rng(seed)
    n = 300
    p = rng.integers(0, 12, n).tolist()
    q = rng.integers(0, 3, n).tolist()
    o = rng.integers(0, 40, n).tolist()
    v = rng.integers(-1000, 1000, n).tolist()
    partition_by, order_by, ascending, dt = ["p"], ["o"], [True], dtypes.INT64
    if case == "null_values":
        v = _nulled(v, rng, 0.3)
    elif case == "null_partition_keys":
        p, v = _nulled(p, rng, 0.2), _nulled(v, rng, 0.2)
    elif case == "null_order_keys":
        o, v = _nulled(o, rng, 0.2), _nulled(v, rng, 0.2)
    elif case == "descending":
        ascending, o, v = [False], _nulled(o, rng, 0.2), _nulled(v, rng, 0.2)
    elif case == "two_keys_each":
        partition_by, order_by, ascending = ["p", "q"], ["o", "v"], \
            [False, True]
        p, o = _nulled(p, rng, 0.1), _nulled(o, rng, 0.1)
    elif case == "empty":
        p, q, o, v = [], [], [], []
    elif case == "one_partition":
        p, v = [7] * n, _nulled(v, rng, 0.3)
    elif case == "no_partition_key":
        partition_by, v = [], _nulled(v, rng, 0.3)
    elif case == "partitions_of_one_row":
        p, v = list(range(n)), _nulled(v, rng, 0.3)
    elif case == "an_all_null_partition":
        v = [None if pp in (3, 5) else x for pp, x in zip(p, _nulled(
            v, rng, 0.2))]
    elif case == "first_values_null":
        v = [None if oo < 15 else x for oo, x in zip(o, v)]
    elif case == "wide_keys":
        # keys that do not fit one packed word: the operands are the keys
        wide = [-2 ** 62, -5, 0, 7, 2 ** 61, 2 ** 62]
        p = _nulled([int(x) for x in rng.choice(wide, n)], rng, 0.1)
        o = _nulled([int(x) for x in rng.choice(wide, n)], rng, 0.1)
        v = _nulled(v, rng, 0.2)
    elif case == "extremes":
        big = 2 ** 62
        v = rng.choice([-big, big - 1, -1, 0, 1, 2 ** 40, -2 ** 40, None],
                       n).tolist()
        v = [None if x is None else int(x) for x in v]
    elif case == "decimal64":
        dt, v = MONEY, _nulled(
            rng.integers(-10 ** 14, 10 ** 14, n).tolist(), rng, 0.25)
    elif case == "decimal32":
        dt, v = SMALL, _nulled(
            rng.integers(-10 ** 6, 10 ** 6, n).tolist(), rng, 0.25)
    else:
        assert case == "plain", case
    rows = [dict(p=a, q=b, o=c, v=d) for a, b, c, d in zip(p, q, o, v)]
    return rows, partition_by, order_by, ascending, dt


CASES = ("plain", "null_values", "null_partition_keys", "null_order_keys",
         "descending", "two_keys_each", "empty", "one_partition",
         "no_partition_key", "partitions_of_one_row",
         "an_all_null_partition", "first_values_null", "wide_keys",
         "extremes",
         "decimal64", "decimal32")


def _table(rows, dt) -> Table:
    names = ["p", "q", "o", "v"]
    return Table([Column.from_pylist([r[n] for r in rows],
                                     dt if n == "v" else dtypes.INT64)
                  for n in names], names)


def _rows_of(table: Table):
    cols = table.to_pydict()
    return [dict(zip(cols, vals)) for vals in zip(*cols.values())] \
        if table.num_rows else []


def _run(tier: str, table: Table, partition_by, order_by, ascending,
         functions):
    plan = (PlanBuilder().scan("t", schema=list(table.names))
            .window(functions, partition_by=partition_by, order_by=order_by,
                    ascending=ascending).build())
    if tier == "eager":
        res = PlanExecutor(mode="eager").execute(plan, {"t": table})
        return res, res.table
    res = PlanExecutor(mode="capped", caps=dict(row_cap=512, key_cap=512)) \
        .execute(plan, {"t": table})
    return res, res.compact()


# ---- the node in both tiers against the reference --------------------------------

@pytest.mark.parametrize("tier", ("eager", "capped"))
@pytest.mark.parametrize("case", CASES)
def test_window_against_the_plain_reference(case, tier):
    rows, partition_by, order_by, ascending, dt = _case(case)
    if case == "empty" and tier == "capped":
        pytest.skip("a capped program has no empty input frame")
    res, got = _run(tier, _table(rows, dt), partition_by, order_by,
                    ascending, ALL_FUNCTIONS)
    want = reference(rows, partition_by, order_by, ascending, ALL_FUNCTIONS,
                     wrap=not dt.is_decimal)
    assert _rows_of(got) == want
    # result types as Spark's: sum widens, min / max keep, count is bigint
    types = {n: got[n].dtype for n in got.names}
    assert types["c"] == dtypes.INT64 and got["c"].validity is None
    assert types["lo"] == types["hi"] == dt
    if dt.is_decimal:
        assert types["s"] == dtypes.decimal(dt.precision + 10, dt.scale)
    else:
        assert types["s"] == dtypes.INT64
    assert res.windows == 1 and res.window_rows == len(rows)
    if tier == "eager":
        assert res.window_partitions == len(
            {tuple(r[k] for k in partition_by) for r in rows})
        m = res.metrics[res.plan.root.label]
        assert m.kernel == "xla:sort_scan" and m.window_sorted == "sort"
        # one packed sort key wherever the keys' ranges fit 63 bits
        assert m.window_key == ("operands" if case in ("wide_keys", "empty")
                                else "packed")


def test_decimal_sum_is_exact_past_64_bits_and_null_on_overflow():
    """decimal(18, 0) values near 10**18 sum past int64 inside one
    partition: the result is decimal(28, 0) in limbs, exact against Python
    integers."""
    dt = dtypes.decimal(18, 0)
    top = 10 ** 18 - 1
    rows = [dict(p=i % 2, q=0, o=i, v=top - i) for i in range(40)]
    _, got = _run("eager", _table(rows, dt), ["p"], ["o"], [True],
                  [("s", "sum", "v")])
    want = reference(rows, ["p"], ["o"], [True], [("s", "sum", "v")],
                     wrap=False)
    assert _rows_of(got) == want
    assert max(r["s"] for r in want) > 2 ** 63
    assert got["s"].dtype == dtypes.decimal(28, 0)


def test_dead_rows_carry_into_nothing_under_a_cap():
    """A filter below the window leaves dead rows in the capped frame: they
    sort last and no live partition's carry reads them."""
    rows, partition_by, order_by, ascending, dt = _case("null_values")
    table = _table(rows, dt)
    plan = (PlanBuilder().scan("t", schema=list(table.names))
            .filter(col("q") > 0)
            .window(ALL_FUNCTIONS, partition_by=partition_by,
                    order_by=order_by).build())
    live = [r for r in rows if r["q"] > 0]
    want = reference(live, partition_by, order_by, ascending, ALL_FUNCTIONS)
    for mode, kw in (("eager", {}),
                     ("capped", dict(caps=dict(row_cap=512, key_cap=512)))):
        res = PlanExecutor(mode=mode, **kw).execute(plan, {"t": table})
        got = res.table if mode == "eager" else res.compact()
        assert _rows_of(got) == want, mode


@pytest.mark.parametrize("n", (5, SCAN_BLOCK * 16 + 77))
@pytest.mark.parametrize("op", ("sum", "max"))
def test_running_in_runs_against_a_loop(op, n):
    """The segmented scans over a frame short enough for one flat scan and
    one long enough for the two levels, 64-bit extremes included."""
    rng = np.random.default_rng(n)
    x = rng.integers(-2 ** 62, 2 ** 62, n, dtype=np.int64)
    x[:3] = [-2 ** 63, 2 ** 63 - 1, -1]
    head = rng.random(n) < 0.01
    head[0] = True
    rank = (np.cumsum(head) - 1).astype(np.int32)
    import jax.numpy as jnp
    got = np.asarray(running_in_runs(jnp.asarray(x), jnp.asarray(head),
                                     jnp.asarray(rank), op))
    want, acc = np.empty(n, np.int64), 0
    with np.errstate(over="ignore"):
        for i in range(n):
            acc = x[i] if head[i] else (
                acc + x[i] if op == "sum" else max(acc, x[i]))
            want[i] = acc
    assert (got == want).all()
    if op == "sum":         # a count, in 32 bits
        ones = (x > 0).astype(np.int32)
        got = np.asarray(running_in_runs(jnp.asarray(ones),
                                         jnp.asarray(head),
                                         jnp.asarray(rank)))
        run = np.cumsum(ones)
        base = np.maximum.accumulate(np.where(head, run - ones, 0))
        assert got.dtype == np.int32 and (got == run - base).all()


def test_a_long_frame_through_the_kernel():
    """Past 16 scan blocks the kernel's scans run in two levels."""
    rng = np.random.default_rng(3)
    n = SCAN_BLOCK * 16 + 501
    rows = [dict(p=int(a), q=0, o=int(b), v=None if c < 0.2 else int(d))
            for a, b, c, d in zip(rng.integers(0, 50, n),
                                  rng.integers(0, 10 ** 6, n), rng.random(n),
                                  rng.integers(-10 ** 9, 10 ** 9, n))]
    got = window_ops.window_functions(_table(rows, dtypes.INT64), ["p"], ["o"], [True],
                            ALL_FUNCTIONS)
    assert _rows_of(got) == reference(rows, ["p"], ["o"], [True],
                                      ALL_FUNCTIONS)


# ---- a child that lies in order already -------------------------------------------

def test_a_window_over_a_sorted_group_by_takes_the_childs_order():
    """The group-by's output says it lies in key order (`Table.ordered_by`):
    a window whose (partition, order) keys are those keys does not sort,
    says so, and gives the sorting kernel's answer; a renaming projection
    in between carries the order, a descending window sorts."""
    rows, _, _, _, dt = _case("null_partition_keys")
    table = _table(rows, dt)

    def build(rename: bool, ascending: bool):
        rel = (PlanBuilder().scan("t", schema=list(table.names))
               .aggregate(["p", "o"], [("v", "sum", "sv")]))
        keys = ("p", "o")
        if rename:
            rel = rel.project({"item": col("p"), "day": col("o"),
                               "sv": col("sv")})
            keys = ("item", "day")
        return rel.window([("run", "sum", "sv"), ("top", "max", "sv")],
                          partition_by=[keys[0]], order_by=[keys[1]],
                          ascending=ascending).build(), keys

    groups = {}
    for r in rows:
        g = groups.setdefault((r["p"], r["o"]), [None])
        if r["v"] is not None:
            g[0] = r["v"] if g[0] is None else g[0] + r["v"]
    for rename in (False, True):
        for ascending in (True, False):
            plan, keys = build(rename, ascending)
            res = PlanExecutor(mode="eager").execute(plan, {"t": table})
            m = res.metrics[res.plan.root.label]    # the executed plan
            assert m.window_sorted == ("child" if ascending else "sort")
            grouped = [{keys[0]: p, keys[1]: o, "sv": s[0]}
                       for (p, o), s in groups.items()]
            want = reference(grouped, [keys[0]], [keys[1]], [ascending],
                             [("run", "sum", "sv"), ("top", "max", "sv")])
            assert _rows_of(res.table) == want
            assert res.table.ordered_by == (keys if ascending else ())


def test_ordered_by_is_a_statement_about_one_table_object():
    t = Table([Column.from_pylist([1, 2], dtypes.INT64)], ["a"],
              ordered_by=["a"])
    assert t.ordered_by == ("a",)
    assert t.select(["a"]).ordered_by == ()
    import jax
    leaves, tree = jax.tree_util.tree_flatten(t)
    assert jax.tree_util.tree_unflatten(tree, leaves).ordered_by == ()


def test_a_string_column_rides_along_and_can_be_counted():
    """A column whose buffers are not one word a row is gathered by the
    sort's order; `count` reads its validity alone."""
    names = ["p", "o", "s"]
    p, o = [1, 0, 1, 0, 1], [3, 2, 1, 1, 2]
    s = ["c", None, "a", "bb", None]
    table = Table([Column.from_pylist(p, dtypes.INT64),
                   Column.from_pylist(o, dtypes.INT64),
                   Column.from_pylist(s, dtypes.STRING)], names)
    got = window_ops.window_functions(table, ["p"], ["o"], [True],
                            [("c", "count", "s")])
    rows = [dict(p=a, o=b, s=c) for a, b, c in zip(p, o, s)]
    assert _rows_of(got) == reference(rows, ["p"], ["o"], [True],
                                      [("c", "count", "s")])


# ---- what the kernel does not lower, refused by name ------------------------------

def _scan(names=("p", "o", "v")):
    return PlanBuilder().scan("t", schema=list(names))


@pytest.mark.parametrize("frame", ("whole", "rows_1_preceding", "range"))
def test_a_frame_that_is_not_lowered_is_refused_by_name(frame):
    with pytest.raises(PlanValidationError,
                       match=f"window frame '{frame}' is not lowered"):
        _scan().window([("s", "sum", "v")], partition_by=["p"],
                       order_by=["o"], frame=frame)
    with pytest.raises(ValueError, match="is not lowered"):
        window_ops.check_frame(frame, "sum")


@pytest.mark.parametrize("op", ("row_number", "rank", "lag", "lead", "mean",
                                "first"))
def test_a_function_that_is_not_lowered_is_refused_by_name(op):
    with pytest.raises(PlanValidationError,
                       match=f"window function '{op}' is not lowered"):
        _scan().window([("s", op, "v")], partition_by=["p"], order_by=["o"])
    with pytest.raises(ValueError, match="is not lowered"):
        window_ops.check_frame("running", op)


@pytest.mark.parametrize("what,kwargs,match", (
    ("no_order", dict(partition_by=["p"]), "needs an order"),
    ("no_function", dict(partition_by=["p"], order_by=["o"]),
     "at least one window function"),
    ("unknown_partition_key", dict(partition_by=["x"], order_by=["o"]),
     r"partition key\(s\) \['x'\]"),
    ("unknown_order_key", dict(partition_by=["p"], order_by=["x"]),
     r"order key\(s\) \['x'\]"),
    ("unknown_input", dict(partition_by=["p"], order_by=["o"]),
     "window function input 'x'"),
    ("duplicate_name", dict(partition_by=["p"], order_by=["o"]),
     "duplicate output name"),
    ("ascending_length", dict(partition_by=["p"], order_by=["o"],
                              ascending=[True, False]),
     "ascending list must match"),
))
def test_a_malformed_window_is_a_validation_error(what, kwargs, match):
    functions = {"no_function": [], "unknown_input": [("s", "sum", "x")],
                 "duplicate_name": [("v", "sum", "v")]}.get(
                     what, [("s", "sum", "v")])
    with pytest.raises(PlanValidationError, match=match):
        _scan().window(functions, **kwargs).build()


@pytest.mark.parametrize("op,dt,invariant,match", (
    ("sum", WIDE, "typing.window-not-lowered", repr(WIDE)),
    ("max", WIDE, "typing.window-not-lowered", repr(WIDE)),
    ("sum", dtypes.FLOAT64, "typing.window-not-lowered", repr(dtypes.FLOAT64)),
    ("min", dtypes.FLOAT32, "typing.window-not-lowered", repr(dtypes.FLOAT32)),
    ("sum", dtypes.STRING, "typing.window-not-lowered",
     repr(dtypes.STRING)),
    ("sum", dtypes.DATE32, "typing.window-not-lowered", "window sum over"),
    ("sum", dtypes.BOOL, "typing.window-not-lowered", repr(dtypes.BOOL)),
))
def test_a_value_type_that_is_not_lowered_is_refused_by_name(
        op, dt, invariant, match):
    from spark_rapids_tpu.analysis import verifier
    plan = _scan().window([("s", op, "v")], partition_by=["p"],
                          order_by=["o"]).build()
    report = verifier.verify(plan, input_dtypes={"t": {
        "p": dtypes.INT64, "o": dtypes.INT64, "v": dt}})
    assert [v.invariant for v in report.violations] == [invariant]
    assert match in report.violations[0].message
    with pytest.raises(TypeError, match="is not lowered"):
        window_ops.result_type(op, dt)


@pytest.mark.parametrize("role", ("partition", "order"))
def test_a_string_key_is_refused_by_name(role):
    from spark_rapids_tpu.analysis import verifier
    keys = dict(partition_by=["p"], order_by=["o"])
    plan = _scan().window([("s", "sum", "v")], **keys).build()
    types = {"p": dtypes.INT64, "o": dtypes.INT64, "v": dtypes.INT64}
    types["p" if role == "partition" else "o"] = dtypes.STRING
    report = verifier.verify(plan, input_dtypes={"t": types})
    assert [v.invariant for v in report.violations] \
        == ["typing.window-key-not-fixed-width"]
    assert f"window {role} key" in report.violations[0].message
    assert repr(dtypes.STRING) in report.violations[0].message
    # and the kernel itself, called directly
    table = Table([Column.from_pylist(["a"], dtypes.STRING),
                   Column.from_pylist([1], dtypes.INT64)], ["k", "v"])
    by = dict(partition_by=["k"], order_by=["v"]) if role == "partition" \
        else dict(partition_by=["v"], order_by=["k"])
    with pytest.raises(TypeError, match=f"window {role} key 'k'"):
        window_ops.window_functions(table, by["partition_by"], by["order_by"], [True],
                          [("c", "count", "v")])


def test_result_types_are_sparks():
    from spark_rapids_tpu.analysis import verifier
    plan = _scan(("p", "o", "i", "m", "d")).window(
        [("si", "sum", "i"), ("sm", "sum", "m"), ("xm", "max", "m"),
         ("nd", "min", "d"), ("cd", "count", "d")],
        partition_by=["p"], order_by=["o"]).build()
    inputs = {"t": {"p": dtypes.INT64, "o": dtypes.DATE32,
                    "i": dtypes.INT32, "m": MONEY, "d": dtypes.DATE32}}
    assert verifier.verify(plan, input_dtypes=inputs).ok
    types = verifier.column_types(plan.nodes, plan.schemas, inputs)[
        id(plan.root)]
    assert types["si"] == dtypes.INT64
    assert types["sm"] == dtypes.decimal(25, 2)
    assert types["xm"] == MONEY and types["nd"] == dtypes.DATE32
    assert types["cd"] == dtypes.INT64 and types["i"] == dtypes.INT32


def test_explain_and_fingerprint_know_the_node():
    a = _scan().window([("s", "sum", "v")], partition_by=["p"],
                       order_by=["o"], ascending=False).build()
    b = _scan().window([("s", "sum", "v")], partition_by=["p"],
                       order_by=["o"], ascending=False).build()
    c = _scan().window([("s", "max", "v")], partition_by=["p"],
                       order_by=["o"], ascending=False).build()
    assert "sum(v) AS s over (partition by [p] order by [o DESC] running)" \
        in a.explain()
    assert a.fingerprint == b.fingerprint != c.fingerprint
