"""Typed plan expressions and decimal aggregates (docs/plan.md "Typed
expressions") through `PlanExecutor` in both tiers and a serving session,
against a plain reference that shares nothing with `ops/decimal*`: Spark's
type rules and exact Python-integer arithmetic written out here, and
`chipbench/plans/tpch_q1.py`'s for the whole query.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, Table, dtypes
from spark_rapids_tpu.plan import (PlanBuilder, PlanExecutor,
                                   PlanValidationError, col)

TIERS = ("eager", "capped")


# ---- the plain reference: Spark 3.5's rules, exact integers ---------------------

def adjust(p, s):
    if p <= 38:
        return p, s
    return 38, max(38 - (p - s), min(s, 6))


def binop_type(op, a, b):
    (p1, s1), (p2, s2) = a, b
    if op == "*":
        return adjust(p1 + p2 + 1, s1 + s2)
    s = max(s1, s2)
    return adjust(max(p1 - s1, p2 - s2) + s + 1, s)


def half_up(num, den):
    q, r = divmod(abs(num), den)
    q += 2 * r >= den
    return -q if num < 0 else q


def fits(v, p):
    return v if v is not None and abs(v) < 10 ** p else None


def binop_value(op, x, a, y, b):
    """Exact `x op y` at the result type, None on overflow (or a null)."""
    if x is None or y is None:
        return None
    p, s = binop_type(op, a, b)
    if op == "*":
        exact, at = x * y, a[1] + b[1]
    else:
        at = max(a[1], b[1])
        x, y = x * 10 ** (at - a[1]), y * 10 ** (at - b[1])
        exact = x + y if op == "+" else x - y
    return fits(half_up(exact, 10 ** (at - s)) if at > s
                else exact * 10 ** (s - at), p)


def sum_value(values, t):
    live = [v for v in values if v is not None]
    return fits(sum(live), min(t[0] + 10, 38)) if live else None


def avg_value(values, t):
    live = [v for v in values if v is not None]
    total = sum_value(values, t)
    if total is None:
        return None
    p, s = min(t[0] + 10, 38), t[1]
    qs = max(6, s + 20 + 1)                      # the divide rule, count is
    qp, qs = adjust(p - s + qs, qs)              # decimal(20,0)
    q = fits(half_up(total * 10 ** (qs - s), len(live)), qp)
    rp, rs = min(t[0] + 4, 38), min(t[1] + 4, 38)
    if q is None:
        return None
    return fits(half_up(q, 10 ** (qs - rs)) if qs > rs
                else q * 10 ** (rs - qs), rp)


# ---- helpers -----------------------------------------------------------------------

def dcol(values, p, s):
    return Column.from_pylist(list(values), dtypes.decimal(p, s))


def icol(values):
    return Column.from_numpy(np.asarray(values, np.int64))


def run(plan, inputs, tier, key_cap=8):
    ex = (PlanExecutor(mode="eager") if tier == "eager" else
          PlanExecutor(mode="capped", caps=dict(row_cap=64, key_cap=key_cap)))
    res = ex.execute(plan, inputs)
    assert res.degraded is False
    return res, res.compact()


def typed(c):
    return (c.dtype.precision, c.dtype.scale), c.to_pylist()


def verify(plan, inputs):
    from spark_rapids_tpu.analysis import footprint, verifier
    return verifier.verify(
        plan, bound={n: tuple(t.names) for n, t in inputs.items()},
        input_dtypes=footprint.table_metadata(inputs)[0])


# ---- expressions: every derived type of Q1's table, and the edges ----------------

M = (15, 2)
BIG = 10 ** 17 - 1
EXPRESSIONS = {
    # name: (expression over a, b, c; their types; values per column)
    "one_minus": (lambda a, b, c: 1 - b, (M, M, M), (16, 2)),
    "one_plus": (lambda a, b, c: 1 + c, (M, M, M), (16, 2)),
    "disc_price": (lambda a, b, c: a * (1 - b), (M, M, M), (32, 4)),
    "charge": (lambda a, b, c: a * (1 - b) * (1 + c), (M, M, M), (38, 6)),
    "wider_than_64_bits": (lambda a, b, c: a * b,
                           ((18, 2), (18, 2), M), (37, 4)),
    "add_unequal_scales": (lambda a, b, c: a + b,
                           ((10, 2), (12, 4), M), (13, 4)),
    "sub_to_limbs": (lambda a, b, c: a - b, ((18, 2), (18, 6), M), (23, 6)),
    "literal_times": (lambda a, b, c: a * 100, (M, M, M), (19, 2)),
    "negate": (lambda a, b, c: -(a * b), ((18, 2), (18, 2), M), (37, 4)),
    # 77 digits at scale 20, adjusted to (38, 6): HALF_UP on 14 digits
    "half_up_rescale": (lambda a, b, c: a * b,
                        ((38, 10), (38, 10), M), (38, 6)),
}
VALUES = {
    "wider_than_64_bits": ([BIG, -BIG, 12345678901234567, 0],
                           [BIG, BIG, -98765432109876543, 5]),
    "negate": ([BIG, -BIG, 3, 0], [BIG, 7, -9, 5]),
    "sub_to_limbs": ([BIG, -BIG, 5, 0], [-BIG, BIG, 5000001, 1]),
    # products ending in ...5 x 10^13 at scale 20 are exact ties
    "half_up_rescale": ([15 * 10 ** 9, -15 * 10 ** 9, 25 * 10 ** 9, 10 ** 24],
                        [10 ** 4, 10 ** 4, -3 * 10 ** 4 + 0, 10 ** 23]),
}


def _expression_case(name):
    build, types, want_type = EXPRESSIONS[name]
    rng = np.random.default_rng(len(name))
    n = 4
    a, b = VALUES.get(name, (rng.integers(-10 ** 9, 10 ** 9, n).tolist(),
                             rng.integers(0, 11, n).tolist()))
    c = rng.integers(0, 9, n).tolist()
    if name in ("one_minus", "disc_price", "charge"):
        a = [10495000, 90100, -10495000, 1]      # dbgen's price range, signed
    t = Table([dcol(a, *types[0]), dcol(b, *types[1]), dcol(c, *types[2])],
              names=["a", "b", "c"])
    # the reference walks the same tree over (value, type) pairs
    class V:
        def __init__(self, vals, typ):
            self.vals, self.typ = vals, typ

        @staticmethod
        def lift(o):
            return o if isinstance(o, V) else V([o] * n,
                                                (len(str(abs(o))), 0))

        def op(self, sym, other, swap=False):
            l, r = (V.lift(other), self) if swap else (self, V.lift(other))
            return V([binop_value(sym, x, l.typ, y, r.typ)
                      for x, y in zip(l.vals, r.vals)],
                     binop_type(sym, l.typ, r.typ))
        __add__ = lambda s, o: s.op("+", o)
        __radd__ = lambda s, o: s.op("+", o, True)
        __sub__ = lambda s, o: s.op("-", o)
        __rsub__ = lambda s, o: s.op("-", o, True)
        __mul__ = lambda s, o: s.op("*", o)
        __rmul__ = lambda s, o: s.op("*", o, True)
        __neg__ = lambda s: V([None if v is None else -v for v in s.vals],
                              s.typ)
    ref = build(V(a, types[0]), V(b, types[1]), V(c, types[2]))
    assert ref.typ == want_type, (name, ref.typ)
    plan = (PlanBuilder().scan("t", schema=["a", "b", "c"])
            .project([("x", build(col("a"), col("b"), col("c")))]).build())
    return plan, t, ref


@pytest.mark.parametrize("tier", TIERS)
@pytest.mark.parametrize("name", sorted(EXPRESSIONS))
def test_expression_has_spark_type_and_exact_value(name, tier):
    plan, t, ref = _expression_case(name)
    res, out = run(plan, {"t": t}, tier)
    assert typed(out["x"]) == (ref.typ, ref.vals)
    assert res.decimal_overflow_rows == sum(v is None for v in ref.vals)
    assert out["x"].dtype == dtypes.decimal(*ref.typ)   # storage by precision


@pytest.mark.parametrize("tier", TIERS)
def test_overflow_nulls_the_row_never_wraps(tier):
    a = [9 * 10 ** 37, 10 ** 20, -9 * 10 ** 37, None]
    b = [9 * 10 ** 37, 10 ** 12, 9 * 10 ** 37, 10 ** 12]
    t = Table([dcol(a, 38, 10), dcol(b, 38, 10)], names=["a", "b"])
    plan = (PlanBuilder().scan("t", schema=["a", "b"])
            .project([("x", col("a") * col("b"))]).build())
    res, out = run(plan, {"t": t}, tier)
    want = [binop_value("*", x, (38, 10), y, (38, 10)) for x, y in zip(a, b)]
    assert want == [None, 10 ** 18, None, None]
    assert typed(out["x"]) == ((38, 6), want)
    assert res.decimal_overflow_rows == 2          # the null input is no overflow


@pytest.mark.parametrize("tier", TIERS)
def test_decimal_comparison_at_equal_scales(tier):
    t = Table([dcol([5, -7, 300], 15, 2), dcol([5, 2, 299], 12, 2),
               icol([1, 2, 3])], names=["a", "b", "k"])
    plan = (PlanBuilder().scan("t", schema=["a", "b", "k"])
            .filter(col("a") >= col("b")).select(["k"]).build())
    _, out = run(plan, {"t": t}, tier)
    assert out["k"].to_pylist() == [1, 3]


@pytest.mark.parametrize("op", ["<", "<=", ">", ">=", "==", "!="])
@pytest.mark.parametrize("tier", TIERS)
def test_decimal_comparison_casts_to_the_wider_type(tier, op):
    """Spark compares at the larger scale: decimal(15,2) beside
    decimal(12,4), a DECIMAL128 sum beside an integer literal (Q18's
    HAVING) and beside a DECIMAL64 column, limb by limb and signed."""
    import operator
    fn = {"<": operator.lt, "<=": operator.le, ">": operator.gt,
          ">=": operator.ge, "==": operator.eq, "!=": operator.ne}[op]
    a = [500, -700, 30000, 29999, 0, -1]
    b = [50000, -70001, 2999900, 3000000, 0, -100]
    wide = [30000, -30000, 2 ** 70, -(2 ** 70), 30001, 29999]
    t = Table([dcol(a, 15, 2), dcol(b, 12, 4), dcol(wide, 25, 2),
               icol(list(range(6)))], names=["a", "b", "w", "k"])
    cases = ((fn(col("a"), col("b")),
              [fn(x * 100, y) for x, y in zip(a, b)]),
             (fn(col("w"), 300), [fn(x, 30000) for x in wide]),
             (fn(col("w"), col("a")), [fn(x, y) for x, y in zip(wide, a)]))
    for predicate, want in cases:
        plan = (PlanBuilder().scan("t", schema=["a", "b", "w", "k"])
                .filter(predicate).select(["k"]).build())
        _, out = run(plan, {"t": t}, tier)
        assert out["k"].to_pylist() == [k for k, w in enumerate(want) if w]


# ---- aggregates ------------------------------------------------------------------------

AGG_CASES = {
    # name: (type, values per row (None: null), key per row)
    "decimal64_money": ((15, 2), None, None),
    "decimal32": ((7, 2), None, None),
    "decimal128_products": ((32, 4), None, None),
    "adjusted_sum_type": ((38, 6), None, None),
    "half_up_ties_in_the_average": (
        (15, 2), [1] + [0] * 19999 + [-1] + [0] * 19999 + [1, 2] + [None] * 3,
        [0] * 20000 + [1] * 20000 + [2, 2] + [3] * 3),
    "overflow_nulls_a_groups_sum": (
        (38, 6), [9 * 10 ** 37, 9 * 10 ** 37, 5, -9 * 10 ** 37,
                  -9 * 10 ** 37, 7, None],
        [0, 0, 1, 2, 2, 1, 1]),
}


def _agg_case(name):
    typ, values, keys = AGG_CASES[name]
    if values is None:
        rng = np.random.default_rng(len(name))
        n = 600
        hi = 10 ** min(typ[0], 30) - 1
        values = [int(rng.integers(-10 ** 9, 10 ** 9)) * (hi // 10 ** 9)
                  // 7 for _ in range(n)]
        values = [None if rng.random() < 0.1 else v for v in values]
        keys = rng.integers(0, 5, n).tolist()
        values += [None, None]                     # an all-null group
        keys += [5, 5]
    groups = sorted(set(keys))
    by = {g: [v for v, k in zip(values, keys) if k == g] for g in groups}
    ref = {"k": groups,
           "s": [sum_value(by[g], typ) for g in groups],
           "m": [avg_value(by[g], typ) for g in groups],
           "n": [sum(v is not None for v in by[g]) for g in groups]}
    t = Table([icol(keys), dcol(values, *typ)], names=["k", "v"])
    plan = (PlanBuilder().scan("t", schema=["k", "v"])
            .aggregate(["k"], [("v", "sum", "s"), ("v", "mean", "m"),
                               ("v", "count", "n")]).sort(["k"]).build())
    return plan, t, ref, typ


@pytest.mark.parametrize("tier,key_cap", [("eager", 0), ("capped", 8),
                                          ("capped", 32)],
                         ids=["eager", "capped-direct", "capped-sort"])
@pytest.mark.parametrize("name", sorted(AGG_CASES))
def test_decimal_sum_and_mean_have_spark_types_and_values(name, tier,
                                                          key_cap):
    plan, t, ref, typ = _agg_case(name)
    res, out = run(plan, {"t": t}, tier, key_cap)
    assert out["k"].to_pylist() == ref["k"]
    assert out["n"].to_pylist() == ref["n"]
    assert typed(out["s"]) == ((min(typ[0] + 10, 38), typ[1]), ref["s"])
    assert typed(out["m"]) == ((min(typ[0] + 4, 38), min(typ[1] + 4, 38)),
                               ref["m"])
    # groups with values whose sum (and with it the mean) overflowed
    nulled = sum(2 for s, n in zip(ref["s"], ref["n"]) if n and s is None)
    assert res.decimal_overflow_rows == nulled
    if tier == "capped":
        kernel = [m.kernel for m in res.metrics.values()
                  if m.kind == "HashAggregate"]
        assert kernel == ["direct:groupby" if key_cap <= 8
                          else kernel[0]] and "direct" not in (
                              kernel[0] if key_cap > 8 else "")


def test_overflow_case_really_overflows():
    _, _, ref, _ = _agg_case("overflow_nulls_a_groups_sum")
    assert ref["s"] == [None, 12, None] and ref["m"][1] == 6 * 10 ** 4
    _, _, ref, _ = _agg_case("half_up_ties_in_the_average")
    # 0.01 / 20000 = 0.0000005 rounds away from zero, both signs
    assert ref["m"] == [1, -1, 15000, None]


@pytest.mark.parametrize("key_cap", [2, 4, 8, 16])
def test_direct_groupby_equals_the_sort_kernels(key_cap):
    """The sort-free kernel (key cap <= 8) against the sort path on the
    same rows: alive mask, nulls, two key columns, every exact aggregate,
    and the overflow flag when the groups outnumber the cap."""
    from spark_rapids_tpu.ops import (groupby_aggregate,
                                      groupby_aggregate_capped)
    rng = np.random.default_rng(key_cap)
    n = 3000
    valid = rng.random(n) > 0.2
    t = Table([icol(rng.integers(0, 3, n)), icol(rng.integers(7, 9, n)),
               Column.from_numpy(rng.integers(-99, 99, n).astype(np.int64),
                                 validity=valid)], names=["a", "b", "v"])
    aggs = [("v", "sum"), ("v", "count"), ("v", "min"), ("v", "max"),
            ("v", "size")]
    alive = jnp.asarray(rng.random(n) > 0.3)
    got, live, overflow = groupby_aggregate_capped(t, ["a", "b"], aggs,
                                                   key_cap=key_cap,
                                                   alive=alive)
    from spark_rapids_tpu.ops import apply_boolean_mask
    want = groupby_aggregate(apply_boolean_mask(t, alive), ["a", "b"], aggs)
    assert bool(overflow) == (want.num_rows > key_cap)     # six groups
    if not bool(overflow):
        keep = np.asarray(live)
        for g, w in zip(got.columns, want.columns):
            assert [v for v, k in zip(g.to_pylist(), keep) if k] \
                == w.to_pylist()


# ---- Q1 whole ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def q1():
    import jax
    from chipbench import tpcds
    from chipbench.plans import tpch_q1
    batch = {"lineitem_rows": 20000}
    gen = tpch_q1.batch_generator({"part_rows": 2000000}, batch)
    drawn = gen(tpcds.run_key(2 ** 31 + 28, 1), tpcds.run_key(2 ** 31 + 28, 2))
    cols, _ = drawn["lineitem"]
    inputs = {"lineitem": tpcds.table(cols, {},
                                      tpch_q1.COLUMNS["lineitem"])}
    ref = tpch_q1.reference(jax.device_get(drawn))
    return tpch_q1, tpch_q1.plan(), inputs, ref, tpch_q1.caps(batch)


def _q1_answer(res):
    from chipbench import check
    return check.to_host(res)


Q1_TYPES = {"sum_qty": (25, 2), "sum_base_price": (25, 2),
            "sum_disc_price": (38, 4), "sum_charge": (38, 6),
            "avg_qty": (19, 6), "avg_price": (19, 6), "avg_disc": (19, 6)}


@pytest.mark.parametrize("entry", ["eager", "capped", "serving"])
def test_q1_equals_the_plain_reference(q1, entry):
    from chipbench import check
    mod, plan, inputs, ref, caps = q1
    if entry == "serving":
        from spark_rapids_tpu.serving import ServingScheduler
        ex = PlanExecutor(mode="capped", caps=caps)
        ex.execute(plan, inputs)       # a cold session is charged the bound
        with ServingScheduler(ex, workers=1, cache_entries=0) as sched:
            session = sched.open_session("q1", quota_bytes=1 << 30)
            res = session.submit(plan, inputs).result(timeout=600)
    else:
        ex = PlanExecutor(mode=entry, **({"caps": caps}
                                         if entry == "capped" else {}))
        res = ex.execute(plan, inputs)
    assert res.degraded is False and res.decimal_overflow_rows == 0
    assert len(ref) == 4
    assert check.compare(_q1_answer(res), ref, mod.RESULT_COLUMNS,
                         mod.ORDERED) == {"ordered_mismatch": 0,
                                          "rows_unmatched": 0}
    for name, want in Q1_TYPES.items():
        dt = res.table[name].dtype
        assert (dt.precision, dt.scale) == want, name
        assert dt.kind == dtypes.Kind.DECIMAL128


@pytest.mark.parametrize("control", ["truncate", "float64"])
def test_q1_reference_controls(q1, control):
    """The reference's own lower-precision forms differ from it where they
    must: truncation at any size; float64 only once the sums pass 2**53,
    which 20,000 rows do not (chipbench/tests/test_correct_q1.py has the
    cell's size)."""
    import jax
    from chipbench import check, tpcds
    mod, _, inputs, ref, _ = q1
    t = inputs["lineitem"]
    tables = {"lineitem": ({n: np.asarray(t[n].data) for n in t.names}, {})}
    other = mod.reference(tables, control=control)
    got = {c: other[c].values for c in mod.RESULT_COLUMNS}
    n = check.compare(got, ref, mod.RESULT_COLUMNS, mod.ORDERED)
    assert (n["rows_unmatched"] > 0) == (control == "truncate")


# ---- verifier, certifier, names ------------------------------------------------------------

def test_verifier_accepts_q1_and_still_rejects_a_string_expression(q1):
    _, plan, inputs, _, _ = q1
    assert verify(plan, inputs).ok
    names = Table([Column.from_pylist(["a", "b"], dtypes.STRING),
                   dcol([1, 2], 15, 2)], names=["s", "d"])
    bad = (PlanBuilder().scan("t", schema=["s", "d"])
           .project([("x", col("s") + 1)]).build())
    report = verify(bad, {"t": names})
    assert [v.invariant for v in report.violations] \
        == ["typing.column-not-expr-addressable"]


@pytest.mark.parametrize("case", ["narrow_buffer", "unequal_scales",
                                  "decimal_under_and", "float_beside",
                                  "keyless_sum"])
def test_verifier_rejects_what_is_not_lowered(case):
    t = Table([Column.from_numpy(np.arange(3, dtype=np.int32)),
               dcol([1, 2, 3], 15, 2), dcol([1, 2, 3], 15, 4),
               dcol([1, 2, 3], 18, 2)],
              names=["i", "a", "b", "c"])
    b = PlanBuilder()
    if case == "narrow_buffer":
        plan = b.scan("t", schema=["i", "a", "b", "c"],
                      types={"i": dtypes.decimal(15, 2)}).build()
        want = "typing.scan-type-storage"
    elif case == "unequal_scales":
        # 18 digits at scale 2 would be 20 at scale 4: past an int64
        plan = b.scan("t").filter(col("c") < col("b")).build()
        want = "typing.decimal-not-lowered"
    elif case == "decimal_under_and":
        plan = b.scan("t").project([("x", col("a") & col("b"))]).build()
        want = "typing.decimal-not-lowered"
    elif case == "float_beside":
        plan = b.scan("t").project([("x", col("a") * 0.5)]).build()
        want = "typing.decimal-not-lowered"
    else:
        plan = b.scan("t").aggregate([], [("a", "sum", "s")]).build()
        want = "typing.agg-over-non-scalar"
    report = verify(plan, {"t": t})
    assert want in [v.invariant for v in report.violations]


def test_scan_types_retag_without_a_copy_and_gate_at_execute():
    raw = Table([icol([100, 250])], names=["v"])
    scan = PlanBuilder().scan("t", schema=["v"],
                              types={"v": dtypes.decimal(15, 2)})
    node = scan.node
    assert node.typed(raw)["v"].data is raw["v"].data
    assert node.typed(raw)["v"].dtype == dtypes.decimal(15, 2)
    plan = scan.project([("x", col("v") * col("v"))]).build()
    _, out = run(plan, {"t": raw}, "eager")
    assert typed(out["x"]) == ((31, 4), [10000, 62500])
    narrow = Table([Column.from_numpy(np.arange(2, dtype=np.int32))],
                   names=["v"])
    with pytest.raises(PlanValidationError, match="scan-type-storage"):
        PlanExecutor(mode="eager").execute(plan, {"t": narrow})


def test_certifier_charges_limb_columns(q1):
    from spark_rapids_tpu.analysis import footprint
    _, plan, inputs, _, _ = q1
    dts, nul = footprint.table_metadata(inputs)
    cert = footprint.certify(plan, bound={"lineitem": inputs["lineitem"].names},
                             bound_rows={"lineitem": 20000},
                             input_dtypes=dts, input_nullable=nul)
    by_kind = {op.kind: op for op in cert.ops}
    # two DECIMAL128 products ride beside five 8-byte columns
    assert by_kind["Project"].out_bytes_hi >= 20000 * (5 * 8 + 2 * 16)
    # seven decimal aggregates of 16 bytes and a count per group
    assert by_kind["HashAggregate"].out_bytes_hi >= 7 * 16


def test_decimal_scopes_reach_the_capped_programs_owners(q1):
    _, plan, inputs, _, caps = q1
    ex = PlanExecutor(mode="capped", caps=caps)
    owners = ex.device_op_owners(plan, inputs, nested=True)
    scopes = {o.split("/", 1)[1] for o in owners.values() if "/" in o}
    assert {"decimal.mul", "decimal.sum", "decimal.div"} <= scopes
    flat = set(ex.device_op_owners(plan, inputs).values())
    assert all("/" not in o for o in flat)
    assert any(o.endswith(".HashAggregate") for o in flat)


# ---- the streaming prefix over a decimal-bearing source -------------------------

@pytest.mark.parametrize("case", ["int_aggregate", "decimal_aggregate",
                                  "decimal_project"])
def test_decimal_source_still_streams_what_decomposes(case, tmp_path):
    """A parquet file with a decimal column streams its prefix; only an
    aggregate that reads the decimal leaves the chain and runs whole."""
    import decimal
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_tpu.io import ParquetSource
    from spark_rapids_tpu.plan.nodes import HashAggregate
    rng = np.random.default_rng(7)
    n = 4000
    key, qty = rng.integers(0, 5, n), rng.integers(1, 50, n)
    cents = rng.integers(-10 ** 9, 10 ** 9, n)
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({
        "k": key, "qty": qty,
        "price": pa.array([decimal.Decimal(int(c)).scaleb(-2)
                           for c in cents], pa.decimal128(15, 2))}),
        path, row_group_size=n // 4, compression="NONE",
        store_decimal_as_integer=True)      # an INT64 page annotated DECIMAL
    src = ParquetSource(path)
    assert src.column_dtypes["price"] == dtypes.decimal(15, 2)
    rel = PlanBuilder().scan("t", schema=["k", "qty", "price"]) \
        .filter(col("qty") > 10)
    if case == "int_aggregate":
        plan = rel.aggregate(["k"], [("qty", "sum", "q")]).sort(["k"]).build()
    elif case == "decimal_aggregate":
        plan = rel.aggregate(["k"], [("price", "sum", "p"),
                                     ("qty", "sum", "q")]).sort(["k"]).build()
    else:
        plan = rel.project([("k", col("k")), ("qty", col("qty")),
                            ("twice", col("price") * 2)]) \
            .aggregate(["k"], [("twice", "sum", "p")]).sort(["k"]).build()
    chains = PlanExecutor._stream_chains(plan, {"t": src})
    (chain,) = chains.values()
    assert len(chain) >= 2          # the filter streams in every case
    assert isinstance(chain[-1], HashAggregate) == (case == "int_aggregate")
    res = PlanExecutor().execute(plan, {"t": src})
    t = Table([dcol(cents.tolist(), 15, 2) if c == "price"
               else icol({"k": key, "qty": qty}[c])
               for c in ("k", "qty", "price")], names=["k", "qty", "price"])
    ref = PlanExecutor().execute(plan, {"t": t})
    for name in ref.table.names:
        a, b = res.table[name], ref.table[name]
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a.data), np.asarray(b.data))
    keep = qty > 10
    if case != "int_aggregate":
        total = [sum(int(c) for c in cents[keep & (key == g)]) for g in range(5)]
        mult = 2 if case == "decimal_project" else 1
        assert res.table["p"].dtype == dtypes.decimal(
            27 if case == "decimal_project" else 25, 2)
        assert typed(res.table["p"])[1] == [mult * v for v in total]


# ---- the benchmark's readers of the decimal scopes -------------------------------

class _TracedRun:
    """What the `decimal_*` readers take of a `harness.Run`: a second of
    device self time per instruction of the capped program."""

    def __init__(self, q1, executor):
        import types
        mod, self.plan, self._inputs, _, _ = q1
        self.executor = executor
        self.cell = types.SimpleNamespace(
            traffic={"tier": "capped"}, plan=mod, sizes={},
            batch={"lineitem_rows": 20000})
        self.requests = [{"ok": True}] * 3
        self.peaks = {"hbm_bytes_per_s": 819e9}
        owners = PlanExecutor(mode="capped", caps=q1[4]).device_op_owners(
            self.plan, self._inputs)
        seconds = {("jit_capped_plan/" + i, o): 1.0 for i, o in owners.items()}
        self._program_spans = types.SimpleNamespace(
            op_owner_s=seconds, busy_s=2.0 * len(seconds))

    def make_inputs(self, request):
        return self._inputs


def test_decimal_readers_keep_the_aggregates_casts_out_of_the_bandwidth_share(q1):
    from chipbench import decimal_scopes, harness
    run = _TracedRun(q1, PlanExecutor(mode="capped", caps=q1[4]))
    by = decimal_scopes.seconds(run)
    kinds = {kind for kind, _ in by}
    assert {"Project", "HashAggregate"} <= kinds
    assert by[("Project", "mul")] > 0 and ("HashAggregate", "div") in by
    assert ("HashAggregate", "rescale") in by      # the averages' casts
    row_wise = sum(s for (kind, op), s in by.items()
                   if kind != "HashAggregate" and op in ("mul", "rescale"))
    assert 0 < row_wise < sum(s for (_, op), s in by.items()
                              if op in ("mul", "rescale"))
    share = harness.read_layer_metric("decimal_bw_share", run)
    assert share == pytest.approx(
        100.0 * q1[0].decimal_bytes(run.cell.batch, {}) * 3 / row_wise / 819e9)
    assert harness.read_layer_metric("decimal_device_share", run) \
        == pytest.approx(100.0 * sum(by.values())
                         / run._program_spans.busy_s)


def test_decimal_readers_report_nothing_for_a_program_without_the_scopes(q1):
    """The parent's `device_op_owners` takes no `nested`: the readers
    return None. Any other error is the program's and is not swallowed."""
    import types
    from chipbench import harness

    def owners(plan, inputs):
        return {}
    run = _TracedRun(q1, types.SimpleNamespace(device_op_owners=owners))
    assert harness.read_layer_metric("decimal_bw_share", run) is None
    assert harness.read_layer_metric("decimal_device_share", run) is None

    def broken(plan, inputs, nested=False):
        raise TypeError("a fault while lowering")
    run = _TracedRun(q1, types.SimpleNamespace(device_op_owners=broken))
    with pytest.raises(TypeError, match="while lowering"):
        harness.read_layer_metric("decimal_device_share", run)
