"""SQL null semantics of plan expressions (plan/expr.py, docs/plan.md):
every operator x null pattern x type, in the eager and the capped tier,
against a plain three-valued reference over Python values with None for a
null, which shares nothing with the engine. A boolean expression is also a
`Filter`'s predicate: the rows it keeps are those where it is TRUE, not
null.
"""
import pytest

import jax.numpy as jnp

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, Table, dtypes
from spark_rapids_tpu.plan import (PlanBuilder, PlanExecutor, coalesce, col,
                                   is_not_null, is_null, scalar_max,
                                   scalar_min, scalar_sum, when)

TIERS = ("eager", "capped")
ROWS = 8
# the values of columns `a` and `b` by type (decimals: unscaled)
VALUES = {
    "int64": ([3, -1, 0, 7, 7, 2, -5, 4], [3, 2, 0, -7, 1, 2, 9, 0]),
    "bool": ([True, True, False, False, True, False, True, False],
             [True, False, True, False, False, True, True, False]),
    "decimal64": ([301, -150, 0, 799, 799, 250, -5, 4],
                  [301, 225, 0, -799, 100, 250, 9, 0]),
    # (past 64 bits, and a product within decimal(38, 4))
    "decimal128": ([10 ** 18 + 1, -150, 0, 799, 799, 250, -10 ** 19, 4],
                   [10 ** 18 + 1, 225, 0, -799, 100, 250, 9, 0]),
}
DTYPES = {"int64": dtypes.INT64, "bool": dtypes.BOOL,
          "decimal64": dtypes.decimal(15, 2),
          "decimal128": dtypes.decimal(25, 2)}
COND = [True, False, True, False, True, True, False, False]
# which rows of `a` / of `b` are valid, by pattern; `c` (a `when`'s
# condition) is null where `a` is. Rows 1, 4, 6 and 0, 2, 4 put a null
# beside a TRUE and beside a FALSE on either side of `&` / `|`
LEFT = [True, False, True, True, False, True, False, True]
RIGHT = [False, True, False, True, False, True, True, True]
PATTERNS = {"none": (None, None), "left": (LEFT, None),
            "right": (None, RIGHT), "both": (LEFT, RIGHT),
            "all": ([False] * ROWS, [False] * ROWS)}


# ---- the plain reference: Python values, None for a null -------------------

def lift(fn):
    """A null in, a null out."""
    return lambda *xs: None if any(x is None for x in xs) else fn(*xs)


def and3(x, y):
    if x is False or y is False:
        return False
    return None if x is None or y is None else True


def or3(x, y):
    if x is True or y is True:
        return True
    return None if x is None or y is None else False


def aggregate(fn):
    """A scalar aggregate skips nulls, is null over none, and is the same
    on every row."""
    def over(column):
        live = [v for v in column if v is not None]
        return [fn(live) if live else None] * len(column)
    return over


ARITHMETIC = {"+": lambda x, y: x + y, "-": lambda x, y: x - y,
              "*": lambda x, y: x * y}
COMPARISONS = {"==": lambda x, y: x == y, "!=": lambda x, y: x != y,
               "<": lambda x, y: x < y, "<=": lambda x, y: x <= y,
               ">": lambda x, y: x > y, ">=": lambda x, y: x >= y}
NUMERIC = ("int64", "decimal64", "decimal128")
EVERY = NUMERIC + ("bool",)

# name -> (expression over a, b, c; row-wise reference over their values,
#          or a whole-column one for an aggregate; the types it takes)
OPERATORS = {}
for sym, fn in ARITHMETIC.items():
    OPERATORS[sym] = (lambda a, b, c, s=sym: {
        "+": a + b, "-": a - b, "*": a * b}[s],
        lambda x, y, z, f=lift(fn): f(x, y), NUMERIC)
for sym, fn in COMPARISONS.items():
    OPERATORS[sym] = (lambda a, b, c, s=sym: {
        "==": a == b, "!=": a != b, "<": a < b, "<=": a <= b, ">": a > b,
        ">=": a >= b}[s], lambda x, y, z, f=lift(fn): f(x, y), NUMERIC)
OPERATORS.update({
    "&": (lambda a, b, c: a & b, lambda x, y, z: and3(x, y), ("bool",)),
    "|": (lambda a, b, c: a | b, lambda x, y, z: or3(x, y), ("bool",)),
    "~": (lambda a, b, c: ~a, lambda x, y, z: lift(lambda v: not v)(x),
          ("bool",)),
    "bitwise&": (lambda a, b, c: a & b,
                 lambda x, y, z: lift(lambda p, q: p & q)(x, y), ("int64",)),
    "neg": (lambda a, b, c: -a, lambda x, y, z: lift(lambda v: -v)(x),
            NUMERIC),
    "is_null": (lambda a, b, c: is_null(a), lambda x, y, z: x is None,
                EVERY),
    "is_not_null": (lambda a, b, c: is_not_null(b),
                    lambda x, y, z: y is not None, EVERY),
    "is_null_of_sum": (lambda a, b, c: is_null(a + b),
                       lambda x, y, z: x is None or y is None,
                       ("int64", "decimal64")),
    "when": (lambda a, b, c: when(c, a, b),
             lambda x, y, z: x if z is True else y, EVERY),
    "when_literal": (lambda a, b, c: when(is_not_null(a) & is_null(b), 1, 0),
                     lambda x, y, z: int(x is not None and y is None),
                     EVERY),
    "coalesce": (lambda a, b, c: coalesce(a, b),
                 lambda x, y, z: y if x is None else x, EVERY),
    "coalesce_literal": (lambda a, b, c: coalesce(a, 7),
                         lambda x, y, z: 7 if x is None else x,
                         ("int64",)),
    "coalesce_decimal_literal": (lambda a, b, c: coalesce(a, 7),
                                 lambda x, y, z: 700 if x is None else x,
                                 ("decimal64", "decimal128")),
    "scalar_max": (lambda a, b, c: scalar_max(a), aggregate(max),
                   ("int64",)),
    "scalar_min": (lambda a, b, c: scalar_min(a), aggregate(min),
                   ("int64",)),
    "scalar_sum": (lambda a, b, c: scalar_sum(a), aggregate(sum),
                   ("int64",)),
    "above_the_mean": (lambda a, b, c: a * ROWS > scalar_sum(a),
                       None, ("int64",)),
})
CASES = [(op, kind) for op, (_, _, kinds) in OPERATORS.items()
         for kind in kinds]


def _inputs(kind, pattern):
    a, b = VALUES[kind]
    va, vb = PATTERNS[pattern]

    def column(values, valid, dt):
        c = Column.from_pylist(list(values), dt)
        return c if valid is None else c.with_validity(jnp.asarray(valid))
    table = Table([column(a, va, DTYPES[kind]), column(b, vb, DTYPES[kind]),
                   column(COND, va, dtypes.BOOL),
                   column(range(ROWS), None, dtypes.INT64)],
                  names=["a", "b", "c", "rid"])
    nulled = lambda values, valid: [
        v if valid is None or ok else None
        for v, ok in zip(values, valid or values)]
    return table, nulled(a, va), nulled(b, vb), nulled(COND, va)


def _expected(op, xs, ys, zs):
    _, ref, _ = OPERATORS[op]
    if op == "above_the_mean":
        total = aggregate(sum)(xs)[0]
        return [lift(lambda v, t: v * ROWS > t)(x, total) for x in xs]
    if op.startswith("scalar_"):
        return ref(xs)
    return [ref(x, y, z) for x, y, z in zip(xs, ys, zs)]


def _run(plan, table, tier):
    ex = (PlanExecutor(mode="eager") if tier == "eager" else
          PlanExecutor(mode="capped", caps=dict(row_cap=16, key_cap=16)))
    res = ex.execute(plan, {"t": table})
    assert res.degraded is False
    return res.compact()


@pytest.mark.parametrize("tier", TIERS)
@pytest.mark.parametrize("pattern", list(PATTERNS))
@pytest.mark.parametrize("op,kind", CASES,
                         ids=[f"{op}-{kind}" for op, kind in CASES])
def test_operator_follows_sql_null_semantics(op, kind, pattern, tier):
    table, xs, ys, zs = _inputs(kind, pattern)
    want = _expected(op, xs, ys, zs)
    expr = OPERATORS[op][0](col("a"), col("b"), col("c"))
    scan = PlanBuilder().scan("t", schema=list(table.names))
    out = _run(scan.project({"out": expr, "rid": col("rid")}).build(),
               table, tier)
    assert out["rid"].to_pylist() == list(range(ROWS))
    assert out["out"].to_pylist() == want
    if pattern == "none":
        # null-free inputs cost no validity plane
        assert out["out"].validity is None or op.startswith("scalar_") \
            or kind == "decimal128"
    a, b = VALUES[kind]
    if all(isinstance(v, bool) for v in _expected(op, a, b, COND)):
        # as a predicate: a Filter keeps the rows where it is TRUE
        kept = _run(scan.filter(expr).project({"rid": col("rid")}).build(),
                    table, tier)
        assert kept["rid"].to_pylist() \
            == [i for i, v in enumerate(want) if v is True]
