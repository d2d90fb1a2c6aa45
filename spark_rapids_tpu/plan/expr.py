"""Expression mini-language for plan predicates and projections.

The slot Catalyst expressions fill in the reference plugin: `Filter` takes a
boolean `Expr`, `Project` takes named `Expr`s. Expressions evaluate to raw
device arrays over one input relation; evaluation is pure jnp, so the same
expression works in the eager tier (concrete arrays) and inside the capped
whole-plan jit (tracers).

Scalar-aggregate expressions (`scalar_max(col("rev"))`) evaluate an
aggregate over the WHOLE input relation and broadcast it — the scalar
subquery shape q23's `HAVING sum > 0.95 * MAX(...)` needs. In the capped
tier they reduce only over `alive` rows (the padded-row contract).

Null semantics: expressions read the data buffer only; rows whose inputs
are null must be dropped by validity-aware operators (the NDS tier is
null-free). This matches the capped kernels, which also carry validity
out-of-band.

Typed expressions: where a decimal column reaches a `+ - *` or a
comparison, the expression has Spark's result type (`decimal_type`; the
rules live in `ops/decimal_utils.py`) and `Expr.column` evaluates it to a
typed Column through that file's kernels: an integer literal beside a
decimal is `decimal(digits, 0)`, an integral column `decimal(p, 0)`, an
overflow nulls the row. `evaluate` of such an expression is that column's
data. Expressions no decimal reaches keep the untyped x64 semantics
above. docs/plan.md "Typed expressions" has the table.
"""
from __future__ import annotations

import dataclasses
from typing import Any, FrozenSet, Optional

import jax.numpy as jnp


class Expr:
    """Base expression. Build with `col`/`lit` and python operators."""

    def references(self) -> FrozenSet[str]:
        raise NotImplementedError

    def evaluate(self, table, alive: Optional[jnp.ndarray] = None):
        """Array of the expression over `table` ((n,) jnp array; scalar
        aggregates reduce over `alive` rows when a mask is given)."""
        raise NotImplementedError

    def column(self, table, alive: Optional[jnp.ndarray] = None):
        """The typed Column of a decimal-valued expression (`decimal_type`
        is not None): Spark's result type, the inputs' validity, overflow
        rows null."""
        raise TypeError(f"{self!r} is not a decimal-valued expression")

    # ---- operator sugar ---------------------------------------------------
    def _bin(self, op: str, other) -> "BinOp":
        return BinOp(op, self, _wrap(other))

    def __eq__(self, other):                       # noqa: D105
        return self._bin("==", other)

    def __ne__(self, other):
        return self._bin("!=", other)

    __hash__ = None   # comparison builds expressions; not hashable

    def __lt__(self, other):
        return self._bin("<", other)

    def __le__(self, other):
        return self._bin("<=", other)

    def __gt__(self, other):
        return self._bin(">", other)

    def __ge__(self, other):
        return self._bin(">=", other)

    def __and__(self, other):
        return self._bin("&", other)

    def __or__(self, other):
        return self._bin("|", other)

    def __add__(self, other):
        return self._bin("+", other)

    def __radd__(self, other):
        return _wrap(other)._bin("+", self)

    def __sub__(self, other):
        return self._bin("-", other)

    def __rsub__(self, other):
        return _wrap(other)._bin("-", self)

    def __mul__(self, other):
        return self._bin("*", other)

    def __rmul__(self, other):
        return _wrap(other)._bin("*", self)

    def __invert__(self):
        return UnaryOp("~", self)

    def __neg__(self):
        return UnaryOp("-", self)


def _wrap(v) -> Expr:
    return v if isinstance(v, Expr) else Literal(v)


@dataclasses.dataclass(frozen=True, eq=False)
class ColumnRef(Expr):
    name: str

    def references(self):
        return frozenset((self.name,))

    def evaluate(self, table, alive=None):
        return table[self.name].data

    def column(self, table, alive=None):
        return table[self.name]

    def __repr__(self):
        return self.name


@dataclasses.dataclass(frozen=True, eq=False)
class Literal(Expr):
    value: Any

    def references(self):
        return frozenset()

    def evaluate(self, table, alive=None):
        n = table.num_rows
        return jnp.full((n,), self.value)

    def __repr__(self):
        return repr(self.value)


_BIN_FNS = {
    "==": lambda a, b: a == b, "!=": lambda a, b: a != b,
    "<": lambda a, b: a < b, "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b, ">=": lambda a, b: a >= b,
    "&": lambda a, b: a & b, "|": lambda a, b: a | b,
    "+": lambda a, b: a + b, "-": lambda a, b: a - b,
    "*": lambda a, b: a * b,
}


@dataclasses.dataclass(frozen=True, eq=False)
class BinOp(Expr):
    op: str
    left: Expr
    right: Expr

    def references(self):
        return self.left.references() | self.right.references()

    def evaluate(self, table, alive=None):
        if decimal_sides(self, _types_of(table)) is not None:
            return self.column(table, alive).data
        return _BIN_FNS[self.op](self.left.evaluate(table, alive),
                                 self.right.evaluate(table, alive))

    def column(self, table, alive=None):
        from .. import dtypes
        from ..columnar import Column
        from ..ops import decimal_utils
        if decimal_sides(self, _types_of(table)) is None:
            return super().column(table, alive)
        n = table.num_rows
        compares = self.op in _CMP_OPS
        # a literal beside a comparison is one row, which broadcasts
        l, r = (decimal_utils.literal_column(e.value, 1 if compares else n)
                if isinstance(e, Literal) else e.column(table, alive)
                for e in (self.left, self.right))
        if compares:
            return Column(dtype=dtypes.BOOL, length=n,
                          data=jnp.broadcast_to(
                              decimal_utils.compare(self.op, l, r), (n,)))
        return decimal_utils.arithmetic(self.op, l, r, alive)

    def __repr__(self):
        return f"({self.left!r} {self.op} {self.right!r})"


@dataclasses.dataclass(frozen=True, eq=False)
class UnaryOp(Expr):
    op: str
    child: Expr

    def references(self):
        return self.child.references()

    def evaluate(self, table, alive=None):
        if decimal_type(self, _types_of(table)) is not None:
            return self.column(table, alive).data
        v = self.child.evaluate(table, alive)
        return ~v if self.op == "~" else -v

    def column(self, table, alive=None):
        from ..columnar import Column
        from ..ops import decimal256 as d256
        if decimal_type(self, _types_of(table)) is None:
            return super().column(table, alive)
        c = self.child.column(table, alive)
        data = (-c.data if c.data.ndim == 1 else d256.to_i128_limbs(
            d256.negate(d256.from_i128_limbs(c.data))))
        return Column(dtype=c.dtype, length=c.length, data=data,
                      validity=c.validity)

    def __repr__(self):
        return f"{self.op}{self.child!r}"


@dataclasses.dataclass(frozen=True, eq=False)
class ScalarAgg(Expr):
    """Aggregate over the whole input relation, broadcast as a scalar —
    the scalar-subquery shape (q23's `> 0.95 * MAX(rev)`). Honors the
    capped tier's `alive` mask by reducing over live rows only."""
    op: str                  # max | min | sum
    child: Expr

    def references(self):
        return self.child.references()

    def evaluate(self, table, alive=None):
        v = self.child.evaluate(table, alive)
        if alive is not None:
            ident = _reduce_identity(self.op, v.dtype)
            v = jnp.where(alive, v, ident)
        return {"max": jnp.max, "min": jnp.min, "sum": jnp.sum}[self.op](v)

    def __repr__(self):
        return f"{self.op}({self.child!r})"


def _reduce_identity(op: str, dtype):
    if op == "sum":
        return jnp.asarray(0, dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        inf = jnp.asarray(jnp.inf, dtype)
        return -inf if op == "max" else inf
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.min if op == "max" else info.max, dtype)


# ---- Spark's types of decimal expressions -------------------------------------

_CMP_OPS = ("==", "!=", "<", "<=", ">", ">=")


def _types_of(table):
    """name -> DType over a bound table's columns."""
    return lambda name: table[name].dtype


def decimal_type(e: Expr, col_type):
    """The Spark decimal type of `e` where it is decimal-valued, else
    None: untyped x64 semantics apply, as they do to a comparison's
    boolean. `col_type(name)` is a column's DType (None: unknown). Raises
    TypeError for what is not lowered: a decimal under `& | ~` or a
    scalar aggregate, a float or a computed integer beside a decimal."""
    if isinstance(e, ColumnRef):
        dt = col_type(e.name)
        return dt if dt is not None and dt.is_decimal else None
    if isinstance(e, BinOp):
        sides = decimal_sides(e, col_type)
        if sides is None or e.op in _CMP_OPS:
            return None
        from ..ops.decimal_utils import arithmetic_type
        return arithmetic_type(e.op, *sides)
    if isinstance(e, (UnaryOp, ScalarAgg)):
        ct = decimal_type(e.child, col_type)
        if ct is not None and not (isinstance(e, UnaryOp) and e.op == "-"):
            raise TypeError(f"{e!r}: {e.op!r} over a decimal is not lowered")
        return ct
    return None


def decimal_sides(e: BinOp, col_type):
    """(left type, right type) as decimals where a decimal reaches either
    side of `e`, else None."""
    from ..ops.decimal_utils import as_decimal_type, literal_type
    lt = decimal_type(e.left, col_type)
    rt = decimal_type(e.right, col_type)
    if lt is None and rt is None:
        return None
    if e.op not in _CMP_OPS and e.op not in ("+", "-", "*"):
        raise TypeError(f"{e!r}: {e.op!r} over a decimal is not lowered")

    def beside(side: Expr):
        if isinstance(side, Literal) and isinstance(side.value, int) \
                and not isinstance(side.value, bool):
            return literal_type(side.value)
        dt = (as_decimal_type(col_type(side.name))
              if isinstance(side, ColumnRef) else None)
        if dt is None:
            raise TypeError(
                f"{e!r}: {side!r} beside a decimal must be a decimal, an "
                "integer literal or an integral column (Spark would cast "
                "it; a plan states that cast)")
        return dt
    return (lt if lt is not None else beside(e.left),
            rt if rt is not None else beside(e.right))


# ---- structural helpers (the optimizer's expression toolkit) ----------------

def _foldable(v) -> bool:
    """Folded python arithmetic matches runtime jnp arithmetic because the
    engine runs under x64 (int64/float64 storage, enabled at import): an
    int that no longer fits int64 would RAISE at Literal.evaluate where
    the unfolded tree silently wraps — don't fold those."""
    if isinstance(v, bool) or not isinstance(v, int):
        return True
    return -(2 ** 63) <= v < 2 ** 63


def fold(e: Expr) -> Expr:
    """Constant-fold literal-only subtrees bottom-up. `BinOp(lit, lit)` and
    `UnaryOp(lit)` become a `Literal` of the evaluated python value —
    including comparisons, so a whole literal predicate reduces to
    `Literal(True/False)` and the optimizer's trivial-predicate rule can
    drop/short-circuit the Filter. Returns `e` itself when nothing folded
    (callers detect a rewrite by identity). Scalar aggregates never fold:
    even over a literal, their value depends on the live-row set (an
    empty relation reduces max/min to the identity, sum to n*v)."""
    if isinstance(e, BinOp):
        l, r = fold(e.left), fold(e.right)
        if isinstance(l, Literal) and isinstance(r, Literal):
            v = _BIN_FNS[e.op](l.value, r.value)
            if _foldable(v):
                return Literal(v)
        if l is e.left and r is e.right:
            return e
        return BinOp(e.op, l, r)
    if isinstance(e, UnaryOp):
        c = fold(e.child)
        if isinstance(c, Literal):
            if e.op == "~":
                # python's ~True is -2; the jnp evaluation of ~ on a bool
                # array is logical not — fold must match the array semantics
                v = (not c.value) if isinstance(c.value, bool) else ~c.value
            else:
                v = -c.value
            if _foldable(v):
                return Literal(v)
        return e if c is e.child else UnaryOp(e.op, c)
    if isinstance(e, ScalarAgg):
        c = fold(e.child)
        return e if c is e.child else ScalarAgg(e.op, c)
    return e


def substitute(e: Expr, mapping) -> Expr:
    """Replace every `ColumnRef(name)` with `mapping[name]` (an Expr) —
    how a predicate is rewritten through a Project during pushdown.
    Unmapped names raise KeyError (callers guard with references())."""
    if isinstance(e, ColumnRef):
        return mapping[e.name]
    if isinstance(e, BinOp):
        return BinOp(e.op, substitute(e.left, mapping),
                     substitute(e.right, mapping))
    if isinstance(e, UnaryOp):
        return UnaryOp(e.op, substitute(e.child, mapping))
    if isinstance(e, ScalarAgg):
        return ScalarAgg(e.op, substitute(e.child, mapping))
    return e


def has_scalar_agg(e: Expr) -> bool:
    """Whether the expression contains a whole-relation scalar aggregate —
    such expressions are NOT row-wise, so reorderings that change the row
    set under them (pushdown below a join/union, limit pushdown) are
    invalid and the optimizer must skip them."""
    if isinstance(e, ScalarAgg):
        return True
    if isinstance(e, BinOp):
        return has_scalar_agg(e.left) or has_scalar_agg(e.right)
    if isinstance(e, UnaryOp):
        return has_scalar_agg(e.child)
    return False


# ---- public constructors ----------------------------------------------------

def col(name: str) -> ColumnRef:
    """Reference a column of the input relation by name."""
    return ColumnRef(name)


def lit(value) -> Literal:
    """A literal, broadcast to the relation's length."""
    return Literal(value)


def scalar_max(e: Expr) -> ScalarAgg:
    return ScalarAgg("max", _wrap(e))


def scalar_min(e: Expr) -> ScalarAgg:
    return ScalarAgg("min", _wrap(e))


def scalar_sum(e: Expr) -> ScalarAgg:
    return ScalarAgg("sum", _wrap(e))
