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

Null semantics: SQL's. Every expression evaluates to a value AND a
validity, as one `Column` (`Expr.column`, the one evaluator; `evaluate` is
its data buffer, `truth` the rows where it is TRUE, which is what a
`Filter` keeps: a null predicate drops the row). Arithmetic, comparisons,
`~` and unary `-` are null where an input is; `&` and `|` over booleans
follow Spark's three-valued logic (`false & null` is false, `true | null`
true); `is_null` / `is_not_null` are never null; `when(cond, then,
otherwise)` takes `otherwise` where `cond` is false OR null; `coalesce`
takes its first non-null argument; a scalar aggregate skips null rows
(and is null where a nullable input had no valid row). A column without a
validity mask costs what it cost before there were nulls: validity `None`
stands for "all valid" through every operator and no mask is built for
it. docs/plan.md has the table.

Typed expressions: where a decimal column reaches a `+ - *` or a
comparison, the expression has Spark's result type (`decimal_type`; the
rules live in `ops/decimal_utils.py`) and is evaluated through that
file's kernels: an integer literal beside a decimal is `decimal(digits,
0)`, an integral column `decimal(p, 0)`, an overflow nulls the row. The
branches of a `when` / `coalesce` that a decimal reaches must have ONE
decimal type (an integer literal takes it; a plan states any other cast).
Expressions no decimal reaches keep the untyped x64 semantics above.
docs/plan.md "Typed expressions" has the table.
"""
from __future__ import annotations

import dataclasses
from typing import Any, FrozenSet, Optional, Tuple

import jax.numpy as jnp


class Expr:
    """Base expression. Build with `col`/`lit` and python operators."""

    def references(self) -> FrozenSet[str]:
        """The columns the expression reads."""
        return frozenset().union(*(c.references() for c in self.children()))

    def children(self) -> Tuple["Expr", ...]:
        """The sub-expressions, in the order `rebuild` takes them back."""
        return ()

    def rebuild(self, children) -> "Expr":
        """This node over other `children` (the structural walks' one
        constructor: fold, substitute, the optimizer's rewrites)."""
        return self

    def column(self, table, alive: Optional[jnp.ndarray] = None):
        """The expression over `table` as a Column of the table's length:
        its value, its type (Spark's result type where a decimal reaches
        it) and its validity (None: no row is null). Scalar aggregates
        reduce over `alive` rows when a mask is given."""
        raise NotImplementedError

    def evaluate(self, table, alive: Optional[jnp.ndarray] = None):
        """The data buffer of `column`: what lies under a null row is
        unspecified, and no operator may read it without the validity."""
        return self.column(table, alive).data

    def truth(self, table, alive: Optional[jnp.ndarray] = None):
        """(n,) bool: the rows where a boolean expression is TRUE, neither
        false nor null. What a `Filter` keeps."""
        c = self.column(table, alive)
        return c.data if c.validity is None else c.data & c.validity

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

    def column(self, table, alive=None):
        return table[self.name]

    def __repr__(self):
        return self.name


@dataclasses.dataclass(frozen=True, eq=False)
class Literal(Expr):
    value: Any

    def column(self, table, alive=None):
        return untyped_column(jnp.full((table.num_rows,), self.value))

    def __repr__(self):
        return repr(self.value)


def untyped_column(data, validity=None):
    """A Column over an x64 array no decimal reached: the type is the
    array's own."""
    from ..columnar import Column
    return Column(dtype=_array_dtype(data.dtype), length=int(data.shape[0]),
                  data=data, validity=validity)


def _array_dtype(np_dt):
    import numpy as np
    from .. import dtypes
    np_dt = np.dtype(np_dt)
    key = "b" if np_dt.kind == "b" else f"{np_dt.kind}{np_dt.itemsize}"
    dt = {"b": dtypes.BOOL, "i1": dtypes.INT8, "i2": dtypes.INT16,
          "i4": dtypes.INT32, "i8": dtypes.INT64,
          "f4": dtypes.FLOAT32, "f8": dtypes.FLOAT64}.get(key)
    if dt is None:
        from .nodes import PlanValidationError
        raise PlanValidationError(
            f"expression produced unsupported dtype {np_dt}")
    return dt


def _both_valid(a, b):
    """The AND of two validity masks, None standing for "all valid" (and
    staying None where both are: no mask is built for null-free inputs)."""
    if a is None or b is None:
        return b if a is None else a
    return a & b


def _kleene(op: str, l, r):
    """Spark's three-valued `&` / `|` over two boolean Columns ->
    (data, validity). A null side reads as the operator's neutral value
    (true for `&`, false for `|`); where the result then differs from the
    neutral value a valid side decided it (a FALSE under `&`, a TRUE
    under `|`) whatever the other side is, and else it is null where a
    side is."""
    if l.validity is None and r.validity is None:
        return _BIN_FNS[op](l.data, r.data), None
    neutral = op == "&"
    ld, rd = (c.data if c.validity is None
              else jnp.where(c.validity, c.data, neutral) for c in (l, r))
    data = ld & rd if neutral else ld | rd
    decided = ~data if neutral else data
    return data, _both_valid(l.validity, r.validity) | decided


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

    def children(self):
        return (self.left, self.right)

    def rebuild(self, children):
        return BinOp(self.op, *children)

    def column(self, table, alive=None):
        from .. import dtypes
        from ..columnar import Column
        from ..ops import decimal_utils
        if decimal_sides(self, _types_of(table)) is None:
            l = self.left.column(table, alive)
            r = self.right.column(table, alive)
            if self.op in ("&", "|") and l.data.dtype == jnp.bool_ \
                    and r.data.dtype == jnp.bool_:
                return untyped_column(*_kleene(self.op, l, r))
            return untyped_column(_BIN_FNS[self.op](l.data, r.data),
                                  _both_valid(l.validity, r.validity))
        n = table.num_rows
        compares = self.op in _CMP_OPS
        # a literal beside a comparison is one row, which broadcasts
        l, r = (decimal_utils.literal_column(e.value, 1 if compares else n)
                if isinstance(e, Literal) else e.column(table, alive)
                for e in (self.left, self.right))
        if compares:
            return Column(dtype=dtypes.BOOL, length=n,
                          data=jnp.broadcast_to(
                              decimal_utils.compare(self.op, l, r), (n,)),
                          validity=_both_valid(l.validity, r.validity))
        return decimal_utils.arithmetic(self.op, l, r, alive)

    def __repr__(self):
        return f"({self.left!r} {self.op} {self.right!r})"


@dataclasses.dataclass(frozen=True, eq=False)
class UnaryOp(Expr):
    op: str
    child: Expr

    def children(self):
        return (self.child,)

    def rebuild(self, children):
        return UnaryOp(self.op, *children)

    def column(self, table, alive=None):
        from ..columnar import Column
        from ..ops import decimal256 as d256
        c = self.child.column(table, alive)
        if decimal_type(self, _types_of(table)) is None:
            return untyped_column(~c.data if self.op == "~" else -c.data,
                                  c.validity)
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

    def children(self):
        return (self.child,)

    def rebuild(self, children):
        return ScalarAgg(self.op, *children)

    def column(self, table, alive=None):
        c = self.child.column(table, alive)
        v, ok = c.data, _both_valid(c.validity, alive)
        if ok is not None:
            v = jnp.where(ok, v, _reduce_identity(self.op, v.dtype))
        v = {"max": jnp.max, "min": jnp.min, "sum": jnp.sum}[self.op](v)
        n = table.num_rows
        # null rows are skipped; over a nullable input with no valid row
        # the aggregate is NULL, as Spark's is
        return untyped_column(jnp.broadcast_to(v, (n,)),
                              None if c.validity is None
                              else jnp.broadcast_to(jnp.any(ok), (n,)))

    def __repr__(self):
        return f"{self.op}({self.child!r})"


@dataclasses.dataclass(frozen=True, eq=False)
class IsNull(Expr):
    """`child IS NULL` (`negate`: `IS NOT NULL`). Never null itself; over
    a column without a validity mask it is a constant and reads nothing."""
    child: Expr
    negate: bool = False

    def children(self):
        return (self.child,)

    def rebuild(self, children):
        return IsNull(*children, negate=self.negate)

    def column(self, table, alive=None):
        v = self.child.column(table, alive).validity
        if v is None:
            return untyped_column(jnp.full((table.num_rows,), self.negate))
        return untyped_column(v if self.negate else ~v)

    def __repr__(self):
        return f"({self.child!r} is {'not ' if self.negate else ''}null)"


def _branches(e: Expr, exprs, table, alive):
    """-> (type, Columns) of a `when` / `coalesce`'s value branches under
    ONE type: where a decimal reaches any of them (`decimal_type` has
    checked that they agree), an integer literal takes that type; None
    where none does."""
    dt = decimal_type(e, _types_of(table))
    return dt, [_literal_as(dt, b.value, table.num_rows)
                if dt is not None and isinstance(b, Literal)
                else b.column(table, alive) for b in exprs]


def _literal_as(dt, value: int, n: int):
    """An integer literal as `n` rows of the decimal type `dt`."""
    from ..columnar import Column
    from ..dtypes import Kind
    from ..ops.decimal_utils import _i64_limbs
    unscaled = int(value) * 10 ** dt.scale
    if not -(2 ** 63) <= unscaled < 2 ** 63:
        raise TypeError(f"literal {value} as {dt} passes 64 bits")
    data = jnp.full((n,), unscaled, jnp.int64)
    data = _i64_limbs(data) if dt.kind == Kind.DECIMAL128 \
        else data.astype(dt.storage_dtype())
    return Column(dtype=dt, length=n, data=data)


def _pick(mask, a, b):
    """`a` where `mask` else `b`, over data buffers of one layout."""
    return jnp.where(mask if a.ndim == 1 else mask[:, None], a, b)


def _typed(dt, data, validity):
    from ..columnar import Column
    if dt is None:
        return untyped_column(data, validity)
    return Column(dtype=dt, length=int(data.shape[0]), data=data,
                  validity=validity)


@dataclasses.dataclass(frozen=True, eq=False)
class When(Expr):
    """`CASE WHEN cond THEN then ELSE otherwise END`: `then` where `cond`
    is TRUE, `otherwise` where it is false or null."""
    cond: Expr
    then: Expr
    otherwise: Expr

    def children(self):
        return (self.cond, self.then, self.otherwise)

    def rebuild(self, children):
        return When(*children)

    def column(self, table, alive=None):
        take = self.cond.truth(table, alive)
        dt, (t, o) = _branches(self, (self.then, self.otherwise), table,
                               alive)
        validity = None
        if t.validity is not None or o.validity is not None:
            validity = jnp.where(take, t.null_mask, o.null_mask)
        return _typed(dt, _pick(take, t.data, o.data), validity)

    def __repr__(self):
        return (f"when({self.cond!r}, {self.then!r}, "
                f"{self.otherwise!r})")


@dataclasses.dataclass(frozen=True, eq=False)
class Coalesce(Expr):
    """The first of `args` that is not null (null where all are)."""
    args: Tuple[Expr, ...]

    def children(self):
        return self.args

    def rebuild(self, children):
        return Coalesce(tuple(children))

    def column(self, table, alive=None):
        dt, cols = _branches(self, self.args, table, alive)
        out = cols[0]
        for c in cols[1:]:
            if out.validity is None:    # static: nothing left to replace
                break
            out = _typed(dt, _pick(out.validity, out.data, c.data),
                         None if c.validity is None
                         else out.validity | c.validity)
        return out

    def __repr__(self):
        return f"coalesce({', '.join(map(repr, self.args))})"


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
    if isinstance(e, IsNull):
        decimal_type(e.child, col_type)      # a boolean; the child is checked
        return None
    if isinstance(e, (When, Coalesce)):
        if isinstance(e, When):
            decimal_type(e.cond, col_type)
        branches = e.children()[1:] if isinstance(e, When) else e.args
        types = [decimal_type(b, col_type) for b in branches]
        found = [t for t in types if t is not None]
        if not found:
            return None
        for b, t in zip(branches, types):
            if t != found[0] and not (t is None and isinstance(b, Literal)
                                      and isinstance(b.value, int)
                                      and not isinstance(b.value, bool)):
                raise TypeError(
                    f"{e!r}: the branches must have one decimal type "
                    f"({found[0]} beside {t if t is not None else b!r}; "
                    "a plan states the cast)")
        return found[0]
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
    kids = e.children()
    folded = tuple(fold(c) for c in kids)
    if isinstance(e, IsNull) and isinstance(folded[0], Literal):
        return Literal(e.negate)             # a literal is never null
    if isinstance(e, When) and isinstance(folded[0], Literal):
        return folded[1] if folded[0].value else folded[2]
    if all(f is c for f, c in zip(folded, kids)):
        return e
    return e.rebuild(folded)


def substitute(e: Expr, mapping) -> Expr:
    """Replace every `ColumnRef(name)` with `mapping[name]` (an Expr) —
    how a predicate is rewritten through a Project during pushdown.
    Unmapped names raise KeyError (callers guard with references())."""
    if isinstance(e, ColumnRef):
        return mapping[e.name]
    kids = e.children()
    return e.rebuild([substitute(c, mapping) for c in kids]) if kids else e


def has_scalar_agg(e: Expr) -> bool:
    """Whether the expression contains a whole-relation scalar aggregate —
    such expressions are NOT row-wise, so reorderings that change the row
    set under them (pushdown below a join/union, limit pushdown) are
    invalid and the optimizer must skip them."""
    return isinstance(e, ScalarAgg) or any(
        has_scalar_agg(c) for c in e.children())


def nullable(e: Expr, col_nullable, col_type=None) -> bool:
    """Whether a row of `e` can be null: `col_nullable(name)` says it of a
    column (unknown: True). Conservative where a value decides (a `&`
    with a null side counts as nullable; decimal arithmetic, whose
    overflow nulls the row, always does where `col_type` can tell)."""
    if isinstance(e, ColumnRef):
        return bool(col_nullable(e.name))
    if isinstance(e, (Literal, IsNull)):
        return False
    if isinstance(e, When):
        return any(nullable(b, col_nullable, col_type)
                   for b in (e.then, e.otherwise))
    if isinstance(e, Coalesce):
        return all(nullable(a, col_nullable, col_type) for a in e.args)
    if col_type is not None and isinstance(e, BinOp):
        try:
            if decimal_type(e, col_type) is not None:
                return True
        except TypeError:
            return True
    return any(nullable(c, col_nullable, col_type) for c in e.children())


def not_null_columns(pred: Expr) -> frozenset:
    """The columns a filter on `pred` proves hold no NULL in the rows it
    keeps: a filter keeps TRUE alone, so a top-level AND conjunct `col IS
    NOT NULL` says it of `col` (Catalyst narrows an attribute's
    nullability by the same constraint). The filter's output drops those
    columns' validity masks, and every sort below stops carrying a null
    rank and a mask plane for a key that cannot be null (TPC-DS's `where
    ws_item_sk is not null`)."""
    if isinstance(pred, BinOp) and pred.op == "&":
        return not_null_columns(pred.left) | not_null_columns(pred.right)
    if isinstance(pred, IsNull) and pred.negate \
            and isinstance(pred.child, ColumnRef):
        return frozenset((pred.child.name,))
    return frozenset()


def null_aware(e: Expr) -> bool:
    """Whether the expression can be TRUE or non-null over a row whose
    inputs are null (`is_null`, `when`, `coalesce` anywhere in it): such a
    predicate does not commute with an outer join's null extension."""
    return isinstance(e, (IsNull, When, Coalesce)) or any(
        null_aware(c) for c in e.children())


# ---- public constructors ----------------------------------------------------

def col(name: str) -> ColumnRef:
    """Reference a column of the input relation by name."""
    return ColumnRef(name)


def lit(value) -> Literal:
    """A literal, broadcast to the relation's length."""
    return Literal(value)


def is_null(e) -> IsNull:
    """`e IS NULL`."""
    return IsNull(_wrap(e))


def is_not_null(e) -> IsNull:
    """`e IS NOT NULL`."""
    return IsNull(_wrap(e), negate=True)


def when(cond, then, otherwise) -> When:
    """`CASE WHEN cond THEN then ELSE otherwise END`."""
    return When(_wrap(cond), _wrap(then), _wrap(otherwise))


def coalesce(*args) -> Coalesce:
    """The first argument that is not null."""
    if not args:
        raise ValueError("coalesce needs an argument")
    return Coalesce(tuple(_wrap(a) for a in args))


def scalar_max(e: Expr) -> ScalarAgg:
    return ScalarAgg("max", _wrap(e))


def scalar_min(e: Expr) -> ScalarAgg:
    return ScalarAgg("min", _wrap(e))


def scalar_sum(e: Expr) -> ScalarAgg:
    return ScalarAgg("sum", _wrap(e))
