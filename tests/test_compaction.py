"""The eager tier's row compaction (ops/gather.py): `apply_boolean_mask`
and `kept_rows` move rows by the count they have read, few kept rows by
their positions, the rest riding one sort keyed on where each row goes.
Every path against numpy, whatever the frame's length; the choice as plain
arithmetic; a warm filter lowers nothing."""
import jax.numpy as jnp
import numpy as np
import numpy.testing as npt
import pytest

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, Table, dtypes
from spark_rapids_tpu.ops import apply_boolean_mask
from spark_rapids_tpu.ops import gather
from spark_rapids_tpu.ops.gather import (FEW_KEPT, KEPT_FLOOR, RIDE_WORDS,
                                         compaction_path, compactions,
                                         few_kept, kept_rows, plane_words,
                                         ride_groups, rows_by_position,
                                         rows_by_sort)
from spark_rapids_tpu.plan import PlanBuilder, PlanExecutor, col
from spark_rapids_tpu.utils import tracing

N = 100 * FEW_KEPT      # the few-kept rule turns between 100 and 101 rows


@pytest.fixture
def no_floor(monkeypatch):
    """The share of the frame alone chooses: frames of these tests' size
    keep fewer rows than `KEPT_FLOOR`, under which all go by positions."""
    monkeypatch.setattr(gather, "KEPT_FLOOR", 0)


# how many of N rows a mask keeps
SHARES = {"none": 0, "one_row": 1, "one_in_1000": N // 1000,
          "few_kept_last": N // FEW_KEPT, "few_kept_past": N // FEW_KEPT + 1,
          "half": N // 2, "all_but_one": N - 1, "all": N}


def _mask(kept: int, seed: int = 0) -> np.ndarray:
    mask = np.zeros(N, bool)
    mask[np.random.default_rng(seed + kept).permutation(N)[:kept]] = True
    return mask


def _nulls(rng):
    return rng.random(N) < 0.25


def _column(kind: str) -> Column:
    rng = np.random.default_rng(len(kind))
    if kind == "int32":
        return Column.from_numpy(rng.integers(-99, 99, N).astype(np.int32))
    if kind == "int64":
        return Column.from_numpy(rng.integers(-2**62, 2**62, N))
    if kind == "float64":
        return Column.from_numpy(rng.standard_normal(N))
    if kind == "bool":
        return Column.from_numpy(rng.random(N) < 0.5)
    if kind == "nullable_int64":
        return Column.from_numpy(rng.integers(-2**40, 2**40, N),
                                 validity=~_nulls(rng))
    if kind == "nullable_float64":
        return Column.from_numpy(rng.standard_normal(N),
                                 validity=~_nulls(rng))
    if kind == "decimal128":
        return Column.from_pylist(
            [None if v % 5 == 0 else (int(v) << 70 | 1) * (-1) ** int(v % 2)
             for v in rng.integers(1, 1 << 30, N)], dtypes.decimal(38, 2))
    assert kind == "string"
    return Column.from_pylist(
        [None if v % 7 == 0 else "s%d" % v for v in rng.integers(1, 999, N)],
        dtypes.STRING)


FIXED = ["int32", "int64", "float64", "bool", "nullable_int64",
         "nullable_float64", "decimal128"]
KINDS = FIXED + ["string"]


def _arrays(c: Column):
    return [np.asarray(p) for p in (c.data, c.validity) if p is not None]


def _assert_rows(got: Column, src: Column, mask: np.ndarray):
    """`got` is `src` at the rows of `mask`, in order: values, validity,
    dtype, and no validity where the source has none."""
    assert got.dtype == src.dtype and got.length == int(mask.sum())
    assert (got.validity is None) == (src.validity is None)
    if src.dtype.is_string:
        assert got.to_pylist() == [v for v, k in zip(src.to_pylist(), mask)
                                   if k]
        return
    for g, want in zip(_arrays(got), _arrays(src)):
        assert g.dtype == want.dtype
        npt.assert_array_equal(g, want[mask])


# ---- the public entries, each path as the count chooses it ------------------------

@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("share", list(SHARES))
def test_apply_boolean_mask_is_numpy_indexing(no_floor, share, kind):
    kept = SHARES[share]
    mask, src = _mask(kept), _column(kind)
    with compactions.collect() as moved:
        got = apply_boolean_mask(src, jnp.asarray(mask))
    _assert_rows(got, src, mask)
    path = compaction_path(N, kept, kind == "string")
    assert moved == [(path, N, kept)]
    assert path == {"all": "none", "half": "sort", "all_but_one": "sort",
                    "few_kept_past": "sort"}.get(share, "positions") \
        + ("+gather" if kind == "string" and path.startswith("sort") else "")
    if path == "none":
        assert got is src


@pytest.mark.parametrize("share", list(SHARES))
def test_kept_rows_is_flatnonzero(no_floor, share):
    """Ascending int32 positions on either side of the rule, with the count
    read here or handed in (the semi / anti joins hold it)."""
    mask = _mask(SHARES[share], seed=3)
    for got in (kept_rows(jnp.asarray(mask)),
                kept_rows(jnp.asarray(mask), SHARES[share]),
                kept_rows(jnp.asarray(mask.astype(np.int64) * 3))):
        got = np.asarray(got)
        assert got.dtype == np.int32
        npt.assert_array_equal(got, np.flatnonzero(mask))


@pytest.mark.parametrize("share", list(SHARES))
def test_a_table_with_a_string_column_and_a_nullable_mask(no_floor, share):
    """A table moves as one: fixed-width columns ride or are gathered with
    the positions, the string column is gathered by the positions the
    same sort gives; a null in a predicate column drops the row."""
    kept = SHARES[share]
    names = ["int64", "string", "nullable_float64", "decimal128"]
    t = Table([_column(k) for k in names], names=names)
    valid = np.ones(N, bool)
    valid[::3] = False
    truth = _mask(kept, seed=1)
    pred = Column.from_numpy(truth | ~valid, validity=valid)
    mask = truth & valid
    with compactions.collect() as moved:
        got = apply_boolean_mask(t, pred)
    assert list(got.names) == names and got.num_rows == int(mask.sum())
    for name in names:
        _assert_rows(got[name], t[name], mask)
    assert moved == [(compaction_path(N, int(mask.sum()), True), N,
                      int(mask.sum()))]


# ---- each path through its own entry, whatever the count -------------------------

@pytest.mark.parametrize("kind", FIXED)
@pytest.mark.parametrize("share", list(SHARES))
@pytest.mark.parametrize("entry", ["positions", "sort", "sort_in_groups"])
def test_each_entry_moves_every_share(entry, share, kind):
    kept = SHARES[share]
    mask, src = _mask(kept, seed=2), _column(kind)
    arrays = [p for p in (src.data, src.validity) if p is not None]
    if entry == "positions":
        rows, got = rows_by_position(jnp.asarray(mask), arrays, kept=kept)
    else:
        words = plane_words(arrays)
        groups = ride_groups(words, limit=RIDE_WORDS if entry == "sort"
                             else 1)
        assert len(groups) == (1 if entry == "sort" else len(words))
        rows, got = rows_by_sort(jnp.asarray(mask), arrays, kept=kept,
                                 groups=groups)
    rows = np.asarray(rows)
    assert rows.dtype == np.int32
    npt.assert_array_equal(rows, np.flatnonzero(mask))
    for g, want in zip(got, _arrays(src)):
        assert np.asarray(g).dtype == want.dtype
        npt.assert_array_equal(np.asarray(g), want[mask])


@pytest.mark.parametrize("share", ["few_kept_past", "half", "all_but_one"])
def test_a_table_wider_than_one_sort_goes_in_groups(no_floor, share):
    """Seven int64 columns, one nullable, are 15 words: two sorts at
    `RIDE_WORDS` (12) words each, the same rows as one column at a time."""
    kept = SHARES[share]
    rng = np.random.default_rng(7)
    cols = [Column.from_numpy(rng.integers(-2**62, 2**62, N),
                              validity=(~_nulls(rng) if j == 3 else None))
            for j in range(7)]
    arrays = [p for c in cols for p in (c.data, c.validity) if p is not None]
    words = plane_words(arrays)
    assert sum(words) == 15 and len(ride_groups(words)) == 2
    mask = _mask(kept, seed=5)
    with compactions.collect() as moved:
        got = apply_boolean_mask(
            Table(cols, names=[f"c{j}" for j in range(7)]),
            jnp.asarray(mask))
    assert moved == [("sort", N, kept)]
    for g, src in zip(got.columns, cols):
        _assert_rows(g, src, mask)


# ---- the choice: arithmetic over counts -------------------------------------------

@pytest.mark.parametrize("n, kept, ragged, path", [
    (0, 0, False, "none"), (0, 0, True, "none"),
    (15_000_000, 15_000_000, False, "none"),
    (15_000_000, 14_834_663, False, "sort"),          # q13.batch's filter
    (15_000_000, 14_834_663, True, "sort+gather"),
    (15_000_000, 677, False, "positions"),            # q18.batch's
    (15_000_000, 677, True, "positions"),
    (15_000_000, 0, False, "positions"),
    (15_000_000, 15_000_000 // FEW_KEPT, False, "positions"),
    (15_000_000, 15_000_000 // FEW_KEPT + 1, False, "sort"),
    (73_049, 6_200, False, "positions"),      # q3.share's date filter:
    (204_000, 204, False, "positions"),       # under the floor; its items
    (20_000, KEPT_FLOOR, False, "positions"),
    (20_000, KEPT_FLOOR + 1, False, "sort"),
    (20_000, KEPT_FLOOR + 1, True, "sort+gather"),
    (KEPT_FLOOR, KEPT_FLOOR, True, "none"),
])
def test_compaction_path_is_arithmetic(n, kept, ragged, path):
    assert compaction_path(n, kept, ragged) == path
    assert few_kept(kept, n) == (kept * FEW_KEPT <= n)


@pytest.mark.parametrize("n, kept, path", [
    (FEW_KEPT, 1, "positions"), (FEW_KEPT - 1, 1, "sort"),
    (1, 0, "positions"), (2, 1, "sort")])
def test_without_the_floor_the_share_chooses(no_floor, n, kept, path):
    assert compaction_path(n, kept) == path
    assert compaction_path(n, kept, True) == path + "+gather" * (path == "sort")


def test_a_frame_past_the_floor_sorts_as_it_stands():
    """No constant lowered: 40,000 rows of which 20,000 stay ride a sort,
    `KEPT_FLOOR` of them go by positions."""
    n = 40_000
    src = Column.from_numpy(np.arange(n) * 3)
    for kept, path in ((n // 2, "sort"), (KEPT_FLOOR, "positions")):
        mask = np.zeros(n, bool)
        mask[np.random.default_rng(kept).permutation(n)[:kept]] = True
        with compactions.collect() as moved:
            got = apply_boolean_mask(src, jnp.asarray(mask))
        assert moved == [(path, n, kept)]
        npt.assert_array_equal(np.asarray(got.data), np.flatnonzero(mask) * 3)
        npt.assert_array_equal(np.asarray(kept_rows(jnp.asarray(mask))),
                               np.flatnonzero(mask))


@pytest.mark.parametrize("words, limit, groups", [
    ((), 8, ()),
    ((1,), 8, ((0,),)),
    ((2, 2, 2, 2), 8, ((0, 1, 2, 3),)),
    ((2, 2, 2, 2, 1), 8, ((0, 1, 2, 3), (4,))),
    ((2, 1, 2, 1, 2, 1), 4, ((0, 1), (2, 3), (4, 5))),
    ((1, 2, 2), 4, ((0, 1), (2,))),
    ((2, 2), 1, ((0,), (1,))),            # a plane past the limit rides alone
    ((1,) * 17, 8, (tuple(range(8)), tuple(range(8, 16)), (16,))),
])
def test_ride_groups_close_before_the_limit(words, limit, groups):
    assert ride_groups(words, limit) == groups
    assert [i for g in groups for i in g] == list(range(len(words)))


def test_plane_words_counts_32_bit_words_a_plane():
    arrays = [jnp.zeros((4,), jnp.int64), jnp.zeros((4,), bool),
              jnp.zeros((4, 4), jnp.uint32), jnp.zeros((4,), jnp.int8),
              jnp.zeros((4,), jnp.float64), jnp.zeros((4,), jnp.float32)]
    assert plane_words(arrays) == (2, 1, 1, 1, 1, 1, 1, 2, 1)
    assert len(gather._planes(arrays)) == len(plane_words(arrays))


# ---- a warm filter is programs that are there -------------------------------------

def _filter_plan():
    b = PlanBuilder()
    return (b.scan("t", schema=["k", "v", "w"]).filter(col("v") > 10)
            .project([("k", col("k")), ("twice", col("v") + col("v"))])
            .build())


@pytest.mark.parametrize("keeps", ["most", "few"])
def test_a_second_eager_filter_lowers_nothing(no_floor, keeps):
    """An eager `FusedSelect` over the same shapes and the same count again
    lowers no program (each path is one module-level jit, `kept` static),
    whichever way its rows move."""
    n = 4000
    v = np.arange(n) % 100 if keeps == "most" else \
        np.where(np.arange(n) % 400 == 0, 50, 0)
    t = Table([Column.from_numpy(np.arange(n)), Column.from_numpy(v),
               Column.from_numpy(np.arange(n) * 2)], names=["k", "v", "w"])
    plan, ex = _filter_plan(), PlanExecutor(mode="eager")
    first = ex.execute(plan, {"t": t})
    (m,) = [m for m in first.metrics.values() if m.compact]
    assert m.compact == ("sort" if keeps == "most" else "positions")
    assert first.compactions == 1
    assert (first.compact_sorted_rows, first.compact_position_rows) \
        == ((n, 0) if keeps == "most" else (0, n))
    ex.execute(plan, {"t": t})      # the stats store re-plans the second
    with tracing.bracket("test.filter") as b:
        again = ex.execute(plan, {"t": t})
    assert b.lowered()[0] == 0 and again.lowerings == 0
    npt.assert_array_equal(np.asarray(again.table["k"].data),
                           np.flatnonzero(v > 10))
