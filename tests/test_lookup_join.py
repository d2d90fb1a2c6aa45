"""The eager joins' small-side path (ops/join.py, ops/join_lookup.py): with
one side of at most LOOKUP_SMALL rows and the other of at least
LOOKUP_LARGE, membership by comparison, the positions of the survivors,
then the sort join over the small side and the survivors. Every case holds
the public join to the sort join of the whole sides: the same gather maps,
pair for pair IN ORDER. Since PR 43 the path reads the large side once
whatever share of it passes (one row in 16, in 5, in 2, all): the
survivors move the way `compaction_path` says, and where the small side is
the right one with distinct keys no sort join follows.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, dtypes
from spark_rapids_tpu.ops import join as J
from spark_rapids_tpu.ops import join_lookup as jl
from spark_rapids_tpu.ops.scans import live_positions

S = jl.LOOKUP_SMALL
MODULE_FLOOR = jl.LOOKUP_LARGE
FLOOR = 1 << 13                      # the cases run with the floor here
N = FLOOR + 1_500                    # a large side just over it
MONEY = dtypes.decimal(15, 2)


@pytest.fixture(autouse=True)
def low_floor(monkeypatch):
    """The path's floor is where the sort join of the whole sides starts
    to cost more than the path's fixed price on the chip (262,144 rows);
    the cases hold the same code to the sort join at a size the CPU sorts
    in a moment."""
    monkeypatch.setattr(jl, "LOOKUP_LARGE", FLOOR)


def _col(values, dtype=dtypes.INT64, valid=None):
    return Column.from_numpy(np.asarray(values), dtype, validity=valid)


def _sides(case: str):
    """-> (small key columns, large key columns) of one case."""
    rng = np.random.default_rng(len(case) * 7919 + sum(map(ord, case)))
    large = rng.integers(-(1 << 40), 1 << 40, N)
    hits = large[rng.integers(0, N, 300)]
    small = np.concatenate([hits, rng.integers(1 << 41, 1 << 42, 77)])
    if case == "int64":
        return [_col(small)], [_col(large)]
    if case in ("int32", "date32"):
        dt = dtypes.INT32 if case == "int32" else dtypes.DATE32
        large = rng.integers(-(1 << 30), 1 << 30, N)
        small = np.concatenate([large[:200], np.full(9, (1 << 31) - 1)])
        return [_col(small, dt)], [_col(large, dt)]
    if case == "decimal64":
        return [_col(small, MONEY)], [_col(large, MONEY)]
    if case == "high_words_differ":
        # equal low words under different high words match nothing
        large = (rng.integers(0, 1 << 20, N) << 32) | 7
        small = np.concatenate([large[:50], (np.arange(50) + (1 << 21)) << 32
                                | 7, np.full(3, 7)])
        return [_col(small)], [_col(large)]
    if case == "two_columns":
        a, b = rng.integers(0, 300, N), rng.integers(0, 1 << 34, N)
        at = rng.integers(0, N, 200)
        # the first column alone matches thousands of rows; the pair, few
        sa = np.concatenate([a[at], a[at[:50]]])
        sb = np.concatenate([b[at], b[at[:50]] + 1])
        return ([_col(sa, dtypes.INT32), _col(sb)],
                [_col(a, dtypes.INT32), _col(b)])
    if case == "nulls":
        return ([_col(small, valid=rng.random(small.size) > 0.2)],
                [_col(large, valid=rng.random(N) > 0.1)])
    if case == "nulls_two_columns":
        a, b = rng.integers(0, 300, N), rng.integers(0, 300, N)
        return ([_col(a[:300], valid=rng.random(300) > 0.2),
                 _col(b[:300])],
                [_col(a), _col(b, valid=rng.random(N) > 0.1)])
    if case == "duplicates":
        large = rng.integers(0, 5_000, N)       # some 13 rows a key
        return [_col(np.repeat(large[:40], 3))], [_col(large)]
    if case == "no_match":
        return [_col(rng.integers(1 << 41, 1 << 42, 100))], [_col(large)]
    if case == "every_row":
        return [_col(np.arange(64))], [_col(rng.integers(0, 64, N))]
    if case.startswith("one_row_in_"):       # past the share of "few kept"
        keys = 64 // int(case[len("one_row_in_"):])
        return [_col(np.arange(keys))], [_col(rng.integers(0, 64, N))]
    if case == "many_duplicates":            # one row in 4, each key twice
        return ([_col(np.repeat(np.arange(16), 2))],
                [_col(rng.integers(0, 64, N))])
    if case == "many_nulls":                 # one row in 2 less the nulls
        return ([_col(np.arange(32), valid=rng.random(32) > 0.2)],
                [_col(rng.integers(0, 64, N), valid=rng.random(N) > 0.1)])
    if case == "many_two_columns":           # one pair in 4
        return ([_col(np.arange(16) % 8, dtypes.INT32),
                 _col(np.arange(16) // 8)],
                [_col(rng.integers(0, 8, N), dtypes.INT32),
                 _col(rng.integers(0, 8, N))])
    if case.startswith("small_"):
        n = int(case[len("small_"):])
        return [_col(np.resize(hits, n))], [_col(large)]
    raise AssertionError(case)


CASES = ["int64", "int32", "date32", "decimal64", "high_words_differ",
         "two_columns", "nulls", "nulls_two_columns", "duplicates",
         "no_match", "every_row", "one_row_in_16", "one_row_in_5",
         "one_row_in_2", "many_duplicates", "many_nulls", "many_two_columns",
         "small_0", "small_1", f"small_{S}"]
# more rows pass than `few_kept` holds: until PR 43 the path handed such an
# inner join back to the sort join of the whole sides
MANY_PASS = ("every_row", "one_row_in_16", "one_row_in_5", "one_row_in_2",
             "many_duplicates", "many_nulls", "many_two_columns")


def _order(case, small_side):
    small, large = _sides(case)
    return (small, large) if small_side == "left" else (large, small)


@pytest.mark.parametrize("small_side", ["left", "right"])
@pytest.mark.parametrize("case", CASES)
def test_inner_join_gives_the_sort_joins_pairs_in_order(case, small_side):
    lcols, rcols = _order(case, small_side)
    assert jl.lookup_side(lcols, rcols, False) == small_side
    with jl.lookup_counts() as took:
        lmap, rmap = J.inner_join(lcols, rcols)
    want_l, want_r, total = J._sort_inner_join(lcols, rcols, False)
    assert lmap.length == rmap.length == total
    assert np.array_equal(lmap.data, want_l)
    assert np.array_equal(rmap.data, want_r)
    small, large = sorted((lcols[0].length, rcols[0].length))
    assert took == [(small, large)]
    if case not in ("no_match", "small_0"):
        assert total > 0
    if case in MANY_PASS:
        assert total * 32 > large


@pytest.mark.parametrize("case", MANY_PASS)
def test_many_rows_pass_a_distinct_right_side_and_no_sort_join_runs(
        case, monkeypatch):
    """A fact table against a filtered dimension (the small side on the
    right, its keys distinct): the match rides the survivors' compaction.
    Duplicate small keys, or the small side on the left, take the sort
    join over the survivors, never over the whole large side."""
    small, large = _sides(case)
    sorted_rows = []
    sort_join = J._sort_inner_join

    def counted(lcols, rcols, null_equal):
        sorted_rows.append(lcols[0].length + rcols[0].length)
        return sort_join(lcols, rcols, null_equal)
    monkeypatch.setattr(J, "_sort_inner_join", counted)
    survivors = jl.member_mask(small, large)[1]
    J.inner_join(large, small)
    assert sorted_rows == ([small[0].length + survivors]
                           if case == "many_duplicates" else [])
    J.inner_join(small, large)
    assert sorted_rows[-1] == small[0].length + survivors


@pytest.mark.parametrize("how", ["semi", "anti"])
@pytest.mark.parametrize("small_side", ["left", "right"])
@pytest.mark.parametrize("case", CASES)
def test_semi_and_anti_join_give_the_sort_joins_rows(case, small_side, how):
    lcols, rcols = _order(case, small_side)
    join = J.left_semi_join if how == "semi" else J.left_anti_join
    with jl.lookup_counts() as took:
        keep = join(lcols, rcols)
    want = J._sort_semi_anti(lcols, rcols, False, how == "semi")
    assert keep.length == want.shape[0]
    assert np.array_equal(keep.data, want)
    # with the large side on the left the mask is the answer whatever
    # share of the rows it holds; on the right its survivors are what the
    # sort join runs over
    assert len(took) == 1


def test_null_safe_join_over_keys_without_nulls_takes_the_path():
    small, large = _sides("int64")
    with jl.lookup_counts() as took:
        lmap, rmap = J.inner_join(small, large, null_equal=True)
    want_l, want_r, _ = J._sort_inner_join(small, large, True)
    assert took and np.array_equal(lmap.data, want_l) \
        and np.array_equal(rmap.data, want_r)


def _strings(n):
    return Column.from_pylist([f"k{i % 97}" for i in range(n)],
                              dtypes.STRING)


def _decimal128(n):
    return Column.from_pylist(list(range(n)), dtypes.decimal(25, 2))


DECLINES = {
    "small_side_over_the_bound": lambda: (
        [_col(np.arange(S + 1))], [_col(np.arange(N))], False),
    "large_side_under_the_floor": lambda: (
        [_col(np.arange(100))], [_col(np.arange(FLOOR - 1))], False),
    "float_keys": lambda: (
        [_col(np.arange(100.0), dtypes.FLOAT64)],
        [_col(np.arange(float(N)), dtypes.FLOAT64)], False),
    "string_keys": lambda: ([_strings(100)], [_strings(N)], False),
    "decimal128_keys": lambda: ([_decimal128(100)], [_decimal128(N)], False),
    "three_int64_columns": lambda: (
        [_col(np.arange(100))] * 3, [_col(np.arange(N) % 500)] * 3, False),
    "null_safe_over_nullable_keys": lambda: (
        [_col(np.arange(100), valid=np.arange(100) % 5 > 0)],
        [_col(np.arange(N) % 500, valid=np.arange(N) % 7 > 0)], True),
}


@pytest.mark.parametrize("small_side", ["left", "right"])
@pytest.mark.parametrize("why", sorted(DECLINES))
def test_path_declines_and_the_sort_join_runs(why, small_side):
    small, large, null_equal = DECLINES[why]()
    lcols, rcols = (small, large) if small_side == "left" else (large, small)
    assert jl.lookup_side(lcols, rcols, null_equal) is None
    with jl.lookup_counts() as took:
        lmap, rmap = J.inner_join(lcols, rcols, null_equal)
        semi = J.left_semi_join(lcols, rcols, null_equal)
        anti = J.left_anti_join(lcols, rcols, null_equal)
    assert took == []
    want_l, want_r, _ = J._sort_inner_join(lcols, rcols, null_equal)
    assert np.array_equal(lmap.data, want_l) \
        and np.array_equal(rmap.data, want_r)
    assert semi.length + anti.length == lcols[0].length
    if why != "large_side_under_the_floor" or small_side == "right":
        assert semi.length > 0


@pytest.mark.parametrize("small_side", ["left", "right"])
@pytest.mark.parametrize("rows,takes", [(MODULE_FLOOR, True),
                                        (MODULE_FLOOR - 1, False)])
def test_the_modules_floor_is_where_the_path_starts(monkeypatch, rows, takes,
                                                    small_side):
    monkeypatch.setattr(jl, "LOOKUP_LARGE", MODULE_FLOOR)
    rng = np.random.default_rng(rows)
    large = [_col(rng.integers(0, 1 << 40, rows))]
    small = [_col(np.asarray(large[0].data[:500:5]))]
    lcols, rcols = (small, large) if small_side == "left" else (large, small)
    assert (jl.lookup_side(lcols, rcols, False) == small_side) == takes
    with jl.lookup_counts() as took:
        lmap, rmap = J.inner_join(lcols, rcols)
    want_l, want_r, total = J._sort_inner_join(lcols, rcols, False)
    assert took == ([(100, rows)] if takes else []) and total == 100
    assert np.array_equal(lmap.data, want_l) \
        and np.array_equal(rmap.data, want_r)


def test_sides_of_different_types_are_the_sort_joins_to_refuse():
    small, large = [_col(np.arange(100), dtypes.INT32)], [_col(np.arange(N))]
    assert jl.lookup_side(small, large, False) is None
    with pytest.raises(TypeError, match="join key"):
        J.inner_join(small, large)


PLANES = {
    "one_word": ([dtypes.INT32], 1),
    "two_words": ([dtypes.INT64], 2),
    "three_words": ([dtypes.INT32, dtypes.INT64], 3),
}


@pytest.mark.parametrize("live", [0, 1, 677, S])
@pytest.mark.parametrize("layout", sorted(PLANES))
def test_membership_equals_a_plain_set(layout, live):
    """Step 1 alone (`_member`) against a Python set of the live small
    keys: one, two and three key words, dead small slots between the live
    ones, a live count that ends inside a chunk, at a chunk's end, at 0."""
    kinds, planes = PLANES[layout]
    rng = np.random.default_rng(live + planes)
    n = 140_000
    large = [rng.integers(0, 40, n).astype(dt.storage_dtype())
             for dt in kinds]
    alive = np.zeros(S, bool)
    alive[rng.choice(S, live, replace=False)] = True
    small = [rng.integers(0, 48, S).astype(dt.storage_dtype())
             for dt in kinds]
    mask, count = jl._member(
        [jnp.asarray(s) for s in small], jnp.asarray(alive),
        [jnp.asarray(x) for x in large], [])
    keys = {tuple(int(s[i]) for s in small) for i in np.flatnonzero(alive)}
    want = np.fromiter((k in keys for k in zip(*(x.tolist() for x in large))),
                       bool, n)
    assert np.array_equal(mask, want) and int(count) == want.sum()


@pytest.mark.parametrize("n,kept,cap", [
    (1_000, 0, 0), (1_000, 17, 17), (100_003, 4_739, 4_739),
    (100_003, 100_003, 100_003), (65_536, 300, 512), (65_536, 300, 100)])
def test_live_positions_are_the_masks_rows_in_order(n, kept, cap):
    """`ops/scans.py:live_positions` (moved from parallel/relational.py,
    which the SPMD walk's compaction still calls): the first `cap` live
    rows, which slots hold one, and whether rows were lost."""
    rng = np.random.default_rng(n + kept)
    rows = np.sort(rng.choice(n, kept, replace=False))
    mask = np.zeros(n, bool)
    mask[rows] = True
    idx, keep, lost = live_positions(jnp.asarray(mask), cap)
    held = min(kept, cap)
    assert idx.shape == keep.shape == (cap,)
    assert np.array_equal(np.asarray(idx)[:held], rows[:held])
    assert np.asarray(keep).sum() == held and bool(lost) == (kept > cap)
    assert not np.asarray(idx)[held:].any()


@pytest.mark.parametrize("rows", [0, 1, 64, 677, S])
def test_every_small_side_shares_one_membership_program(rows):
    """A new survivor count of an upstream filter compiles no new
    membership program: the small side is padded to LOOKUP_SMALL dead
    slots, and `_member` is keyed by the large side alone."""
    data, live = jl._pad_small([jnp.arange(rows)], [])
    assert data[0].shape == live.shape == (S,)
    assert int(live.sum()) == rows and bool(live[:rows].all())
    large = [_col(np.arange(N) % 1000)]
    jl.member_mask([_col(np.arange(5))], large)
    programs = jl._member._cache_size()
    mask, count = jl.member_mask([_col(np.arange(rows) * 2)], large)
    assert jl._member._cache_size() == programs
    want = np.isin(np.arange(N) % 1000, np.arange(rows) * 2)
    assert count == want.sum() and np.array_equal(mask, want)
