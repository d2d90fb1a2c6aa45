"""sort / gather / groupby / join tests (BASELINE.json configs[0-2]; oracle =
numpy/pandas, the way the reference's JUnit tests oracle against BigDecimal /
java.time — SURVEY.md §4 tier 2)."""
import numpy as np
import pandas as pd
import pytest

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, Table, dtypes
from spark_rapids_tpu.ops import (groupby_aggregate, inner_join,
                                  left_anti_join, left_join, left_semi_join,
                                  sort_table, sorted_order, take)


def col(values, dtype=None, nulls=None):
    arr = np.asarray(values, dtype=dtype)
    c = Column.from_numpy(arr)
    if nulls is not None:
        import jax.numpy as jnp
        c = c.with_validity(jnp.asarray(~np.asarray(nulls)))
    return c


def scol(values):
    return Column.from_pylist(values, dtypes.STRING)


# ---- take -------------------------------------------------------------------

def test_take_fixed_and_null_index():
    c = col([10, 20, 30, 40], np.int64, nulls=[False, True, False, False])
    out = take(c, np.array([3, 1, 0, -1], np.int32))
    assert out.to_pylist() == [40, None, 10, None]


def test_take_strings():
    c = scol(["aa", None, "cccc", ""])
    out = take(c, np.array([2, 0, -1, 3, 1], np.int32))
    assert out.to_pylist() == ["cccc", "aa", None, "", None]


def test_take_decimal128():
    from spark_rapids_tpu.ops import string_to_decimal
    c = string_to_decimal(scol(["1.23", "-99999999999999999999.99", "0.01"]),
                          precision=38, scale=2)
    out = take(c, np.array([2, 0], np.int32))
    assert out.to_pylist() == [1, 123]    # unscaled values at scale 2


# ---- sort -------------------------------------------------------------------

def test_sorted_order_ints_stable():
    c = col([3, 1, 2, 1, 3], np.int64)
    order = np.asarray(sorted_order([c]).data)
    assert order.tolist() == [1, 3, 2, 0, 4]


def test_sort_multi_key_mixed_direction():
    a = col([1, 1, 2, 2, 1], np.int32)
    b = col([5.0, 7.0, 1.0, 3.0, 6.0], np.float64)
    t = Table([a, b], names=["a", "b"])
    out = sort_table(t, ["a", "b"], ascending=[True, False])
    assert out["a"].to_pylist() == [1, 1, 1, 2, 2]
    assert out["b"].to_pylist() == [7.0, 6.0, 5.0, 3.0, 1.0]


def test_sort_nulls_first_last():
    c = col([2, 0, 1, 0], np.int64, nulls=[False, True, False, True])
    asc = sort_table(Table([c]), [0]).columns[0].to_pylist()
    assert asc == [None, None, 1, 2]            # Spark asc: nulls first
    desc = sort_table(Table([c]), [0], ascending=False).columns[0].to_pylist()
    assert desc == [2, 1, None, None]           # Spark desc: nulls last


def test_sort_float_nan_and_negzero():
    c = col([np.nan, 1.0, -np.inf, -0.0, 0.0, np.inf], np.float64)
    out = sort_table(Table([c]), [0]).columns[0].to_pylist()
    assert np.isnan(out[-1])                    # NaN greatest, like Spark
    assert out[:5] == [-np.inf, 0.0, 0.0, 1.0, np.inf]


def test_sort_strings_bytewise():
    c = scol(["b", "", "ab", "a", "a\x00", "ba", None])
    out = sort_table(Table([c]), [0]).columns[0].to_pylist()
    assert out == [None, "", "a", "a\x00", "ab", "b", "ba"]


def test_sort_random_against_numpy():
    rng = np.random.default_rng(0)
    vals = rng.integers(-1000, 1000, size=4096).astype(np.int64)
    out = sort_table(Table([col(vals)]), [0]).columns[0].to_pylist()
    assert out == sorted(vals.tolist())


# ---- groupby ----------------------------------------------------------------

def test_groupby_sum_count_basic():
    k = col([1, 2, 1, 2, 1], np.int32)
    v = col([10, 20, 30, 40, 50], np.int64)
    t = Table([k, v], names=["k", "v"])
    out = groupby_aggregate(t, ["k"], [("v", "sum"), ("v", "count"),
                                       ("v", "size")])
    assert out["k"].to_pylist() == [1, 2]
    assert out["sum(v)"].to_pylist() == [90, 60]
    assert out["count(v)"].to_pylist() == [3, 2]
    assert out["size(*)"].to_pylist() == [3, 2]


def test_groupby_nulls_in_keys_and_values():
    k = col([1, 1, 0, 2], np.int32, nulls=[False, False, True, False])
    v = col([5, 0, 7, 9], np.int64, nulls=[False, True, False, False])
    t = Table([k, v], names=["k", "v"])
    out = groupby_aggregate(t, ["k"], [("v", "sum"), ("v", "count")])
    # null key is its own group, sorted first
    assert out["k"].to_pylist() == [None, 1, 2]
    assert out["sum(v)"].to_pylist() == [7, 5, 9]
    assert out["count(v)"].to_pylist() == [1, 1, 1]


def test_groupby_all_null_group_yields_null_agg():
    k = col([1, 1, 2], np.int32)
    v = col([0, 0, 3], np.int64, nulls=[True, True, False])
    out = groupby_aggregate(Table([k, v], names=["k", "v"]), ["k"],
                            [("v", "sum"), ("v", "min"), ("v", "max"),
                             ("v", "mean")])
    assert out["sum(v)"].to_pylist() == [None, 3]
    assert out["min(v)"].to_pylist() == [None, 3]
    assert out["max(v)"].to_pylist() == [None, 3]
    assert out["mean(v)"].to_pylist() == [None, 3.0]


def test_groupby_string_keys():
    k = scol(["x", "y", "x", None, "y", "x"])
    v = col([1, 2, 3, 4, 5, 6], np.int64)
    out = groupby_aggregate(Table([k, v], names=["k", "v"]), ["k"],
                            [("v", "sum")])
    assert out["k"].to_pylist() == [None, "x", "y"]
    assert out["sum(v)"].to_pylist() == [4, 10, 7]


def test_groupby_random_against_pandas():
    rng = np.random.default_rng(1)
    n = 20_000
    k1 = rng.integers(0, 97, size=n).astype(np.int32)
    k2 = rng.integers(0, 5, size=n).astype(np.int64)
    v = rng.integers(-10**6, 10**6, size=n).astype(np.int64)
    f = rng.standard_normal(n)
    t = Table([col(k1), col(k2), col(v), col(f)], names=["k1", "k2", "v", "f"])
    out = groupby_aggregate(t, ["k1", "k2"],
                            [("v", "sum"), ("v", "min"), ("f", "max"),
                             ("v", "count"), ("f", "mean")])
    df = pd.DataFrame({"k1": k1, "k2": k2, "v": v, "f": f})
    ref = df.groupby(["k1", "k2"], sort=True).agg(
        s=("v", "sum"), mn=("v", "min"), mx=("f", "max"),
        c=("v", "count"), m=("f", "mean")).reset_index()
    assert out["k1"].to_pylist() == ref["k1"].tolist()
    assert out["k2"].to_pylist() == ref["k2"].tolist()
    assert out["sum(v)"].to_pylist() == ref["s"].tolist()
    assert out["min(v)"].to_pylist() == ref["mn"].tolist()
    assert np.allclose(out["max(f)"].to_pylist(), ref["mx"].tolist())
    assert out["count(v)"].to_pylist() == ref["c"].tolist()
    assert np.allclose(out["mean(f)"].to_pylist(), ref["m"].tolist())


def test_groupby_string_min_max():
    k = col([1, 1, 1, 2, 2, 3], np.int32)
    s = scol(["pear", "apple", None, "b", "a", None])
    out = groupby_aggregate(Table([k, s], names=["k", "s"]), ["k"],
                            [("s", "min"), ("s", "max"), ("s", "count")])
    # min/max ignore nulls; an all-null group yields null
    assert out["min(s)"].to_pylist() == ["apple", "a", None]
    assert out["max(s)"].to_pylist() == ["pear", "b", None]
    assert out["count(s)"].to_pylist() == [2, 2, 0]


def test_groupby_string_min_max_against_pandas():
    rng = np.random.default_rng(4)
    n = 5000
    k = rng.integers(0, 40, n).astype(np.int32)
    words = np.array(["kiwi", "fig", "apple", "banana", "cherry", "date",
                      "elderberry", "grape"])
    s = words[rng.integers(0, len(words), n)]
    t = Table([col(k), scol(list(s))], names=["k", "s"])
    out = groupby_aggregate(t, ["k"], [("s", "min"), ("s", "max")])
    df = pd.DataFrame({"k": k, "s": s})
    ref = df.groupby("k", sort=True).agg(mn=("s", "min"),
                                         mx=("s", "max")).reset_index()
    assert out["min(s)"].to_pylist() == ref["mn"].tolist()
    assert out["max(s)"].to_pylist() == ref["mx"].tolist()


def test_groupby_string_min_max_empty_table():
    t = Table([col([], np.int32), scol([])], names=["k", "s"])
    out = groupby_aggregate(t, ["k"], [("s", "min"), ("s", "max")])
    assert out.num_rows == 0
    assert out["min(s)"].to_pylist() == []


def test_sort_empty_string_keys():
    t = Table([scol([])], names=["s"])
    from spark_rapids_tpu.ops import sort_table
    assert sort_table(t, ["s"]).num_rows == 0


def test_groupby_int_sum_wraps_like_java_long():
    k = col([7, 7], np.int32)
    v = col([2**63 - 1, 1], np.int64)
    out = groupby_aggregate(Table([k, v], names=["k", "v"]), ["k"],
                            [("v", "sum")])
    assert out["sum(v)"].to_pylist() == [-(2**63)]   # wraps, non-ANSI Spark


# ---- joins ------------------------------------------------------------------

def test_inner_join_basic_with_dups():
    lk = col([1, 2, 3, 2], np.int64)
    rk = col([2, 4, 2, 1], np.int64)
    lmap, rmap = inner_join([lk], [rk])
    pairs = sorted(zip(lmap.to_pylist(), rmap.to_pylist()))
    assert pairs == [(0, 3), (1, 0), (1, 2), (3, 0), (3, 2)]


def test_inner_join_nulls_never_match():
    lk = col([1, 0, 2], np.int64, nulls=[False, True, False])
    rk = col([0, 2], np.int64, nulls=[True, False])
    lmap, rmap = inner_join([lk], [rk])
    assert sorted(zip(lmap.to_pylist(), rmap.to_pylist())) == [(2, 1)]
    # null-safe equality (<=>) matches nulls
    lmap2, rmap2 = inner_join([lk], [rk], null_equal=True)
    assert sorted(zip(lmap2.to_pylist(), rmap2.to_pylist())) == [(1, 0), (2, 1)]


def test_left_join_unmatched_gets_null():
    lk = col([5, 6], np.int64)
    rk = col([6], np.int64)
    rv = scol(["hit"])
    lmap, rmap = left_join([lk], [rk])
    got = sorted(zip(lmap.to_pylist(), rmap.to_pylist()))
    assert got == [(0, -1), (1, 0)]
    joined = take(rv, rmap.data)
    by_left = dict(zip(lmap.to_pylist(), joined.to_pylist()))
    assert by_left == {0: None, 1: "hit"}


def test_semi_and_anti_join():
    lk = col([1, 2, 3, 0], np.int64, nulls=[False, False, False, True])
    rk = col([2, 2, 3], np.int64)
    assert left_semi_join([lk], [rk]).to_pylist() == [1, 2]
    assert left_anti_join([lk], [rk]).to_pylist() == [0, 3]


@pytest.mark.parametrize("n, share", [(0, 0.5), (1, 1.0), (7, 0.0),
                                      (100_000, 0.3), (200_000, 1e-4),
                                      (70_000, 1.0)])
def test_kept_rows_is_nonzero(n, share):
    """The filter's and the semi / anti joins' row ids: `jnp.nonzero` in
    int32, ascending, few rows kept (their positions) and many (a sort);
    an integer mask reads as its truth. tests/test_compaction.py holds
    every path at every share."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import apply_boolean_mask
    from spark_rapids_tpu.ops.gather import kept_rows
    mask = np.random.default_rng(n).random(n) < share
    for m in (mask, mask.astype(np.int64) * 3):
        got = np.asarray(kept_rows(jnp.asarray(m)))
        assert got.dtype == np.int32
        assert np.array_equal(got, np.flatnonzero(mask))
    col = Column.from_numpy(np.arange(n, dtype=np.int64))
    assert np.array_equal(
        np.asarray(apply_boolean_mask(col, jnp.asarray(mask)).data),
        np.flatnonzero(mask))


def test_join_multi_key_and_strings():
    lk1 = col([1, 1, 2], np.int32)
    lk2 = scol(["a", "b", "a"])
    rk1 = col([1, 2, 1], np.int32)
    rk2 = scol(["b", "a", "z"])
    lmap, rmap = inner_join([lk1, lk2], [rk1, rk2])
    assert sorted(zip(lmap.to_pylist(), rmap.to_pylist())) == [(1, 0), (2, 1)]


def test_join_empty_right():
    lk = col([1, 2], np.int64)
    rk = col([], np.int64)
    lmap, rmap = inner_join([lk], [rk])
    assert lmap.length == 0
    lmap, rmap = left_join([lk], [rk])
    assert sorted(zip(lmap.to_pylist(), rmap.to_pylist())) == [(0, -1), (1, -1)]


def test_null_payload_bytes_do_not_split_groups():
    # payload under null slots is undefined; two nulls with different
    # underlying bytes must still be ONE group / match under <=>
    import jax.numpy as jnp
    k = Column.from_numpy(np.array([5, 7], np.int64)).with_validity(
        jnp.asarray([False, False]))
    v = col([1, 2], np.int64)
    out = groupby_aggregate(Table([k, v], names=["k", "v"]), ["k"],
                            [("v", "sum")])
    assert out["k"].to_pylist() == [None]
    assert out["sum(v)"].to_pylist() == [3]
    lk = Column.from_numpy(np.array([5], np.int64)).with_validity(
        jnp.asarray([False]))
    rk = Column.from_numpy(np.array([7], np.int64)).with_validity(
        jnp.asarray([False]))
    lmap, rmap = inner_join([lk], [rk], null_equal=True)
    assert list(zip(lmap.to_pylist(), rmap.to_pylist())) == [(0, 0)]


def test_groupby_float_min_max_nan_semantics():
    # Spark: NaN is greatest — min skips NaN unless the group is all-NaN
    k = col([1, 1, 1, 2, 2], np.int32)
    v = col([np.nan, 3.0, 7.0, np.nan, np.nan], np.float64)
    out = groupby_aggregate(Table([k, v], names=["k", "v"]), ["k"],
                            [("v", "min"), ("v", "max")])
    mins = out["min(v)"].to_pylist()
    maxs = out["max(v)"].to_pylist()
    assert mins[0] == 3.0 and np.isnan(mins[1])
    assert np.isnan(maxs[0]) and np.isnan(maxs[1])


def test_groupby_float_sum_nan_inf_stay_confined():
    # a NaN/Inf in one group must not poison later groups' sums (global
    # cumsum-difference would produce NaN - NaN = NaN everywhere after)
    k = col([1, 2, 3, 3], np.int32)
    v = col([np.nan, np.inf, 1.5, 2.5], np.float64)
    out = groupby_aggregate(Table([k, v], names=["k", "v"]), ["k"],
                            [("v", "sum"), ("v", "mean")])
    sums = out["sum(v)"].to_pylist()
    assert np.isnan(sums[0]) and sums[1] == np.inf and sums[2] == 4.0
    means = out["mean(v)"].to_pylist()
    assert np.isnan(means[0]) and means[1] == np.inf and means[2] == 2.0


def test_join_rejects_mismatched_decimal_scales():
    from spark_rapids_tpu.ops import string_to_decimal
    a = string_to_decimal(scol(["1.00"]), precision=18, scale=2)
    b = string_to_decimal(scol(["100"]), precision=18, scale=0)
    with pytest.raises(TypeError):
        inner_join([a], [b])


def test_join_random_against_pandas():
    rng = np.random.default_rng(3)
    nl, nr = 5000, 1000
    lk = rng.integers(0, 700, size=nl).astype(np.int64)
    rk = rng.integers(0, 700, size=nr).astype(np.int64)
    lmap, rmap = inner_join([col(lk)], [col(rk)])
    got = sorted(zip(lmap.to_pylist(), rmap.to_pylist()))
    dl = pd.DataFrame({"k": lk, "li": np.arange(nl)})
    dr = pd.DataFrame({"k": rk, "ri": np.arange(nr)})
    ref = dl.merge(dr, on="k")
    assert got == sorted(zip(ref["li"].tolist(), ref["ri"].tolist()))


def test_groupby_capped_matches_uncapped_under_jit():
    import jax
    from spark_rapids_tpu.ops import groupby_aggregate_capped
    rng = np.random.default_rng(17)
    n = 5000
    t = Table([Column.from_numpy(rng.integers(0, 37, n).astype(np.int32)),
               Column.from_numpy(rng.integers(-100, 100, n).astype(np.int64))],
              names=["k", "v"])
    ref = groupby_aggregate(t, ["k"], [("v", "sum"), ("v", "count"),
                                       ("v", "min"), ("v", "mean")])

    @jax.jit
    def run(tb):
        out, valid, overflow = groupby_aggregate_capped(
            tb, ["k"], [("v", "sum"), ("v", "count"), ("v", "min"),
                        ("v", "mean")], key_cap=64)
        return [c.data for c in out.columns], valid, overflow

    cols, valid, overflow = run(t)
    assert not bool(overflow)
    v = np.asarray(valid)
    assert v.sum() == ref.num_rows
    for got, want in zip(cols, ref.columns):
        np.testing.assert_array_equal(np.asarray(got)[v],
                                      np.asarray(want.data))

    # overflow flags when the cap is too small
    out2, valid2, overflow2 = groupby_aggregate_capped(
        t, ["k"], [("v", "sum")], key_cap=8)
    assert bool(overflow2)


def test_groupby_capped_small_batch_and_overflow_retry():
    from spark_rapids_tpu.ops import groupby_aggregate_capped
    # cap larger than the batch: pads, never raises (fixed-cap jit pipeline)
    t = Table([Column.from_numpy(np.array([3, 1, 3], np.int32)),
               Column.from_numpy(np.array([10, 20, 30], np.int64))],
              names=["k", "v"])
    out, valid, overflow = groupby_aggregate_capped(
        t, ["k"], [("v", "sum")], key_cap=64)
    assert not bool(overflow)
    v = np.asarray(valid)
    assert v.sum() == 2
    assert np.asarray(out.columns[0].data)[v].tolist() == [1, 3]
    assert np.asarray(out.columns[1].data)[v].tolist() == [20, 40]
    # retry-bigger converges even past n
    n = 10
    t2 = Table([Column.from_numpy(np.arange(n, dtype=np.int32)),
                Column.from_numpy(np.ones(n, np.int64))], names=["k", "v"])
    _, _, ov_small = groupby_aggregate_capped(t2, ["k"], [("v", "sum")],
                                              key_cap=8)
    assert bool(ov_small)
    out2, valid2, ov_big = groupby_aggregate_capped(t2, ["k"], [("v", "sum")],
                                                    key_cap=32)
    assert not bool(ov_big) and int(np.asarray(valid2).sum()) == n
    # empty table
    t0 = Table([Column.from_numpy(np.zeros(0, np.int32)),
                Column.from_numpy(np.zeros(0, np.int64))], names=["k", "v"])
    out0, valid0, ov0 = groupby_aggregate_capped(t0, ["k"], [("v", "sum")],
                                                 key_cap=4)
    assert not bool(ov0) and not np.asarray(valid0).any()
    assert out0.columns[0].length == 4


def test_inner_join_capped_matches_eager_under_jit():
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import inner_join_capped
    rng = np.random.default_rng(23)
    nl, nr = 4000, 600
    lk = col(rng.integers(0, 500, nl).astype(np.int64))
    rk = col(rng.integers(0, 500, nr).astype(np.int64))
    ref_l, ref_r = inner_join([lk], [rk])
    ref = sorted(zip(np.asarray(ref_l.data).tolist(),
                     np.asarray(ref_r.data).tolist()))

    @jax.jit
    def run(l, r):
        return inner_join_capped([l], [r], row_cap=nl * 4)

    lmap, rmap, valid, overflow = run(lk, rk)
    assert not bool(overflow)
    v = np.asarray(valid)
    got = sorted(zip(np.asarray(lmap)[v].tolist(),
                     np.asarray(rmap)[v].tolist()))
    assert got == ref
    # alive masks exclude rows from matching entirely
    lalive = jnp.asarray(np.asarray(lk.data) % 2 == 0)
    ralive = jnp.asarray(np.asarray(rk.data) % 3 == 0)
    lmap2, rmap2, valid2, ovf2 = inner_join_capped(
        [lk], [rk], row_cap=nl * 4, lalive=lalive, ralive=ralive)
    v2 = np.asarray(valid2)
    la, ra = np.asarray(lalive), np.asarray(ralive)
    ref2 = sorted((l, r) for l, r in ref if la[l] and ra[r])
    got2 = sorted(zip(np.asarray(lmap2)[v2].tolist(),
                      np.asarray(rmap2)[v2].tolist()))
    assert got2 == ref2
    # too-small cap flags overflow (SplitAndRetry contract)
    *_, ovf3 = inner_join_capped([lk], [rk], row_cap=16)
    assert bool(ovf3)


def test_semi_join_mask_matches_eager():
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import semi_join_mask
    rng = np.random.default_rng(29)
    nl, nr = 3000, 400
    lk = col(rng.integers(0, 900, nl).astype(np.int64))
    rk = col(rng.integers(0, 900, nr).astype(np.int64))
    keep = left_semi_join([lk], [rk])
    want = np.zeros(nl, bool)
    want[np.asarray(keep.data)] = True
    mask = jax.jit(lambda l, r: semi_join_mask([l], [r]))(lk, rk)
    np.testing.assert_array_equal(np.asarray(mask), want)
    # ralive: dead right rows can't witness a match
    ralive = jnp.asarray(np.asarray(rk.data) % 2 == 0)
    mask2 = semi_join_mask([lk], [rk], ralive=ralive)
    rset = set(np.asarray(rk.data)[np.asarray(ralive)].tolist())
    want2 = np.asarray([int(k) in rset for k in np.asarray(lk.data)])
    np.testing.assert_array_equal(np.asarray(mask2), want2)


def test_groupby_capped_alive_excludes_dead_rows():
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import groupby_aggregate_capped
    rng = np.random.default_rng(31)
    n = 5000
    k = rng.integers(0, 40, n).astype(np.int32)
    v = rng.integers(-100, 100, n).astype(np.int64)
    alive = rng.random(n) < 0.7
    t = Table([col(k), col(v)], names=["k", "v"])
    # oracle: groupby over only the alive rows
    ref = (pd.DataFrame({"k": k[alive], "v": v[alive]})
           .groupby("k", as_index=False)
           .agg(s=("v", "sum"), c=("v", "count"), mn=("v", "min"))
           .sort_values("k"))

    @jax.jit
    def run(tb, a):
        out, valid, overflow = groupby_aggregate_capped(
            tb, ["k"], [("v", "sum"), ("v", "count"), ("v", "min")],
            key_cap=64, alive=a)
        return [c.data for c in out.columns], valid, overflow

    cols, valid, overflow = run(t, jnp.asarray(alive))
    assert not bool(overflow)
    m = np.asarray(valid)
    assert m.sum() == len(ref)
    np.testing.assert_array_equal(np.asarray(cols[0])[m], ref.k.values)
    np.testing.assert_array_equal(np.asarray(cols[1])[m], ref.s.values)
    np.testing.assert_array_equal(np.asarray(cols[2])[m], ref.c.values)
    np.testing.assert_array_equal(np.asarray(cols[3])[m], ref.mn.values)
    # a group whose rows are ALL dead must not appear: kill one key entirely
    alive2 = alive & (k != int(k[0]))
    cols2, valid2, _ = run(t, jnp.asarray(alive2))
    m2 = np.asarray(valid2)
    assert int(k[0]) not in np.asarray(cols2[0])[m2].tolist()


def test_sort_table_alive_sinks_dead_rows():
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import sort_table_capped
    rng = np.random.default_rng(37)
    n = 1000
    k = rng.integers(0, 50, n).astype(np.int64)
    p = rng.integers(0, 10**6, n).astype(np.int64)
    alive = rng.random(n) < 0.5
    t = Table([col(k), col(p)], names=["k", "p"])

    @jax.jit
    def run(tb, a):
        out, sa = sort_table_capped(tb, key_names=["k"], ascending=[False],
                                    alive=a)
        return [c.data for c in out.columns], sa

    cols, sa = run(t, jnp.asarray(alive))
    sa = np.asarray(sa)
    live = int(alive.sum())
    # live rows form a prefix, sorted desc; dead rows all sink behind
    assert sa[:live].all() and not sa[live:].any()
    got_k = np.asarray(cols[0])[:live]
    np.testing.assert_array_equal(got_k, np.sort(k[alive])[::-1])


def test_inner_join_capped_edges_and_string_keys():
    import jax
    from spark_rapids_tpu.ops import inner_join_capped, semi_join_mask
    # empty right side: no matches, no overflow, static shapes hold
    lk = col(np.array([1, 2, 3], np.int64))
    empty = col(np.zeros(0, np.int64))
    _, _, v, o = inner_join_capped([lk], [empty], row_cap=8)
    assert not np.asarray(v).any() and not bool(o)
    # empty LEFT side under a nonzero cap (regression: _expand used to
    # broadcast (cap,) against (0,))
    _, _, v, o = jax.jit(
        lambda l, r: inner_join_capped([l], [r], row_cap=8))(empty, lk)
    assert not np.asarray(v).any() and not bool(o)
    # string keys ride the same machinery; nulls never match
    ls = scol(["a", "bb", "a", None, "ccc"])
    rs = scol(["a", "ccc", "zz"])
    lm, rm, v, o = inner_join_capped([ls], [rs], row_cap=16)
    m = np.asarray(v)
    assert sorted(zip(np.asarray(lm)[m].tolist(),
                      np.asarray(rm)[m].tolist())) == \
        [(0, 0), (2, 0), (4, 1)]
    assert np.asarray(semi_join_mask([ls], [rs])).tolist() == \
        [True, False, True, False, True]


def test_left_join_capped_matches_eager():
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import left_join_capped
    rng = np.random.default_rng(43)
    nl, nr = 2000, 300
    lk = col(rng.integers(0, 400, nl).astype(np.int64),
             nulls=rng.random(nl) < 0.1)
    rk = col(rng.integers(0, 400, nr).astype(np.int64))
    ref_l, ref_r = left_join([lk], [rk])
    rl, rr = np.asarray(ref_l.data), np.asarray(ref_r.data)
    ref = sorted(zip(rl.tolist(),
                     [int(x) if x >= 0 else None for x in rr]))

    lmap, rmap, rvalid, valid, overflow = jax.jit(
        lambda l, r: left_join_capped([l], [r], row_cap=nl * 4))(lk, rk)
    assert not bool(overflow)
    m = np.asarray(valid)
    rv = np.asarray(rvalid)[m]
    got = sorted(zip(np.asarray(lmap)[m].tolist(),
                     [int(x) if ok else None
                      for x, ok in zip(np.asarray(rmap)[m], rv)]))
    assert got == ref
    # lalive: excluded left rows emit NOTHING (vs unmatched rows, which
    # emit null-extended)
    lalive = jnp.asarray(np.asarray(lk.data) % 2 == 0)
    lmap2, rmap2, rvalid2, valid2, ovf2 = left_join_capped(
        [lk], [rk], row_cap=nl * 4, lalive=lalive)
    assert not bool(ovf2)
    m2 = np.asarray(valid2)
    la = np.asarray(lalive)
    want2 = sorted((l, r) for l, r in ref if la[l])
    got2 = sorted(zip(np.asarray(lmap2)[m2].tolist(),
                      [int(x) if ok else None
                       for x, ok in zip(np.asarray(rmap2)[m2],
                                        np.asarray(rvalid2)[m2])]))
    assert got2 == want2
    # too-small cap flags
    *_, ovf3 = left_join_capped([lk], [rk], row_cap=8)
    assert bool(ovf3)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_capped_tier_fuzz_matches_eager(seed):
    """Randomized parity: capped inner/left/semi/groupby against their
    eager forms over random shapes, mixed dtypes (int64/string keys),
    nulls, and random caps — the fuzz-tier pattern of the reference's
    monte-carlo harness applied to the jit tier."""
    from spark_rapids_tpu.ops import (groupby_aggregate_capped,
                                      inner_join_capped, left_join_capped,
                                      semi_join_mask)
    import jax.numpy as jnp
    rng = np.random.default_rng(100 + seed)
    nl = int(rng.integers(1, 900))
    nr = int(rng.integers(1, 300))
    nk = int(rng.integers(1, 60))
    use_strings = bool(rng.integers(0, 2))
    if use_strings:
        vocab = [f"k{i}" for i in range(nk)] + [None]
        lk = scol([vocab[i] for i in rng.integers(0, len(vocab), nl)])
        rk = scol([vocab[i] for i in rng.integers(0, len(vocab), nr)])
    else:
        lk = col(rng.integers(0, nk, nl).astype(np.int64),
                 nulls=rng.random(nl) < 0.15)
        rk = col(rng.integers(0, nk, nr).astype(np.int64),
                 nulls=rng.random(nr) < 0.15)

    # inner
    el, er = inner_join([lk], [rk])
    cap = max(int(el.length * 2), 16)
    lm, rm, v, o = inner_join_capped([lk], [rk], row_cap=cap)
    assert not bool(o)
    m = np.asarray(v)
    assert sorted(zip(np.asarray(lm)[m].tolist(),
                      np.asarray(rm)[m].tolist())) == \
        sorted(zip(np.asarray(el.data).tolist(),
                   np.asarray(er.data).tolist()))
    # left
    el2, er2 = left_join([lk], [rk])
    cap2 = max(int(el2.length * 2), 16)
    lm2, rm2, rv2, v2, o2 = left_join_capped([lk], [rk], row_cap=cap2)
    assert not bool(o2)
    m2 = np.asarray(v2)
    got = sorted(zip(np.asarray(lm2)[m2].tolist(),
                     [int(x) if ok else None for x, ok in
                      zip(np.asarray(rm2)[m2], np.asarray(rv2)[m2])]))
    want = sorted(zip(np.asarray(el2.data).tolist(),
                      [int(x) if x >= 0 else None
                       for x in np.asarray(er2.data)]))
    assert got == want
    # semi mask
    keep = left_semi_join([lk], [rk])
    wantm = np.zeros(nl, bool)
    wantm[np.asarray(keep.data)] = True
    np.testing.assert_array_equal(
        np.asarray(semi_join_mask([lk], [rk])), wantm)
    # groupby with random alive mask (int64 values)
    vals = col(rng.integers(-1000, 1000, nl).astype(np.int64))
    alive = rng.random(nl) < 0.8
    t = Table([lk, vals], names=["k", "v"])
    kc = max(nk + 2, 8)
    out, gvalid, govf = groupby_aggregate_capped(
        t, ["k"], [("v", "sum"), ("v", "count")], key_cap=kc,
        alive=jnp.asarray(alive))
    assert not bool(govf)
    from spark_rapids_tpu.ops import apply_boolean_mask
    eager = groupby_aggregate(apply_boolean_mask(t, jnp.asarray(alive)),
                              ["k"], [("v", "sum"), ("v", "count")])
    gm = np.asarray(gvalid)
    assert gm.sum() == eager.num_rows
    np.testing.assert_array_equal(
        np.asarray(out.columns[1].data)[gm],
        np.asarray(eager.columns[1].data))
    np.testing.assert_array_equal(
        np.asarray(out.columns[2].data)[gm],
        np.asarray(eager.columns[2].data))


def test_full_join_matches_multiset_oracle():
    from spark_rapids_tpu.ops import full_join, take
    rng = np.random.default_rng(47)
    nl, nr = 800, 300
    lkv = rng.integers(0, 250, nl).astype(np.int64)
    rkv = rng.integers(0, 250, nr).astype(np.int64)
    lnull = rng.random(nl) < 0.1
    lk = col(lkv, nulls=lnull)
    rk = col(rkv)
    lmap, rmap = full_join([lk], [rk])
    lkey = take(lk, lmap.data).to_pylist()
    rkey = take(rk, rmap.data).to_pylist()

    # multiset oracle with Spark/cudf semantics: null keys never match
    # (each null-keyed left row emits unmatched; a pandas outer merge would
    # wrongly match null==null)
    import collections
    lcnt = collections.Counter(int(v) for v, b in zip(lkv, lnull) if not b)
    rcnt = collections.Counter(int(v) for v in rkv)
    want = []
    for k in set(lcnt) | set(rcnt):
        if lcnt[k] and rcnt[k]:
            want += [(k, k)] * (lcnt[k] * rcnt[k])
        elif lcnt[k]:
            want += [(k, None)] * lcnt[k]
        else:
            want += [(None, k)] * rcnt[k]
    want += [(None, None)] * int(lnull.sum())   # null left keys: unmatched
    want = sorted(want, key=lambda t: (t[0] is None, t[0] or 0,
                                       t[1] is None, t[1] or 0))
    # got pairs are (left key, right key); unmatched sides are None
    got_pairs = sorted(zip(lkey, rkey),
                       key=lambda t: (t[0] is None, t[0] or 0,
                                      t[1] is None, t[1] or 0))
    assert got_pairs == want


def _full_join_sides(rng, nl, nr, n_keys, nulls):
    def side(n, null):
        return [col(rng.integers(0, 12 if n_keys > 1 else 60, n)
                    .astype(np.int64),
                    nulls=(rng.random(n) < 0.2) if null else None)
                for _ in range(n_keys)]
    return side(nl, nulls in ("left", "both")), \
        side(nr, nulls in ("right", "both"))


@pytest.mark.parametrize("null_equal", [False, True])
@pytest.mark.parametrize("nulls", ["none", "left", "right", "both"])
@pytest.mark.parametrize("shape", [(1, 90, 70), (2, 90, 70), (2, 0, 40),
                                   (2, 40, 0), (1, 0, 0)],
                         ids=lambda s: "keys%d_%dx%d" % s)
def test_full_join_is_the_left_join_then_the_unmatched_right_rows(
        shape, nulls, null_equal):
    """`full_join_counted` reads both sides' answers off ONE union sort
    (`_full_join_kernel`, PR 43: no null-rank operand unless nulls match,
    the row number as the sort's last key); held, map for map and count
    for count, to what it ran before: `left_join` and then the swapped
    anti join's rows, over duplicates on both sides, one and two key
    columns, nulls on either side, `<=>`, and an empty side."""
    from spark_rapids_tpu.ops import (full_join_counted, left_anti_join,
                                      left_join_counted)
    n_keys, nl, nr = shape
    lk, rk = _full_join_sides(np.random.default_rng(nl + 7 * nr + n_keys),
                              nl, nr, n_keys, nulls)
    lmap, rmap, matched, unmatched, unmatched_right = full_join_counted(
        lk, rk, null_equal)
    wl, wr, wmatched, wunmatched = left_join_counted(lk, rk, null_equal)
    extra = np.asarray(left_anti_join(rk, lk, null_equal).data)
    assert (matched, unmatched, unmatched_right) \
        == (wmatched, wunmatched, len(extra))
    assert lmap.length == rmap.length == matched + unmatched + len(extra)
    np.testing.assert_array_equal(
        np.asarray(lmap.data),
        np.concatenate([np.asarray(wl.data), np.full(len(extra), -1)]))
    np.testing.assert_array_equal(
        np.asarray(rmap.data), np.concatenate([np.asarray(wr.data), extra]))
    if nl and nr:       # the case has rows of all three kinds
        assert matched and unmatched and unmatched_right


def test_capped_join_x64_guard():
    """The capped joins' int64 match-count overflow guard must not silently
    degrade to int32 when a host app flips jax_enable_x64 off (round-5
    ADVICE): they fail loudly at use instead."""
    import jax
    from spark_rapids_tpu.ops import inner_join_capped, left_join_capped
    l, r = col([1, 2, 3], np.int32), col([2, 3, 4], np.int32)
    jax.config.update("jax_enable_x64", False)
    try:
        with pytest.raises(RuntimeError, match="x64"):
            inner_join_capped([l], [r], row_cap=8)
        with pytest.raises(RuntimeError, match="x64"):
            left_join_capped([l], [r], row_cap=8)
    finally:
        jax.config.update("jax_enable_x64", True)
    # with the flag restored the op works
    lm, rm, valid, overflow = inner_join_capped([l], [r], row_cap=8)
    assert int(np.asarray(valid).sum()) == 2 and not bool(overflow)


# ---- the capped inner join's two tails (ops/join.py:_capped_inner_kernel) ----

def _general_tail(lk, rk, cap, lalive=None, ralive=None, null_equal=False):
    """`inner_join_capped` as it was before the many-to-one tail: the span
    kernel and the expansion through their public forms."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import join as J
    operands, lvalid, rvalid, nl = J._union_operands(
        [lk], [rk], null_equal, lalive, ralive)
    counts, lo, rorder = J.join_spans(operands, lvalid, rvalid, nl=nl)
    total = jnp.sum(counts.astype(jnp.int64))
    lmap, rmap = J.expand_spans(counts, lo, rorder, total=cap)
    valid = jnp.arange(cap, dtype=jnp.int32) < total
    rmap = jnp.where(valid, jnp.clip(rmap, 0, max(rk.length - 1, 0)), 0)
    return jnp.where(valid, lmap, 0), rmap, valid, total > cap


def _same_as_general(lk, rk, cap, unique, **kw):
    from spark_rapids_tpu.ops import inner_join_capped_tail
    got = inner_join_capped_tail([lk], [rk], cap, **kw)
    for name, g, w in zip(("lmap", "rmap", "valid", "overflow"), got,
                          _general_tail(lk, rk, cap, **kw)):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name
    assert bool(got[4]) is unique
    return got


@pytest.mark.parametrize("seed", range(6))
def test_capped_join_unique_build_side_takes_many_to_one_tail(seed):
    """(a) random keys, nulls on both sides, alive masks, caps below and
    above the match count, null_equal on the odd seeds: slot for slot the
    general tail's answer, and the flag reads unique."""
    import jax.numpy as jnp
    rng = np.random.default_rng(290 + seed)
    nl, nr = 300, 70
    rkeys = rng.permutation(120)[:nr].astype(np.int64)
    lkeys = rng.integers(0, 120, nl).astype(np.int64)
    lk = col(lkeys, nulls=rng.random(nl) < 0.15)
    rk = col(rkeys, nulls=rng.random(nr) < 0.03)   # 0-3 nulls: one null run
    kw = dict(lalive=jnp.asarray(rng.random(nl) > 0.2),
              ralive=jnp.asarray(rng.random(nr) > 0.2),
              null_equal=bool(seed % 2))
    if kw["null_equal"]:           # <=>: two null right rows share a key
        rk = col(rkeys, nulls=np.arange(nr) == 5)
    for cap in (40, 512):
        lm, rm, valid, ovf, _ = _same_as_general(lk, rk, cap, True, **kw)
    assert not bool(ovf) and 0 < int(np.asarray(valid).sum()) < nl


@pytest.mark.parametrize("where", ["first_rows", "last_rows", "far_apart"])
def test_capped_join_duplicate_build_key_takes_expansion(where):
    """(b) one duplicated build key: the flag reads expand, the answer is
    the general tail's (the duplicated key's left rows fan out)."""
    rng = np.random.default_rng(7)
    rkeys = np.arange(40, dtype=np.int64)
    i, j = {"first_rows": (0, 1), "last_rows": (38, 39),
            "far_apart": (3, 31)}[where]
    rkeys[j] = rkeys[i]
    lk = col(rng.integers(0, 40, 200).astype(np.int64))
    lm, rm, valid, ovf, _ = _same_as_general(lk, col(rkeys), 512, False)
    assert int(np.asarray(valid).sum()) > 200 * 0.9


@pytest.mark.parametrize("twin", ["dead", "null", "live_beyond_dead",
                                  "live_beyond_null"])
def test_capped_join_flag_counts_matchable_rows_only(twin):
    """(c) a duplicate whose twin is dead or null-keyed does not count:
    unique, and right; two live twins with a dead or null-keyed row of the
    same key between them: expand."""
    import jax.numpy as jnp
    rkeys = np.array([5, 9, 7, 7, 7, 2], np.int64)
    ralive = np.ones(6, bool)
    rnull = np.zeros(6, bool)
    if twin == "dead":
        ralive[[3, 4]] = False
    elif twin == "null":
        rnull[[3, 4]] = True
    elif twin == "live_beyond_dead":
        ralive[3] = False
    else:
        # a null-keyed row sorts into the null run, so the twin between is
        # dead and the null lies elsewhere: still two live 7s
        ralive[3], rnull[0] = False, True
    lk = col(np.array([7, 7, 1, 9, 7, 5], np.int64))
    rk = col(rkeys, nulls=rnull)
    lm, rm, valid, _, _ = _same_as_general(
        lk, rk, 16, twin in ("dead", "null"), ralive=jnp.asarray(ralive))
    v = np.asarray(valid)
    pairs = list(zip(np.asarray(lm)[v].tolist(), np.asarray(rm)[v].tolist()))
    sevens = [2] if twin in ("dead", "null") else [2, 4]
    want = sorted([(l, r) for l in (0, 1, 4) for r in sevens]
                  + [(3, 1)] + ([(5, 0)] if not rnull[0] else []))
    assert sorted(pairs) == want


@pytest.mark.parametrize("dup", [False, True])
def test_capped_join_overflow_on_both_tails_and_auto_retry(dup):
    """(d) more matches than the cap: overflow on either tail, the first
    `row_cap` pairs are still the general tail's, and auto_retry_overflow
    climbs to a cap that holds them."""
    from spark_rapids_tpu.ops import inner_join_capped_tail
    from spark_rapids_tpu.parallel.autoretry import auto_retry_overflow
    rng = np.random.default_rng(11)
    rkeys = np.arange(30, dtype=np.int64)
    if dup:
        rkeys[17] = rkeys[4]
    lkeys = rng.integers(0, 30, 500).astype(np.int64)
    lk, rk = col(lkeys), col(rkeys)
    *_, ovf, _ = _same_as_general(lk, rk, 64, not dup)
    assert bool(ovf)
    seen = []

    def attempt(row_cap):
        seen.append(row_cap)
        return inner_join_capped_tail([lk], [rk], row_cap)[:4]
    (lm, rm, valid, ovf), caps = auto_retry_overflow(attempt,
                                                     {"row_cap": 64})
    total = int(np.asarray(valid).sum())
    ladder = [64, 128, 256, 512, 1024]
    # the duplicate took key 17's row: its left rows lose their match,
    # key 4's left rows have two
    want = 500 + dup * int((lkeys == 4).sum() - (lkeys == 17).sum())
    assert not bool(ovf) and total == want > 512 * dup
    assert seen == ladder[:4 + dup] and caps == {"row_cap": seen[-1]}


@pytest.mark.parametrize("shape", ["empty_left", "empty_right", "both_empty",
                                   "cap_over_frame", "no_match"])
def test_capped_join_tail_edges(shape):
    """(e) empty sides, a cap larger than the whole union frame, no match."""
    nl, nr = {"empty_left": (0, 5), "empty_right": (5, 0),
              "both_empty": (0, 0), "cap_over_frame": (6, 4),
              "no_match": (6, 4)}[shape]
    lk = col(np.arange(nl, dtype=np.int64) % 3)
    rk = col(np.arange(nr, dtype=np.int64)
             + (100 if shape == "no_match" else 0))
    lm, rm, valid, ovf, _ = _same_as_general(lk, rk, 64, True)
    assert lm.shape == rm.shape == valid.shape == (64,) and not bool(ovf)
    assert int(np.asarray(valid).sum()) == (6 if shape == "cap_over_frame"
                                            else 0)


# ---- expand_spans: the general tail's expansion over its live rows ---------

EXPAND_NL = 3200            # live_chunk(3200) is 1024: three whole chunks of
#                             left rows and one that clamps back onto the end
EXPAND_PREFIX = {"empty": 0, "one_chunk": 1024, "mid_chunk": 1500,
                 "whole_frame": EXPAND_NL}


@pytest.mark.parametrize("room", ["under", "at", "over"])
@pytest.mark.parametrize("mode", ["inner", "outer", "alive"])
@pytest.mark.parametrize("fan_out", ["0", "1", "15", "mixed"])
@pytest.mark.parametrize("prefix", list(EXPAND_PREFIX))
def test_expand_spans_is_numpy_repeat_over_the_live_slots(prefix, fan_out,
                                                          mode, room):
    """`expand_spans` against `numpy.repeat`: the rows that match end at
    `prefix` (no row, a whole chunk of the left frame, inside a chunk, the
    frame's last row), each with `fan_out` matches; inner, outer, and
    outer under an alive mask that is no prefix (`eff`); the pairs sum to
    less than, exactly, and more than `total`, and the first `total` of
    them come back, slot for slot."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.gather import live_chunk
    from spark_rapids_tpu.ops.join import expand_spans
    assert live_chunk(EXPAND_NL) == 1024
    rng = np.random.default_rng(
        [list(EXPAND_PREFIX).index(prefix), len(fan_out), len(mode)])
    nl, matchable, last = EXPAND_NL, 4000, EXPAND_PREFIX[prefix]
    counts = np.zeros(nl, np.int32)
    hit = rng.random(last) < 0.5
    if last:
        hit[last - 1] = True            # the prefix ends where it says
    counts[:last][hit] = {"0": 0, "1": 1, "15": 15}.get(fan_out) \
        if fan_out != "mixed" else rng.integers(0, 16, int(hit.sum()))
    lo = rng.integers(0, matchable - 15, nl).astype(np.int32)
    rorder = np.concatenate([rng.permutation(matchable),
                             np.full(nl, nl + matchable)]).astype(np.int32)
    eff = counts
    if mode != "inner":
        eff = np.maximum(counts, 1)
    alive = None
    if mode == "alive":                 # dead rows emit nothing, wherever
        alive = rng.random(nl) < 0.6    # they lie
        eff = np.where(alive, eff, 0).astype(np.int32)
    pairs = int(eff.sum())
    total = {"under": pairs + 37, "at": pairs,
             "over": pairs - pairs // 3 - (pairs > 0)}[room]
    lsel, rmap = expand_spans(
        jnp.asarray(counts), jnp.asarray(lo), jnp.asarray(rorder),
        total=total, outer=mode != "inner",
        eff=None if alive is None else jnp.asarray(eff))
    assert lsel.shape == rmap.shape == (total,)
    want_l = np.repeat(np.arange(nl, dtype=np.int32), eff)
    k = np.arange(pairs) - np.repeat(np.cumsum(eff) - eff, eff)
    want_r = np.where(counts[want_l] > 0, rorder[lo[want_l] + k], -1)
    live = min(pairs, total)
    np.testing.assert_array_equal(np.asarray(lsel)[:live], want_l[:live])
    np.testing.assert_array_equal(np.asarray(rmap)[:live], want_r[:live])
    # a dead slot holds a row of the frame, never an index outside it
    assert live == total or (0 <= np.asarray(lsel)[live:].min()
                             and np.asarray(lsel)[live:].max() < nl)


# ---- take_live: a capped join's column gathers over its live prefix --------

LIVE_CAP = 2560             # live_chunk(2560) is 1024: two whole chunks and
#                             one that clamps back onto the frame's end
LIVE_TOTALS = {"none": 0, "one": 1, "chunk_less_one": 1023, "chunk": 1024,
               "chunk_and_one": 1025, "cap_less_one": 2559, "cap": 2560,
               "over_cap": 3000}


@pytest.mark.parametrize("total", list(LIVE_TOTALS))
@pytest.mark.parametrize("maps", ["unique", "expand", "pallas"])
def test_take_live_is_take_over_the_live_prefix(maps, total):
    """The gather maps of a capped inner join (both tails of
    `_capped_inner_kernel`, and the Pallas join in interpret mode) over an
    int64, a nullable, a decimal128 and a string column: `take_live` equals
    `take` on `[0, live)`, and every slot of a fixed-width column past the
    last touched chunk is zero and, in a nullable one, invalid (never row 0
    of the source)."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import inner_join_capped_tail, take_live
    from spark_rapids_tpu.ops.gather import live_chunk, live_slots
    from spark_rapids_tpu.ops.join_pallas import inner_join_capped_pallas
    want_total = LIVE_TOTALS[total]
    rng = np.random.default_rng(want_total)
    nl, nr, c = 3200, 50, live_chunk(LIVE_CAP)
    assert c == 1024
    rkeys = np.arange(nr, dtype=np.int64)
    twice = 0
    if maps == "expand":        # key 0 has two right rows: its left rows
        rkeys = np.append(rkeys, 0)             # match twice
        twice = 1 if want_total > 1 else 0
    lkeys = np.full(nl, 1000, np.int64)         # matches nothing
    hits = rng.permutation(nl)[:want_total - twice]
    lkeys[hits] = rng.integers(1, nr, hits.size)
    lkeys[hits[:twice]] = 0
    lk, rk = col(lkeys), col(rkeys)
    if maps == "pallas":
        lm, rm, valid, ovf = inner_join_capped_pallas(
            [lk], [rk], row_cap=LIVE_CAP, interpret=True)
    else:
        lm, rm, valid, ovf, unique = inner_join_capped_tail(
            [lk], [rk], LIVE_CAP)
        assert bool(unique) is (maps == "unique")
    live = int(np.asarray(valid).sum())
    assert live == min(want_total, LIVE_CAP)
    assert bool(ovf) is (want_total > LIVE_CAP)
    touched = live_slots(live, LIVE_CAP)
    assert touched == min(-(-live // c) * c, LIVE_CAP)

    def side(n, seed):
        r = np.random.default_rng(seed)
        return [col(r.integers(1, 1 << 40, n)),
                col(r.integers(1, 99, n), nulls=r.random(n) < 0.3),
                Column.from_pylist(
                    [None if v % 5 == 0 else int(v) << 70 | 1
                     for v in r.integers(1, 1 << 30, n)],
                    dtypes.decimal(38, 2)),
                scol([None if v % 7 == 0 else "s%d" % v
                      for v in r.integers(1, 99, n)])]
    for cols, idx in ((side(nl, 1), lm), (side(len(rkeys), 2), rm)):
        got = take_live(cols, idx, jnp.sum(valid))
        for g, src in zip(got, cols):
            assert g.length == LIVE_CAP and g.dtype == src.dtype
            assert g.to_pylist()[:live] == take(src, idx).to_pylist()[:live]
            if src.dtype.is_string:     # no plane to chunk: the plain take
                continue
            assert not np.asarray(g.data)[touched:].any()
            assert (g.validity is None) == (src.validity is None)
            if g.validity is not None:
                assert not np.asarray(g.validity)[touched:].any()


@pytest.mark.parametrize("fan_out", [True, False])
def test_capped_plan_stamps_each_join_with_its_tail(fan_out):
    """(f) a q72-shaped plan in the capped tier: the dimension joins read
    unique, the inventory join (several rows a key) expand, and the result
    counts both; with one inventory row a key every join is many-to-one."""
    from spark_rapids_tpu.plan import PlanBuilder, PlanExecutor, col as pcol
    rng = np.random.default_rng(72)
    n, items, per_item = 240, 12, 4 if fan_out else 1

    def table(**cols):
        return Table([col(np.asarray(v, np.int64)) for v in cols.values()],
                     names=list(cols))
    inputs = {
        "cs": table(item_sk=rng.integers(0, items, n),
                    hd_sk=rng.integers(0, 20, n), qty=rng.integers(1, 9, n)),
        "hd": table(hd_demo_sk=np.arange(20), potential=np.arange(20) % 4),
        "items": table(i_item_sk=np.arange(items), i_brand=np.arange(items)),
        "inv": table(inv_item_sk=np.repeat(np.arange(items), per_item),
                     inv_qty=rng.integers(0, 9, items * per_item))}
    b = PlanBuilder()
    cs = b.scan("cs", schema=["item_sk", "hd_sk", "qty"])
    hd = b.scan("hd", schema=["hd_demo_sk", "potential"]) \
        .filter(pcol("potential") < 3)
    plan = (cs.join(hd, "hd_sk", "hd_demo_sk")
              .join(b.scan("items", schema=["i_item_sk", "i_brand"]),
                    "item_sk", "i_item_sk")
              .join(b.scan("inv", schema=["inv_item_sk", "inv_qty"]),
                    "i_item_sk", "inv_item_sk")
              .filter(pcol("inv_qty") < pcol("qty"))
              .aggregate(["i_brand"], [("qty", "size", "cnt")])
              .sort(["i_brand"]).build())
    ref = PlanExecutor(mode="eager").execute(plan, inputs)
    # optimize=False: the authored sides stay (the build-side rule would
    # put the larger inventory table on the left)
    res = PlanExecutor(mode="capped", optimize=False).execute(plan, inputs)
    assert res.compact().to_pydict() == ref.table.to_pydict()
    tails = [m.kernel for m in res.metrics.values() if m.kind == "HashJoin"]
    last = "xla:hash_join/expand" if fan_out else "xla:hash_join/unique"
    assert tails == ["xla:hash_join/unique", "xla:hash_join/unique", last]
    assert (res.unique_joins, res.expand_joins) == \
        ((2, 1) if fan_out else (3, 0))
    assert "kernel: " + last in res.profile_text()
