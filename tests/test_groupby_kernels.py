"""The two groupby kernel designs (scan vs scatter/segment — see
ops/aggregate.py) must be interchangeable: same results over every agg op,
null layout, and the capped/alive contract. The suite's CPU backend runs
the scatter kernel by default (backend dispatch), so this file pins each
kernel explicitly and A/Bs them on the same data."""
import numpy as np
import pytest

import jax.numpy as jnp

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, Table, dtypes
from spark_rapids_tpu.ops import groupby_aggregate, groupby_aggregate_capped
from spark_rapids_tpu.ops.aggregate import _use_scan_kernel


@pytest.fixture(params=["scan", "scatter"])
def kernel(request, monkeypatch):
    monkeypatch.setenv("SPARK_RAPIDS_TPU_GROUPBY_KERNEL", request.param)
    return request.param


def _table(n=5000, seed=0, with_nulls=True):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 60, n).astype(np.int64)
    ints = rng.integers(-1000, 1000, n).astype(np.int64)
    floats = rng.standard_normal(n)
    floats[rng.random(n) < 0.02] = np.nan
    valid = rng.random(n) > 0.15 if with_nulls else None
    cols = [Column.from_numpy(keys),
            Column.from_numpy(ints, validity=valid),
            Column.from_numpy(floats, validity=valid)]
    return Table(cols, names=["k", "i", "f"]), keys, ints, floats, valid


AGGS = [("i", "sum"), ("i", "count"), ("i", "min"), ("i", "max"),
        ("f", "sum"), ("f", "mean"), ("f", "min"), ("f", "max"),
        ("i", "size")]


def _ref(keys, ints, floats, valid):
    import pandas as pd
    df = pd.DataFrame({"k": keys,
                       "i": pd.array(ints).astype("Int64"),
                       "f": floats})
    if valid is not None:
        df.loc[~valid, "i"] = pd.NA
        df.loc[~valid, "f"] = np.nan
    return df


def test_kernels_agree_all_ops(monkeypatch):
    t, *_ = _table()
    results = {}
    for k in ("scan", "scatter"):
        monkeypatch.setenv("SPARK_RAPIDS_TPU_GROUPBY_KERNEL", k)
        out = groupby_aggregate(t, ["k"], AGGS)
        results[k] = [c.to_pylist() for c in out]
    a, b = results["scan"], results["scatter"]
    assert len(a) == len(b)
    for ca, cb in zip(a, b):
        assert len(ca) == len(cb)
        for va, vb in zip(ca, cb):
            if va is None or vb is None:
                assert va == vb
            elif isinstance(va, float):
                assert (np.isnan(va) and np.isnan(vb)) or \
                    va == pytest.approx(vb, rel=1e-12)
            else:
                assert va == vb


def test_scatter_kernel_matches_pandas(monkeypatch):
    """Direct oracle for the scatter kernel (the scan kernel's oracle
    coverage lives in test_relational.py)."""
    import pandas as pd
    monkeypatch.setenv("SPARK_RAPIDS_TPU_GROUPBY_KERNEL", "scatter")
    t, keys, ints, floats, valid = _table(seed=4)
    out = groupby_aggregate(t, ["k"], [("i", "sum"), ("i", "count"),
                                       ("f", "mean"), ("i", "max")])
    g = _ref(keys, ints, floats, valid).groupby("k")
    ref_sum = g["i"].sum(min_count=1)
    ref_cnt = g["i"].count()
    ref_max = g["i"].max()
    got_k = out[0].to_pylist()
    assert got_k == sorted(set(keys.tolist()))
    ok = valid if valid is not None else np.ones(len(keys), bool)
    for gk, s, c, m, mx in zip(got_k, out[1].to_pylist(),
                               out[2].to_pylist(), out[3].to_pylist(),
                               out[4].to_pylist()):
        assert c == int(ref_cnt[gk])
        assert s == (None if pd.isna(ref_sum[gk]) else int(ref_sum[gk]))
        # mean skips NULLS but propagates NaN VALUES (Spark double
        # addition) — pandas mean skips both, so oracle it by hand
        vals = floats[(keys == gk) & ok]
        if len(vals) == 0:
            assert m is None
        elif np.isnan(vals.sum()):
            assert np.isnan(m)
        else:
            assert m == pytest.approx(vals.sum() / len(vals), rel=1e-12)
        assert mx == (None if pd.isna(ref_max[gk]) else int(ref_max[gk]))


def test_capped_alive_contract_both_kernels(kernel):
    """The capped/alive padded-row contract holds on either kernel."""
    t, keys, ints, _, valid = _table(n=2000, seed=2)
    alive = jnp.asarray(np.arange(2000) % 4 != 0)
    out, gvalid, overflow = groupby_aggregate_capped(
        t, ["k"], [("i", "sum")], key_cap=128, alive=alive)
    assert not bool(overflow)
    m = np.asarray(gvalid)
    got = dict(zip(np.asarray(out["k"].data)[m].tolist(),
                   np.asarray(out["sum(i)"].data)[m].tolist()))
    a = np.asarray(alive)
    ref = {}
    for k in sorted(set(keys[a].tolist())):
        sel = a & (keys == k) & (valid if valid is not None else True)
        ref[k] = int(ints[sel].sum())
    assert set(got) == set(ref)
    for k in ref:
        sel = a & (keys == k) & (valid if valid is not None else True)
        if sel.any():
            assert got[k] == ref[k], k


def test_dispatch_default_is_scatter_on_cpu(monkeypatch):
    monkeypatch.delenv("SPARK_RAPIDS_TPU_GROUPBY_KERNEL", raising=False)
    import jax
    if jax.default_backend() == "cpu":
        assert not _use_scan_kernel()
    monkeypatch.setenv("SPARK_RAPIDS_TPU_GROUPBY_KERNEL", "scan")
    assert _use_scan_kernel()
    monkeypatch.setenv("SPARK_RAPIDS_TPU_GROUPBY_KERNEL", "bogus")
    with pytest.raises(ValueError, match="bogus"):
        _use_scan_kernel()


# ---- the scan kernel at a frame its scans take in two levels -------------------

def _decimal_frame(n=300_000, groups=100_000, seed=34):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, groups, n).astype(np.int64)
    # unscaled values on both sides of 2**32, so both planes carry
    cents = rng.integers(-2 ** 40, 2 ** 40, n).astype(np.int64)
    valid = rng.random(n) > 0.1
    alive = rng.random(n) > 0.2
    t = Table([Column.from_numpy(keys),
               Column(dtype=dtypes.decimal(15, 2), length=n,
                      data=jnp.asarray(cents), validity=jnp.asarray(valid))],
              names=["k", "d"])
    return t, keys, cents, valid, alive


def _decimal_sums(t, alive, key_cap):
    out, live, overflow = groupby_aggregate_capped(
        t, ["k"], [("d", "sum"), ("d", "count"), ("d", "size")],
        key_cap=key_cap, alive=None if alive is None else jnp.asarray(alive))
    assert not bool(overflow)
    m = np.asarray(live)
    limbs = np.asarray(out["sum(d)"].data)[m].astype(object)
    total = sum(limbs[:, j] << (32 * j) for j in range(4))
    total = np.where(total >= 1 << 127, total - (1 << 128), total)
    nulls = ~np.asarray(out["sum(d)"].null_mask)[m]
    return (np.asarray(out["k"].data)[m], total, nulls,
            np.asarray(out["count(d)"].data)[m],
            np.asarray(out["size(*)"].data)[m])


@pytest.mark.parametrize("has_alive", [False, True])
@pytest.mark.parametrize("planes", ["ride", "gathered"])
def test_scan_kernel_in_two_levels_equals_the_flat_scans(monkeypatch, planes,
                                                         has_alive):
    """300,000 rows (73 blocks of 4,096: `ops/scans.py:running` scans in
    two levels) into some 95,000 groups, a decimal(15,2) summed as its two
    32-bit planes with nulls and dead rows: the kernel's sums, counts and
    sizes equal those of the same kernel over flat `cumsum`s, and exact
    integer sums computed here. The planes ride the key sort or are
    gathered by its order (`RIDE_PAYLOADS`)."""
    from spark_rapids_tpu.ops import aggregate, scans
    monkeypatch.setenv("SPARK_RAPIDS_TPU_GROUPBY_KERNEL", "scan")
    if planes == "gathered":
        monkeypatch.setattr(aggregate, "RIDE_PAYLOADS", 0)
    aggregate._groupby_kernel.clear_cache()
    t, keys, cents, valid, alive = _decimal_frame()
    assert t.num_rows > 16 * scans.SCAN_BLOCK
    alive = alive if has_alive else None
    two = _decimal_sums(t, alive, key_cap=100_000)
    flat_scan = lambda x, op="sum": jnp.cumsum(x)
    monkeypatch.setattr(aggregate, "running", flat_scan)
    aggregate._groupby_kernel.clear_cache()
    flat = _decimal_sums(t, alive, key_cap=100_000)
    aggregate._groupby_kernel.clear_cache()
    for a, b in zip(two, flat):
        assert np.array_equal(a, b)
    rows = np.ones(len(keys), bool) if alive is None else alive
    order = np.argsort(keys[rows], kind="stable")
    k, v, ok = keys[rows][order], cents[rows][order], valid[rows][order]
    starts = np.flatnonzero(np.concatenate([[True], k[1:] != k[:-1]]))
    got_keys, total, nulls, counts, sizes = two
    assert 90_000 < len(starts) and np.array_equal(got_keys, k[starts])
    assert np.array_equal(sizes, np.diff(np.append(starts, len(k))))
    assert np.array_equal(counts, np.add.reduceat(ok.astype(np.int64), starts))
    want = np.add.reduceat(np.where(ok, v, 0), starts)
    assert np.array_equal(nulls, counts == 0)
    assert [int(x) for x in total[~nulls]] == want[~nulls].tolist()


# ---- the groups' keys riding the compaction sort (PR 48) ------------------------
# The `scan` kernel hands the groups' keys back as the key operands its
# compaction sort carried (`ride`), or as the groups' first rows for `take`
# (a key whose sort operands are not its data, a frame of at most
# `KEPT_FLOOR` rows, few slots under a cap). Every case holds the three to
# one another: riding, `take` on the same kernel, and the `scatter` kernel.

_PALETTES = {
    "int64": (dtypes.INT64, [-2 ** 63, -2 ** 40 - 3, -1, 0, 7, 2 ** 40 + 5,
                             2 ** 63 - 1]),
    "int32": (dtypes.INT32, [-2 ** 31, -5, 0, 9, 2 ** 31 - 1]),
    "date": (dtypes.DATE32, [-719162, 0, 10957, 19000]),
    "timestamp": (dtypes.TIMESTAMP_US, [-1, 0, 1_600_000_000_000_000,
                                        2 ** 52]),
    "decimal64": (dtypes.decimal(15, 2), [-10 ** 14, -1, 0, 12345,
                                          10 ** 14]),
    "bool": (dtypes.BOOL, [False, True]),
    "float64": (dtypes.FLOAT64, [-0.0, 0.0, float("nan"), -1.5,
                                 float("inf")]),
    "decimal128": (dtypes.decimal(25, 2), [-10 ** 22, 0, 5, 10 ** 22]),
    "string": (dtypes.STRING, ["", "a", "ab", "b"]),
}


def _key_column(kind, n, rng, nulls):
    dtype, palette = _PALETTES[kind]
    values = [palette[i] for i in rng.integers(0, len(palette), n)]
    col = Column.from_pylist(values, dtype)
    if not nulls:
        return col
    # the data under a null stays what was drawn: undefined by contract
    return Column(dtype=col.dtype, length=n, data=col.data,
                  offsets=col.offsets,
                  validity=jnp.asarray(rng.random(n) > 0.2))


def _key_frame(kinds, n=2000, seed=48, nulls=True):
    rng = np.random.default_rng(seed)
    ints = rng.integers(-1000, 1000, n).astype(np.int64)
    floats = rng.standard_normal(n)
    floats[rng.random(n) < 0.02] = np.nan
    valid = rng.random(n) > 0.15
    cols = [_key_column(k, n, rng, nulls) for k in kinds]
    cols += [Column.from_numpy(ints, validity=valid),
             Column.from_numpy(floats, validity=valid)]
    return Table(cols, names=[f"k{i}" for i in range(len(kinds))]
                 + ["i", "f"])


def _grouped(monkeypatch, how, t, keys, aggs, **capped):
    """The group-by by `how` (`ride`, `take`: the `scan` kernel with the
    choice forced; `scatter`) -> (its result, what it said of its keys).
    `KEPT_FLOOR` is lowered so that riding engages at a few rows."""
    from spark_rapids_tpu.ops import aggregate, gather
    with monkeypatch.context() as m:
        m.setenv("SPARK_RAPIDS_TPU_GROUPBY_KERNEL",
                 "scatter" if how == "scatter" else "scan")
        m.setattr(gather, "KEPT_FLOOR", 0)
        if how == "take":
            m.setattr(aggregate, "words_ride", lambda *a: False)
        with aggregate.group_keys.collect() as did:
            out = (groupby_aggregate_capped(t, keys, aggs, **capped)
                   if capped else groupby_aggregate(t, keys, aggs))
    return out, did[-1]


def _assert_same_tables(a, b, rows=None):
    assert a.names == b.names and a.num_rows == b.num_rows
    for name, ca, cb in zip(a.names, a.columns, b.columns):
        assert ca.dtype == cb.dtype, name
        assert (ca.validity is None) == (cb.validity is None), name
        va, vb = ca.to_pylist()[:rows], cb.to_pylist()[:rows]
        for x, y in zip(va, vb):
            if isinstance(x, float) and isinstance(y, float):
                assert (np.isnan(x) and np.isnan(y)) \
                    or x == pytest.approx(y, rel=1e-12), name
            else:
                assert x == y and type(x) is type(y), name


def _assert_three_ways(monkeypatch, t, keys, aggs, said="ride"):
    ride, did = _grouped(monkeypatch, "ride", t, keys, aggs)
    take, took = _grouped(monkeypatch, "take", t, keys, aggs)
    scatter, _ = _grouped(monkeypatch, "scatter", t, keys, aggs)
    assert did[0] == said and took[0] == "take"
    planes = sum(1 + (t[k].validity is not None) for k in keys)
    assert took[1] == planes * take.num_rows
    if said == "ride":
        assert did[1] == 0
    assert ride.ordered_by == take.ordered_by == tuple(keys)
    _assert_same_tables(ride, take)
    _assert_same_tables(ride, scatter)
    # a key that rode holds 0 under a null: a defined value
    for k in keys:
        if t[k].validity is not None and said == "ride":
            data = np.asarray(ride[k].data)
            assert not data[~np.asarray(ride[k].validity)].any()
    return ride


@pytest.mark.parametrize("nulls", [False, True], ids=["dense", "nulls"])
@pytest.mark.parametrize("kind", ["int64", "int32", "date", "timestamp",
                                  "decimal64", "bool"])
def test_a_key_that_rides_equals_take_and_scatter(monkeypatch, kind, nulls):
    t = _key_frame([kind], nulls=nulls)
    out = _assert_three_ways(monkeypatch, t, ["k0"],
                             [("i", "sum"), ("f", "min"), ("i", "size")])
    want = set(_PALETTES[kind][1]) | ({None} if nulls else set())
    got = out["k0"].to_pylist()
    assert set(got) == want and len(got) == len(want)
    if nulls:
        assert got[0] is None           # the group of NULL keys, first


@pytest.mark.parametrize("kinds", [("int64", "date"),
                                   ("bool", "int32", "decimal64")],
                         ids=["two", "three"])
def test_several_keys_ride_together(monkeypatch, kinds):
    t = _key_frame(list(kinds))
    keys = [f"k{i}" for i in range(len(kinds))]
    _assert_three_ways(monkeypatch, t, keys,
                       [("i", "max"), ("f", "sum"), ("i", "count")])


@pytest.mark.parametrize("kind", ["float64", "decimal128", "string"])
def test_a_key_that_cannot_ride_keeps_take_beside_one_that_does(
        monkeypatch, kind):
    """A float's, a DECIMAL128's and a string's sort operands are not
    their data: the key is gathered through the first rows, which ride on
    beside the integer key's words."""
    t = _key_frame(["int64", kind])
    ride = _assert_three_ways(monkeypatch, t, ["k0", "k1"],
                              [("i", "sum"), ("i", "size")],
                              said="ride+take")
    _, did = _grouped(monkeypatch, "ride", t, ["k0", "k1"], [("i", "sum")])
    assert did == ("ride+take", 2 * ride.num_rows)
    # alone, nothing rides
    _, did = _grouped(monkeypatch, "ride", t, ["k1"], [("i", "sum")])
    assert did[0] == "take"


@pytest.mark.parametrize("agg", [None] + AGGS,
                         ids=["distinct"] + [f"{o}_{c}" for c, o in AGGS])
def test_every_aggregate_under_the_not_stable_compaction_sort(monkeypatch,
                                                              agg):
    """The compaction sort's one key is the start's position and the sort
    not a stable one: the rows that start no group tie, with pads that are
    all alike (the scans' totals, 0.0 under a float sum and a mean, the
    identity under a min and a max), so every aggregate reads as before."""
    t = _key_frame(["int64", "int32"], n=3000, seed=7)
    _assert_three_ways(monkeypatch, t, ["k0", "k1"],
                       [] if agg is None else [agg])


@pytest.mark.parametrize("n,said", [(0, "take"), (1, "take"),
                                    ("floor", "take"), ("floor+1", "ride")])
def test_frames_at_or_under_the_floor_keep_take(monkeypatch, n, said):
    """`KEPT_FLOOR` as it stands: a riding word's fixed costs (code in
    HBM, compile time) do not follow the rows."""
    from spark_rapids_tpu.ops import aggregate, gather
    n = {"floor": gather.KEPT_FLOOR, "floor+1": gather.KEPT_FLOOR + 1}.get(
        n, n)
    t = _key_frame(["int64"], n=n)
    monkeypatch.setenv("SPARK_RAPIDS_TPU_GROUPBY_KERNEL", "scan")
    with aggregate.group_keys.collect() as did:
        out = groupby_aggregate(t, ["k0"], [("i", "sum"), ("i", "size")])
    assert did[-1][0] == said
    scatter, _ = _grouped(monkeypatch, "scatter", t, ["k0"],
                          [("i", "sum"), ("i", "size")])
    _assert_same_tables(out, scatter)
    assert sum(out["size(*)"].to_pylist()) == n


@pytest.mark.parametrize("n,words,slots,planes,rides", [
    (6_874_157, 5, 6_874_157, 4, True),     # q97.batch's store DISTINCT
    (59_998_501, 1, 59_998_501, 1, True),   # q18.batch
    (15_334_665, 1, 15_334_665, 1, True),   # q13.batch, the join's output
    (360_000, 3, 8_192, 2, False),          # q3.tasks' capped program
    (360_000, 3, 360_000, 2, True),         # the same frame, a cap of it
    (16_384, 1, 16_384, 1, False),          # KEPT_FLOOR
    (2_250_000, 0, 2_250_000, 1, True),     # one int32 key for the row's
])
def test_the_choice_is_arithmetic_over_rows_words_slots_and_planes(
        n, words, slots, planes, rides):
    from spark_rapids_tpu.ops.gather import words_ride
    assert words_ride(n, words, slots, planes) is rides


@pytest.mark.parametrize("has_alive", [False, True], ids=["whole", "alive"])
@pytest.mark.parametrize("cap,overflows", [(1500, False), (128, True)])
def test_capped_contract_with_riding_keys(monkeypatch, cap, overflows,
                                          has_alive):
    """`_cap`, `_alive` and the overflow flag as they were, with the keys
    riding: 2,000 rows, a cap of 1,500 slots (or of 128, under the groups:
    overflow), dead rows counted out."""
    rng = np.random.default_rng(3)
    n = 2000
    keys = rng.integers(0, 300 if overflows else 60, n).astype(np.int64)
    valid = rng.random(n) > 0.1
    t = Table([Column.from_numpy(keys, validity=valid),
               Column.from_numpy(rng.integers(-9, 9, n).astype(np.int64))],
              names=["k", "i"])
    alive = jnp.asarray(rng.random(n) > 0.25) if has_alive else None
    got = {}
    for how in ("ride", "take", "scatter"):
        (out, live, overflow), did = _grouped(
            monkeypatch, how, t, ["k"], [("i", "sum"), ("i", "size")],
            key_cap=cap, alive=alive)
        assert out.num_rows == cap and live.shape == (cap,)
        assert bool(overflow) is overflows
        if how != "scatter":
            assert did == (how, 0 if how == "ride" else 2 * cap)
        got[how] = (out, np.asarray(live))
    rows = int(got["ride"][1].sum())
    a = np.asarray(alive) if has_alive else np.ones(n, bool)
    groups = len(set(np.where(valid, keys, -1)[a].tolist()))
    assert rows == min(groups, cap)
    for how in ("take", "scatter"):
        assert np.array_equal(got["ride"][1], got[how][1])
        _assert_same_tables(got["ride"][0], got[how][0], rows=rows)


@pytest.mark.parametrize("ops", [("min",), ("max",), ("min", "max")],
                         ids=["min", "max", "both"])
def test_string_extremes_read_the_starts_beside_riding_keys(monkeypatch,
                                                            ops):
    """A string min / max is found by a second sort and read at the
    groups' starts: the one caller for which the kernel hands the starts
    back (`with_starts`); the integer key rides all the same."""
    t = _key_frame(["int64"], n=1500, seed=11)
    rng = np.random.default_rng(12)
    words = [None if rng.random() < 0.2 else "".join(
        rng.choice(list("abc"), rng.integers(0, 4))) for _ in range(1500)]
    t = Table(list(t.columns) + [Column.from_pylist(words, dtypes.STRING)],
              names=list(t.names) + ["s"])
    _assert_three_ways(monkeypatch, t, ["k0"],
                       [("s", op) for op in ops] + [("i", "sum")])
