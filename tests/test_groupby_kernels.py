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
