"""Kernel registry (ops/registry.py, docs/kernels.md): selection mechanics,
the Pallas kernel parity matrix, and executor integration.

The parity suite runs every registered non-fallback kernel FORCED against
its XLA fallback (interpret mode on this CPU suite) across the supported
dtype x validity matrix, plus the decline/edge cases the registry contract
promises: all-dead rows, empty tables, 64-bit (f64-guard class) columns,
and unsupported signatures declining to the fallback WITHOUT erroring."""
import numpy as np
import numpy.testing as npt
import pytest

import jax.numpy as jnp

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, Table, dtypes
from spark_rapids_tpu.ops import (apply_boolean_mask, inner_join,
                                  inner_join_capped, slice_table, sort_table,
                                  sort_table_capped, take_table)
from spark_rapids_tpu.ops import join_pallas, select_pallas, topk_pallas
from spark_rapids_tpu.ops.registry import REGISTRY, Signature
from spark_rapids_tpu.plan import PlanBuilder, PlanExecutor, col, lit


def _assert_tables_equal(a: Table, b: Table):
    assert list(a.names) == list(b.names)
    assert a.num_rows == b.num_rows
    for ca, cb in zip(a.columns, b.columns):
        npt.assert_array_equal(np.asarray(ca.data), np.asarray(cb.data))
        va = None if ca.validity is None else np.asarray(ca.validity)
        vb = None if cb.validity is None else np.asarray(cb.validity)
        if va is None and vb is None:
            continue
        na = np.zeros(a.num_rows, bool) if va is None else ~va
        nb = np.zeros(b.num_rows, bool) if vb is None else ~vb
        npt.assert_array_equal(na, nb)


# ---- registry mechanics -----------------------------------------------------

def test_backend_ranking():
    # cpu backend prefers the cpu-registered kernel; any other backend
    # lands on the universal fallback
    assert REGISTRY.select("groupby", backend="cpu").name == "scatter"
    assert REGISTRY.select("groupby", backend="tpu").name == "scan"
    assert REGISTRY.select("row_conversion", backend="cpu").name == "concat"
    assert REGISTRY.select("row_conversion", backend="tpu").name == "word"
    # conditional kernels need a signature: blind selection declines
    ch = REGISTRY.select("topk", None, backend="tpu")
    assert ch.fallback and ("pallas", "no signature at call site") \
        in ch.declined


def test_override_forcing(monkeypatch):
    monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNELS", "groupby=scan")
    assert REGISTRY.select("groupby", backend="cpu").name == "scan"
    # the EXECUTED dispatch follows the registry, not a parallel env read —
    # the regression class where the knob is validated but ignored
    from spark_rapids_tpu.ops.aggregate import _use_scan_kernel
    from spark_rapids_tpu.ops.row_conversion import _use_word_kernel
    assert _use_scan_kernel()
    monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNELS", "row_conversion=word")
    assert _use_word_kernel()
    monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNELS", "row_conversion=concat")
    assert not _use_word_kernel()
    # legacy alias still works, explicit entry wins over it
    monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNELS", "")
    monkeypatch.setenv("SPARK_RAPIDS_TPU_GROUPBY_KERNEL", "scan")
    assert REGISTRY.select("groupby", backend="cpu").name == "scan"
    monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNELS", "groupby=scatter")
    assert REGISTRY.select("groupby", backend="cpu").name == "scatter"


def test_strict_typo_policy(monkeypatch):
    monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNELS", "groupby=scna")
    with pytest.raises(ValueError, match="unknown kernel"):
        REGISTRY.select("groupby")
    monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNELS", "gruopby=scan")
    with pytest.raises(ValueError, match="unknown kernel op"):
        REGISTRY.select("groupby")
    monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNELS", "groupby")
    with pytest.raises(ValueError, match="malformed"):
        REGISTRY.select("groupby")
    with pytest.raises(ValueError, match="unknown kernel op"):
        REGISTRY.select("no_such_op")


def test_forced_override_honors_pinned_backend(monkeypatch):
    # an EXPLICIT backend pin (the degraded tier passes "cpu" so nothing
    # lands on the quarantined device) outranks a forced override; without
    # a pin the force crosses the registration gate (interpret-mode runs)
    monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNELS", "topk=pallas")
    t = Table([Column.from_numpy(np.arange(10, dtype=np.int64))],
              names=["a"])
    sig = topk_pallas.make_signature(t, ["a"], [True], 3, "eager")
    assert REGISTRY.select("topk", sig).name == "pallas"
    pinned = REGISTRY.select("topk", sig, backend="cpu")
    assert pinned.fallback
    assert any("pinned backend" in why for _, why in pinned.declined)


def test_forced_unsupported_signature_declines(monkeypatch):
    # a FORCED kernel whose supports() rejects the signature falls back
    # cleanly — a signature is data, not a typo
    monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNELS", "topk=pallas")
    sig = Signature(columns=(("string", False),),
                    extras=(("limit", 5), ("tier", "eager")))
    ch = REGISTRY.select("topk", sig)
    assert ch.fallback and ch.name == "xla"
    assert ("pallas", "unsupported signature") in ch.declined


def test_summary_is_backend_floor():
    s = REGISTRY.summary(backend="cpu")
    assert s["groupby"] == "scatter"
    assert s["fused_select"] == "xla"     # pallas is tpu-only
    s = REGISTRY.summary(backend="tpu")
    # conditional kernels resolve per dispatch: summary shows the floor
    assert s["fused_select"] == "xla" and s["groupby"] == "scan"


# ---- fused_select parity matrix ---------------------------------------------

_FS_DTYPES = [np.int8, np.int16, np.int32, np.int64, np.float32,
              np.float64, np.bool_]


def _fs_table(n=700, seed=0, with_nulls=True):
    rng = np.random.default_rng(seed)
    cols, names = [], []
    for i, dt in enumerate(_FS_DTYPES):
        if dt is np.bool_:
            arr = rng.integers(0, 2, n).astype(bool)
        elif np.issubdtype(dt, np.floating):
            arr = rng.standard_normal(n).astype(dt)
            arr[rng.random(n) < 0.05] = np.nan
        else:
            info = np.iinfo(dt)
            arr = rng.integers(info.min, info.max, n, dtype=dt,
                               endpoint=True)
        valid = (rng.random(n) > 0.15) if (with_nulls and i % 2) else None
        cols.append(Column.from_numpy(arr, validity=valid))
        names.append(f"c_{np.dtype(dt).name}")
    cols.append(Column.from_numpy(rng.integers(0, 50, n).astype(np.int32)))
    names.append("sel")
    return Table(cols, names=names)


@pytest.mark.parametrize("with_nulls", [False, True])
def test_fused_select_dtype_matrix(with_nulls):
    t = _fs_table(with_nulls=with_nulls)
    pred = (col("sel") < 25) | (col("sel") > 48)
    needed = [n for n in t.names if n != "sel"]
    ref = apply_boolean_mask(t.select(needed), pred.evaluate(t))
    got = select_pallas.fused_select_compact(t, pred, needed,
                                             block_rows=256)
    _assert_tables_equal(ref, got)


def test_fused_select_predicate_shapes():
    t = _fs_table(with_nulls=True)
    preds = [
        col("sel") == 7,
        (col("sel") + 3) * 2 > 40,
        ~(col("sel") >= 10) & (col("c_bool") | (col("sel") != 3)),
        col("sel") - 60 < lit(-30),
    ]
    for pred in preds:
        ref = apply_boolean_mask(t.select(["c_int64"]), pred.evaluate(t))
        got = select_pallas.fused_select_compact(t, pred, ["c_int64"],
                                                 block_rows=256)
        _assert_tables_equal(ref, got)


def test_fused_select_literal_weak_typing_parity():
    # literals stay weak-typed in BOTH paths: i8 arithmetic with an int
    # literal wraps in int8 exactly like the fallback (the column dtype
    # wins promotion), and pure-literal subtrees decline
    rng = np.random.default_rng(12)
    n = 400
    t = Table([Column.from_numpy(
        rng.integers(-128, 127, n, dtype=np.int8, endpoint=True)),
        Column.from_numpy(np.arange(n, dtype=np.int64))],
        names=["b", "v"])
    pred = (col("b") + 100) > 50        # wraps in int8 near the top
    ref = apply_boolean_mask(t.select(["v"]), pred.evaluate(t))
    got = select_pallas.fused_select_compact(t, pred, ["v"],
                                             block_rows=256)
    _assert_tables_equal(ref, got)
    from spark_rapids_tpu.plan.expr import BinOp, Literal
    folded_away = BinOp(">", BinOp("+", Literal(2), Literal(3)),
                        Literal(4))
    sig = select_pallas.make_signature(t, folded_away, (("v", col("v")),),
                                       "eager")
    assert not select_pallas._supports(sig)


def test_fused_select_all_dead_and_empty():
    t = _fs_table()
    got = select_pallas.fused_select_compact(t, col("sel") > 10 ** 6,
                                             ["c_int32"], block_rows=256)
    assert got.num_rows == 0
    t0 = Table([Column.from_numpy(np.zeros(0, np.int32))], names=["a"])
    got = select_pallas.fused_select_compact(t0, col("a") > 0, ["a"],
                                             block_rows=256)
    assert got.num_rows == 0 and got["a"].dtype == dtypes.INT32


def test_fused_select_signature_declines():
    t = _fs_table()
    exprs = (("x", col("c_int32")),)
    # float / 64-bit predicate inputs: the f64-guard class
    for pred in (col("c_float64") > 0.0, col("c_int64") > 0):
        sig = select_pallas.make_signature(t, pred, exprs, "eager")
        assert not select_pallas._supports(sig)
        assert REGISTRY.select("fused_select", sig,
                               backend="tpu").fallback
    # capped tier has no compaction to fuse
    sig = select_pallas.make_signature(t, col("sel") > 0, exprs, "capped")
    assert not select_pallas._supports(sig)
    # scalar-aggregate predicates are not row-wise
    from spark_rapids_tpu.plan import scalar_max
    sig = select_pallas.make_signature(
        t, col("sel") > scalar_max(col("sel")), exprs, "eager")
    assert not select_pallas._supports(sig)
    # string projection declines (unsupported plane dtype)
    st = Table([Column.from_pylist([b"a", b"bb", b"ccc"], dtypes.STRING),
                Column.from_numpy(np.arange(3, dtype=np.int32))],
               names=["s", "k"])
    sig = select_pallas.make_signature(st, col("k") > 0, (("s", col("s")),),
                                       "eager")
    assert not select_pallas._supports(sig)


# ---- topk parity matrix -----------------------------------------------------

_TK_CASES = [
    (np.int64, True), (np.int64, False),
    (np.int32, True), (np.int16, False),
    (np.float32, True), (np.float64, False),
    (np.bool_, True),
]


@pytest.mark.parametrize("dt,asc", _TK_CASES)
@pytest.mark.parametrize("with_nulls", [False, True])
def test_topk_dtype_matrix(dt, asc, with_nulls):
    rng = np.random.default_rng(3)
    n, k = 900, 17
    if dt is np.bool_:
        arr = rng.integers(0, 2, n).astype(bool)
    elif np.issubdtype(dt, np.floating):
        arr = rng.standard_normal(n).astype(dt)
        arr[rng.random(n) < 0.05] = np.nan
    else:
        arr = rng.integers(np.iinfo(dt).min, np.iinfo(dt).max, n,
                           dtype=dt, endpoint=True)
    valid = (rng.random(n) > 0.2) if with_nulls else None
    t = Table([Column.from_numpy(arr, validity=valid),
               Column.from_numpy(rng.integers(0, 9, n).astype(np.int32))],
              names=["k", "pay"])
    ref = slice_table(sort_table(t, key_names=["k"], ascending=[asc]), 0, k)
    got = topk_pallas.topk_table(t, ["k"], [asc], k, block_rows=256)
    _assert_tables_equal(ref, got)


def test_topk_multikey_and_edges():
    rng = np.random.default_rng(4)
    n = 500
    t = Table([Column.from_numpy(rng.integers(0, 4, n).astype(np.int64),
                                 validity=rng.random(n) > 0.1),
               Column.from_numpy(rng.standard_normal(n).astype(np.float64))],
              names=["a", "b"])
    for asc in ([True, False], [False, True]):
        ref = slice_table(sort_table(t, key_names=["a", "b"],
                                     ascending=asc), 0, 11)
        got = topk_pallas.topk_table(t, ["a", "b"], asc, 11, block_rows=256)
        _assert_tables_equal(ref, got)
    # k > n clamps to the relation
    ref = sort_table(t, key_names=["a"], ascending=[True])
    got = topk_pallas.topk_table(t, ["a"], [True], n + 50, block_rows=256)
    _assert_tables_equal(ref, got)
    # empty table
    t0 = Table([Column.from_numpy(np.zeros(0, np.int64))], names=["a"])
    assert topk_pallas.topk_table(t0, ["a"], [True], 5).num_rows == 0


def test_topk_capped_alive_and_all_dead():
    rng = np.random.default_rng(5)
    n, k = 800, 9
    t = Table([Column.from_numpy(rng.integers(-99, 99, n).astype(np.int64)),
               Column.from_numpy(rng.integers(0, 7, n).astype(np.int32))],
              names=["k", "pay"])
    for alive_p in (0.6, 0.0):
        alive = jnp.asarray(rng.random(n) < alive_p)
        st, salive = sort_table_capped(t, key_names=["k"],
                                       ascending=[False], alive=alive)
        prefix = jnp.cumsum(salive.astype(jnp.int32))
        ref_alive = salive & (prefix <= k)
        ridx = jnp.asarray(np.nonzero(np.asarray(ref_alive))[0],
                           dtype=jnp.int32)
        ref = take_table(st, ridx, _has_negative=False)
        gt, ga = topk_pallas.topk_capped(t, ["k"], [False], k, alive,
                                         block_rows=256)
        gidx = jnp.asarray(np.nonzero(np.asarray(ga))[0], dtype=jnp.int32)
        _assert_tables_equal(ref, take_table(gt, gidx, _has_negative=False))


def test_topk_signature_declines():
    t = Table([Column.from_pylist([b"a", b"b"], dtypes.STRING)],
              names=["s"])
    sig = topk_pallas.make_signature(t, ["s"], [True], 5, "eager")
    assert not topk_pallas._supports(sig)
    t2 = Table([Column.from_numpy(np.arange(5, dtype=np.int64))],
               names=["a"])
    big = topk_pallas.make_signature(t2, ["a"], [True],
                                     topk_pallas.MAX_K + 1, "eager")
    assert not topk_pallas._supports(big)
    ok = topk_pallas.make_signature(t2, ["a"], [True], 5, "capped")
    assert topk_pallas._supports(ok)


# ---- hash_join parity matrix ------------------------------------------------

_HJ_DTYPES = [np.int64, np.int32, np.int16, np.bool_]


@pytest.mark.parametrize("dt", _HJ_DTYPES)
@pytest.mark.parametrize("with_nulls", [False, True])
def test_hash_join_dtype_matrix(dt, with_nulls):
    rng = np.random.default_rng(6)
    nl, nr = 1200, 250
    if dt is np.bool_:
        lk, rk = (rng.integers(0, 2, nl).astype(bool),
                  rng.integers(0, 2, nr).astype(bool))
    else:
        lk = rng.integers(0, 150, nl).astype(dt)
        rk = rng.integers(0, 150, nr).astype(dt)
    lv = (rng.random(nl) > 0.1) if with_nulls else None
    rv = (rng.random(nr) > 0.1) if with_nulls else None
    lc = [Column.from_numpy(lk, validity=lv)]
    rc = [Column.from_numpy(rk, validity=rv)]
    rl, rr = inner_join(lc, rc)
    gl, gr = join_pallas.inner_join_pallas(lc, rc)
    npt.assert_array_equal(np.asarray(rl.data), np.asarray(gl.data))
    npt.assert_array_equal(np.asarray(rr.data), np.asarray(gr.data))


def test_hash_join_multikey_and_capped():
    rng = np.random.default_rng(7)
    nl, nr = 900, 180
    lc = [Column.from_numpy(rng.integers(0, 40, nl).astype(np.int64)),
          Column.from_numpy(rng.integers(0, 3, nl).astype(np.int32),
                            validity=rng.random(nl) > 0.05)]
    rc = [Column.from_numpy(rng.integers(0, 40, nr).astype(np.int64)),
          Column.from_numpy(rng.integers(0, 3, nr).astype(np.int32))]
    rl, rr = inner_join(lc, rc)
    gl, gr = join_pallas.inner_join_pallas(lc, rc)
    npt.assert_array_equal(np.asarray(rl.data), np.asarray(gl.data))
    npt.assert_array_equal(np.asarray(rr.data), np.asarray(gr.data))
    lalive = jnp.asarray(rng.random(nl) > 0.4)
    ralive = jnp.asarray(rng.random(nr) > 0.4)
    for cap in (8192, 13):                  # roomy + overflowing
        ref = inner_join_capped(lc, rc, row_cap=cap, lalive=lalive,
                                ralive=ralive)
        got = join_pallas.inner_join_capped_pallas(
            lc, rc, row_cap=cap, lalive=lalive, ralive=ralive)
        for i, (a, b) in enumerate(zip(ref, got)):
            npt.assert_array_equal(np.asarray(a), np.asarray(b),
                                   err_msg=f"cap={cap} part {i}")


def test_hash_join_all_null_and_empty():
    rng = np.random.default_rng(8)
    lc = [Column.from_numpy(rng.integers(0, 5, 300).astype(np.int64),
                            validity=np.zeros(300, bool))]
    rc = [Column.from_numpy(rng.integers(0, 5, 50).astype(np.int64))]
    gl, gr = join_pallas.inner_join_pallas(lc, rc)
    assert gl.length == 0                    # null keys never match
    e = [Column.from_numpy(np.zeros(0, np.int64))]
    gl, gr = join_pallas.inner_join_pallas(e, e)
    assert gl.length == 0


def _hj_sides(layout, seed, nl=1100, nr=230):
    """Both sides' key columns for `layout`, one draw a seed: the same
    shapes, dtypes and validity planes whatever the seed."""
    rng = np.random.default_rng(seed)
    kinds = {"int32_key": [np.int32], "int64_key": [np.int64],
             "int32_and_int64_keys": [np.int32, np.int64],
             "int64_key_with_nulls": [np.int64]}[layout]
    nulls = layout.endswith("with_nulls")
    hi = 160 if len(kinds) == 1 else 14

    def side(n):
        return [Column.from_numpy(
            rng.integers(0, hi, n).astype(k),
            validity=(rng.random(n) > 0.1) if nulls else None)
            for k in kinds]
    return side(nl), side(nr)


@pytest.mark.parametrize("layout", ["int32_key", "int64_key",
                                    "int32_and_int64_keys",
                                    "int64_key_with_nulls"])
def test_eager_hash_join_is_two_cached_programs(layout):
    """`inner_join_pallas` runs as two jitted programs with the match
    count's read between them: the same shapes again lower nothing, other
    key values (another `total`) lower the emit stage alone, and every
    result is `ops.inner_join`'s pair for pair."""
    from spark_rapids_tpu.utils import tracing

    def joined(sides):
        with tracing.bracket("test.join") as b:
            gl, gr = join_pallas.inner_join_pallas(*sides)
        n, _ = b.lowered()
        names = list(tracing._lowered.names)[-n:] if n else []
        rl, rr = inner_join(*sides)
        npt.assert_array_equal(np.asarray(rl.data), np.asarray(gl.data))
        npt.assert_array_equal(np.asarray(rr.data), np.asarray(gr.data))
        return gl.length, names

    first, other = _hj_sides(layout, seed=40), _hj_sides(layout, seed=41)
    total, _ = joined(first)
    assert total > 0
    assert joined(first) == (total, [])
    other_total, names = joined(other)
    assert other_total not in (0, total)
    assert names == ["jit(_emit_matches)"]


def test_hash_join_signature_declines():
    f = [Column.from_numpy(np.zeros(4, np.float32))]
    i = [Column.from_numpy(np.zeros(4, np.int64))]
    assert not join_pallas._supports(
        join_pallas.make_signature(f, f, "inner", "eager"))
    assert not join_pallas._supports(
        join_pallas.make_signature(i, i, "left_semi", "eager"))
    big = [Column.from_numpy(np.zeros(join_pallas.MAX_BUILD + 1, np.int64))]
    assert not join_pallas._supports(
        join_pallas.make_signature(i, big, "inner", "eager"))
    assert join_pallas._supports(
        join_pallas.make_signature(big, i, "inner", "capped"))
    # an eager probe side of a fact table's size is the small-side path's
    # (ops/join.py); the capped entry has no row count to branch on
    fact = [Column.from_numpy(np.zeros(join_pallas.EAGER_MAX_PROBE,
                                       np.int64))]
    under = [Column.from_numpy(np.zeros(join_pallas.EAGER_MAX_PROBE - 1,
                                        np.int64))]
    assert not join_pallas._supports(
        join_pallas.make_signature(fact, i, "inner", "eager"))
    assert join_pallas._supports(
        join_pallas.make_signature(under, i, "inner", "eager"))
    assert join_pallas._supports(
        join_pallas.make_signature(fact, i, "inner", "capped"))
    from spark_rapids_tpu.ops import join_lookup
    assert join_lookup.lookup_side(fact, i, False) == "right"


# ---- executor integration ---------------------------------------------------

def _mini_plan():
    b = PlanBuilder()
    facts = b.scan("facts", schema=["k", "v"])
    dims = b.scan("dims", schema=["dk", "tag"]).filter(col("tag") > 2)
    j = facts.join(dims, left_on="k", right_on="dk")
    return (j.aggregate(["tag"], [("v", "sum", "s")])
             .sort(["s", "tag"], ascending=[False, True]).limit(3).build())


def _mini_inputs(n=400, seed=0):
    rng = np.random.default_rng(seed)
    facts = Table([Column.from_numpy(rng.integers(0, 30, n)
                                     .astype(np.int64)),
                   Column.from_numpy(rng.integers(0, 100, n)
                                     .astype(np.int64))],
                  names=["k", "v"])
    dims = Table([Column.from_numpy(np.arange(30, dtype=np.int64)),
                  Column.from_numpy(rng.integers(0, 6, 30)
                                    .astype(np.int64))],
                 names=["dk", "tag"])
    return {"facts": facts, "dims": dims}


def test_executor_stamps_kernels_and_renders():
    plan, inputs = _mini_plan(), _mini_inputs()
    res = PlanExecutor(mode="eager").execute(plan, inputs)
    stamped = {m.kind: m.kernel for m in res.metrics.values() if m.kernel}
    assert stamped.get("HashJoin") == "xla:hash_join"
    assert stamped.get("HashAggregate") == "scatter:groupby"
    assert stamped.get("TopK") == "xla:topk"    # Sort+Limit fused by rules
    assert "kernel: xla:hash_join" in res.profile_text()
    assert res.metrics[res.plan.root.label] is not None
    # explain carries the registry floor line
    txt = PlanExecutor(mode="eager").explain(plan, optimized=True,
                                             inputs=inputs)
    assert "kernels [" in txt


def test_forced_pallas_end_to_end_parity(monkeypatch):
    plan, inputs = _mini_plan(), _mini_inputs()
    ref = PlanExecutor(mode="eager").execute(plan, inputs)
    monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNELS",
                       "hash_join=pallas,topk=pallas,fused_select=pallas")
    got_e = PlanExecutor(mode="eager").execute(plan, inputs)
    assert ref.table.to_pydict() == got_e.table.to_pydict()
    stamped = {m.kind: m.kernel for m in got_e.metrics.values() if m.kernel}
    assert stamped.get("HashJoin") == "pallas:hash_join"
    assert stamped.get("TopK") == "pallas:topk"
    got_c = PlanExecutor(mode="capped").execute(plan, inputs)
    assert ref.table.to_pydict() == got_c.compact().to_pydict()
    stamped_c = {m.kind: m.kernel
                 for m in got_c.metrics.values() if m.kernel}
    assert stamped_c.get("TopK") == "pallas:topk"


def test_unsupported_signature_runs_fallback_without_error(monkeypatch):
    # string join keys with pallas FORCED: the signature declines at
    # lookup time and the plan still runs on the fallback
    monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNELS",
                       "hash_join=pallas,topk=pallas,fused_select=pallas")
    b = PlanBuilder()
    l = b.scan("l", schema=["s", "v"])
    r = b.scan("r", schema=["rs"])
    plan = l.join(r, left_on="s", right_on="rs").build()
    lt = Table([Column.from_pylist([b"a", b"b", b"a", b"c"], dtypes.STRING),
                Column.from_numpy(np.arange(4, dtype=np.int64))],
               names=["s", "v"])
    rt = Table([Column.from_pylist([b"a", b"c"], dtypes.STRING)],
               names=["rs"])
    res = PlanExecutor(mode="eager").execute(plan, {"l": lt, "r": rt})
    assert res.table.num_rows == 3
    join_m = next(m for m in res.metrics.values() if m.kind == "HashJoin")
    assert join_m.kernel == "xla:hash_join"


def test_capped_jit_cache_misses_on_knob_change(monkeypatch):
    plan, inputs = _mini_plan(), _mini_inputs()
    ex = PlanExecutor(mode="capped")
    r1 = ex.execute(plan, inputs)
    r2 = ex.execute(plan, inputs)
    assert r2.jit_cache_hits > 0
    monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNELS", "hash_join=pallas")
    r3 = ex.execute(plan, inputs)
    assert r3.jit_cache_hits == 0           # knob is part of the cache key
    assert r1.compact().to_pydict() == r3.compact().to_pydict()
    r4 = ex.execute(plan, inputs)
    assert r4.jit_cache_hits > 0            # same knob hits again


def test_fused_select_through_executor(monkeypatch):
    # a Filter+Project pair the optimizer fuses into FusedSelect with an
    # int32 predicate column — the shape the Pallas kernel accepts
    b = PlanBuilder()
    t = (b.scan("t", schema=["a", "b", "v"])
          .filter((col("a") > 10) & (col("b") != 0))
          .project([("v2", col("v")), ("a", col("a"))]))
    plan = t.build()
    rng = np.random.default_rng(11)
    n = 600
    tab = Table([Column.from_numpy(rng.integers(0, 20, n)
                                   .astype(np.int32)),
                 Column.from_numpy(rng.integers(-2, 2, n)
                                   .astype(np.int32)),
                 Column.from_numpy(rng.integers(-10**9, 10**9, n)
                                   .astype(np.int64),
                                   validity=rng.random(n) > 0.1)],
                names=["a", "b", "v"])
    ref = PlanExecutor(mode="eager").execute(plan, {"t": tab})
    monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNELS", "fused_select=pallas")
    got = PlanExecutor(mode="eager").execute(plan, {"t": tab})
    assert ref.table.to_pydict() == got.table.to_pydict()
    fs = [m for m in got.metrics.values() if m.kind == "FusedSelect"]
    assert fs and fs[0].kernel == "pallas:fused_select"
