"""Full-plan SPMD distributed tier (plan/distributed.py, docs/
distributed.md) on a SMALL simulated-CPU mesh — deliberately NOT `slow`:
a 2-device mesh keeps every SPMD program's trace/compile inside the timed
tier-1 budget (the jitted-primitive cache plus the repo's persistent
compilation cache make repeats near-free), so the distributed tier is
exercised on every verify run instead of nightly-only. The 8-device
whole-suite variants stay in the `slow`-marked modules.

Oracle everywhere: the single-device eager tier of the SAME plan."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, dtypes
from spark_rapids_tpu.columnar import Table
from spark_rapids_tpu.plan import (PlanBuilder, PlanExecutor,
                                   PlanValidationError, col)

NDEV = 2


def _mesh(n=NDEV):
    from spark_rapids_tpu.parallel import make_mesh
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} simulated devices")
    return make_mesh(n)


def _icol(a, dtype=None):
    a = np.asarray(a, np.int64)
    return Column(dtype=dtype or dtypes.INT64, length=len(a),
                  data=jnp.asarray(a))


def _fcol(a):
    a = np.asarray(a, np.float64)
    return Column(dtype=dtypes.FLOAT64, length=len(a), data=jnp.asarray(a))


def _tables(n=600, seed=0):
    rng = np.random.default_rng(seed)
    sales = Table([_icol(rng.integers(0, 40, n)),
                   _icol(rng.integers(-500, 500, n))], names=["k", "v"])
    dims = Table([_icol(np.arange(40)),
                  _icol(rng.integers(0, 3, 40))], names=["dk", "grp"])
    return sales, dims


def _parity(plan, inputs, mesh, **ex_kw):
    ref = PlanExecutor().execute(plan, inputs)
    res = PlanExecutor(mesh=mesh, **ex_kw).execute(plan, inputs)
    assert not res.degraded, "distributed run fell to the CPU tier"
    assert res.table.to_pydict() == ref.table.to_pydict()
    return res


# ---- joins ------------------------------------------------------------------

def test_shuffle_join_agg_sort_parity():
    """Large-large inner join: exchange_planning hash-partitions BOTH
    sides (visible in the report), the aggregate's exchange rides the
    fused two-phase groupby, and the result gathers once at the sink."""
    mesh = _mesh()
    sales, dims = _tables()
    big_dims = Table([c for c in dims.columns], names=dims.names)
    b = PlanBuilder()
    s = b.scan("sales", schema=["k", "v"])
    d = b.scan("dims", schema=["dk", "grp"])
    plan = (s.join(d, left_on="k", right_on="dk")
             .aggregate(["grp", "k"], [("v", "sum", "t"), ("v", "max", "mx"),
                                       ("v", "size", "n")])
             .sort(["k"]).build())
    import os
    os.environ["SPARK_RAPIDS_TPU_BROADCAST_ROWS"] = "1"  # force shuffle
    try:
        res = _parity(plan, {"sales": sales, "dims": big_dims}, mesh)
    finally:
        del os.environ["SPARK_RAPIDS_TPU_BROADCAST_ROWS"]
    # both join sides shuffle; the aggregate's exchange is ELIDED — the
    # join output is already partitioned by k, a subset of the group keys
    assert res.optimizer["exchanges"]["hash"] == 2
    assert res.optimizer["exchanges_elided"] >= 1
    assert res.optimizer["exchanges"]["broadcast"] == 0
    assert res.optimizer["exchanges"]["gather"] == 1
    gathers = [m for m in res.metrics.values() if m.exchange_how == "gather"]
    assert len(gathers) == 1                         # single sink gather
    moved = sum(m.exchange_bytes for m in res.metrics.values())
    assert moved > 0
    assert any(m.n_peers == NDEV for m in res.metrics.values())


def test_broadcast_join_parity_and_selection():
    """est_rows-driven broadcast: the small build side replicates (no
    shuffle of the probe side), visible in explain() and the metrics."""
    mesh = _mesh()
    sales, dims = _tables()
    b = PlanBuilder()
    s = b.scan("sales", schema=["k", "v"])
    d = b.scan("dims", schema=["dk", "grp"]).filter(col("grp") == 1)
    plan = (s.join(d, left_on="k", right_on="dk")
             .aggregate(["grp"], [("v", "sum", "t")]).build())
    inputs = {"sales": sales, "dims": dims}
    res = _parity(plan, inputs, mesh)
    assert res.optimizer["exchanges"]["broadcast"] == 1
    bc = [m for m in res.metrics.values() if m.exchange_how == "broadcast"]
    assert len(bc) == 1 and bc[0].exchange_bytes > 0
    ex = PlanExecutor(mesh=mesh)
    text = ex.explain(plan, optimized=True, inputs=inputs)
    assert "broadcast" in text and "sharding" in text


def test_semi_and_anti_join_parity():
    mesh = _mesh()
    sales, dims = _tables(seed=3)
    for how in ("left_semi", "left_anti"):
        b = PlanBuilder()
        s = b.scan("sales", schema=["k", "v"])
        d = b.scan("dims", schema=["dk", "grp"]).filter(col("grp") == 1)
        plan = (s.join(d, left_on="k", right_on="dk", how=how)
                 .aggregate(["k"], [("v", "sum", "t"), ("v", "count", "c")])
                 .sort(["k"]).build())
        _parity(plan, {"sales": sales, "dims": dims}, mesh)


def test_multi_key_join_and_agg_elision():
    """Composite-key shuffle join; the aggregate above groups by a
    SUPERSET of the join keys, so its exchange is ELIDED and the groupby
    merges shard-locally (q72's shape)."""
    mesh = _mesh()
    rng = np.random.default_rng(7)
    n = 400
    left = Table([_icol(rng.integers(0, 8, n)), _icol(rng.integers(0, 6, n)),
                  _icol(rng.integers(0, 100, n))], names=["a", "b", "v"])
    pairs = [(a, b) for a in range(8) for b in range(6)]
    right = Table([_icol([p[0] for p in pairs]),
                   _icol([p[1] for p in pairs]),
                   _icol(range(len(pairs)))], names=["ra", "rb", "w"])
    b = PlanBuilder()
    l = b.scan("l", schema=["a", "b", "v"])
    r = b.scan("r", schema=["ra", "rb", "w"])
    plan = (l.join(r, ["a", "b"], ["ra", "rb"])
             .aggregate(["a", "b", "w"], [("v", "sum", "t")])
             .sort(["a", "b", "w"]).build())
    import os
    os.environ["SPARK_RAPIDS_TPU_BROADCAST_ROWS"] = "1"
    try:
        res = _parity(plan, {"l": left, "r": right}, mesh)
    finally:
        del os.environ["SPARK_RAPIDS_TPU_BROADCAST_ROWS"]
    assert res.optimizer["exchanges_elided"] >= 1


# ---- sort / topk ------------------------------------------------------------

def test_distributed_sort_and_topk_parity():
    mesh = _mesh()
    rng = np.random.default_rng(11)
    n = 500
    # unique primary keys: global order is total, so parity is row-exact
    t = Table([_icol(rng.permutation(n)), _icol(rng.integers(0, 99, n))],
              names=["k", "v"])
    b = PlanBuilder()
    plan = b.scan("t", schema=["k", "v"]).sort(["k"]).build()
    _parity(plan, {"t": t}, mesh)
    # descending + TopK (Sort+Limit fuses into TopK in the optimizer)
    b = PlanBuilder()
    plan = (b.scan("t", schema=["k", "v"])
             .sort(["k"], ascending=False).limit(7).build())
    res = _parity(plan, {"t": t}, mesh)
    assert res.table.num_rows == 7
    assert any(m.exchange_how == "range" for m in res.metrics.values())


# ---- aggregates -------------------------------------------------------------

def test_agg_over_authored_exchange_fuses():
    """The PR-1 marker shape — HashAggregate over an authored
    Exchange(hash) — still runs the fused two-phase program; the exchange
    node carries the all-to-all bytes."""
    mesh = _mesh()
    rng = np.random.default_rng(5)
    n = 512
    t = Table([_icol(rng.integers(0, 30, n)),
               _icol(rng.integers(-100, 100, n))], names=["k", "v"])
    b = PlanBuilder()
    plan = (b.scan("t", schema=["k", "v"]).exchange(keys=["k"])
             .aggregate(["k"], [("v", "sum", "s"), ("v", "min", "lo"),
                                ("v", "count", "c")])
             .sort(["k"]).build())
    res = _parity(plan, {"t": t}, mesh)
    exm = next(m for m in res.metrics.values() if m.kind == "Exchange"
               and m.exchange_how == "hash")
    assert exm.exchange_bytes > 0


def test_agg_without_sort_reorders_to_local_kernel_order():
    """An aggregate-rooted plan (no Sort above): the gather re-sorts by
    the group keys so the distributed output matches the local sort-based
    groupby kernel row for row."""
    mesh = _mesh()
    rng = np.random.default_rng(9)
    n = 300
    t = Table([_icol(rng.integers(0, 25, n)),
               _icol(rng.integers(0, 50, n))], names=["k", "v"])
    b = PlanBuilder()
    plan = (b.scan("t", schema=["k", "v"])
             .aggregate(["k"], [("v", "sum", "s")]).build())
    _parity(plan, {"t": t}, mesh)


# ---- graceful boundaries ----------------------------------------------------

def _semi_joined(b):
    s = b.scan("sales", schema=["k", "v"]).filter(col("v") > 0)
    d = b.scan("dims", schema=["dk", "grp"]).filter(col("grp") == 1)
    return s.join(d, left_on="k", right_on="dk", how="left_semi")


def test_global_aggregate_reduces_on_the_mesh():
    """A keyless (global) aggregate reduces each shard's live rows and
    merges the partials by an all-reduce: its one row stays on the mesh,
    and the only gather is the sink's."""
    mesh = _mesh()
    sales, dims = _tables(seed=13)
    plan = (_semi_joined(PlanBuilder())
            .aggregate([], [("v", "sum", "total"), ("v", "count", "n"),
                            ("v", "min", "lo"), ("v", "max", "hi")])
            .build())
    res = _parity(plan, {"sales": sales, "dims": dims}, mesh)
    agg = next(m for m in res.metrics.values() if m.kind == "HashAggregate")
    assert agg.sharding.startswith("rows@")
    assert agg.exchange_how == "reduce" and agg.rows_out == 1
    gathers = [m for m in res.metrics.values() if m.exchange_how == "gather"]
    assert len(gathers) == 1 and res.local_ops == 0


def test_gather_boundary_below_a_mean():
    """`mean` has no distributed form: the plan runs distributed up to
    it, gathers once, and finishes locally; the fallback is counted."""
    mesh = _mesh()
    sales, dims = _tables(seed=13)
    plan = (_semi_joined(PlanBuilder())
            .aggregate(["k"], [("v", "mean", "avg")]).sort(["k"]).build())
    res = _parity(plan, {"sales": sales, "dims": dims}, mesh)
    agg = next(m for m in res.metrics.values() if m.kind == "HashAggregate")
    # the aggregate ran after the planned gather boundary: its input is a
    # plain local table, never a sharded relation
    assert not agg.sharding.startswith(("hash", "rows", "replicated"))
    assert any(m.exchange_how == "gather" for m in res.metrics.values())
    assert res.local_ops == 2           # the aggregate and the sort above it


def test_float_inputs_keep_aggregate_local_with_parity():
    """Float value columns fail the exact-int64 exchange gate: the
    aggregate gathers and runs locally — graceful boundary, same result."""
    mesh = _mesh()
    rng = np.random.default_rng(17)
    n = 200
    t = Table([_icol(rng.integers(0, 10, n)), _fcol(rng.standard_normal(n))],
              names=["k", "x"])
    b = PlanBuilder()
    plan = (b.scan("t", schema=["k", "x"])
             .aggregate(["k"], [("x", "sum", "s")]).build())
    res = _parity(plan, {"t": t}, mesh)
    agg = next(m for m in res.metrics.values() if m.kind == "HashAggregate")
    assert not agg.sharding.startswith(("hash", "rows", "replicated"))


def test_optimizer_off_distributes_with_implicit_exchanges():
    """No exchange_planning (optimizer off): the executor still runs the
    plan on the mesh, repartitioning implicitly at the join (bytes on the
    join's own metric row)."""
    mesh = _mesh()
    sales, dims = _tables(seed=19)
    b = PlanBuilder()
    s = b.scan("sales", schema=["k", "v"])
    d = b.scan("dims", schema=["dk", "grp"])
    plan = (s.join(d, left_on="k", right_on="dk")
             .aggregate(["k"], [("v", "sum", "t")]).sort(["k"]).build())
    res = _parity(plan, {"sales": sales, "dims": dims}, mesh,
                  optimize=False)
    join = next(m for m in res.metrics.values() if m.kind == "HashJoin")
    assert join.exchange_how == "hash" and join.exchange_bytes > 0


def test_capacity_escalation_on_undersized_key_cap():
    """An undersized node key_cap overflows the SPMD program and the
    driver escalates geometrically (SplitAndRetry at plan granularity),
    with the escalations charged to the aggregate's metric row."""
    mesh = _mesh()
    rng = np.random.default_rng(23)
    n = 400
    t = Table([_icol(rng.permutation(n) % 97),
               _icol(rng.integers(0, 50, n))], names=["k", "v"])
    b = PlanBuilder()
    plan = (b.scan("t", schema=["k", "v"])
             .aggregate(["k"], [("v", "sum", "s")], key_cap=4)
             .sort(["k"]).build())
    res = _parity(plan, {"t": t}, mesh)
    agg = next(m for m in res.metrics.values() if m.kind == "HashAggregate")
    assert agg.escalations > 0


def test_profile_text_renders_dist_lines():
    mesh = _mesh()
    sales, dims = _tables(seed=29)
    b = PlanBuilder()
    s = b.scan("sales", schema=["k", "v"])
    d = b.scan("dims", schema=["dk", "grp"]).filter(col("grp") == 1)
    plan = (s.join(d, left_on="k", right_on="dk")
             .aggregate(["grp"], [("v", "sum", "t")]).build())
    res = _parity(plan, {"sales": sales, "dims": dims}, mesh)
    text = res.profile_text()
    assert "dist: sharding" in text and "B moved" in text


def test_stacked_consumers_never_elide_on_stale_placement():
    """Placement claims are path-truthful: an ELIDED aggregate leaves
    rows at the child's subset placement (hash(k), not hash(k,g)), and a
    FUSED aggregate re-places by the full key tuple — a downstream join
    or aggregate must decide its own exchange against the claim of the
    path that actually ran, or it merges rows that are not co-located."""
    mesh = _mesh()
    rng = np.random.default_rng(31)
    n = 600
    left = Table([_icol(rng.integers(0, 7, n)), _icol(rng.integers(0, 4, n)),
                  _icol(rng.integers(0, 50, n))], names=["k", "g", "v"])
    r1 = Table([_icol(np.arange(7)), _icol(np.arange(7))],
               names=["rk", "w"])
    pairs = [(a, c) for a in range(7) for c in range(4)]
    r2 = Table([_icol([p[0] for p in pairs]), _icol([p[1] for p in pairs]),
                _icol(range(len(pairs)))], names=["jk", "jg", "z"])
    import os
    os.environ["SPARK_RAPIDS_TPU_BROADCAST_ROWS"] = "1"   # all shuffles
    try:
        b = PlanBuilder()
        plan = (b.scan("l", schema=["k", "g", "v"])
                 .join(b.scan("r1", schema=["rk", "w"]), "k", "rk")
                 .aggregate(["k", "g"], [("v", "sum", "s")])   # elided:
                 #            rows stay at hash(k) from the join above
                 .join(b.scan("r2", schema=["jk", "jg", "z"]),
                       ["k", "g"], ["jk", "jg"])
                 .aggregate(["k"], [("z", "sum", "zz"), ("s", "sum", "ss")])
                 .sort(["k"]).build())
        inputs = {"l": left, "r1": r1, "r2": r2}
        for opt in (True, False):
            _parity(plan, inputs, mesh, optimize=opt)
    finally:
        del os.environ["SPARK_RAPIDS_TPU_BROADCAST_ROWS"]


# ---- exchange transport (plan/transport.py) ---------------------------------

def _env(**kv):
    """Scoped env override for one block (pytest's MonkeyPatch owns the
    save/restore so this file never hand-rolls it)."""
    import contextlib

    @contextlib.contextmanager
    def cm():
        with pytest.MonkeyPatch.context() as mp:
            for k, v in kv.items():
                if v is None:
                    mp.delenv(k, raising=False)
                else:
                    mp.setenv(k, v)
            yield
    return cm()


def _det_tables(n=100):
    """Deterministic tables for exact byte pins: all-match join keys."""
    sales = Table([_icol(np.arange(n) % 40),
                   _icol(np.arange(n) - 50)], names=["k", "v"])
    dims = Table([_icol(np.arange(40)), _icol(np.arange(40) % 3)],
                 names=["dk", "grp"])
    return sales, dims


def _join_plan():
    b = PlanBuilder()
    s = b.scan("sales", schema=["k", "v"])
    d = b.scan("dims", schema=["dk", "grp"])
    # (k, v) totally orders the rows, so distributed-vs-local parity is
    # row-exact despite the join's emission-order caveat
    return (s.join(d, left_on="k", right_on="dk")
             .sort(["k", "v"]).build())


def test_exchange_accounting_pinned_two_peer():
    """The audit satellite's regression pin (pack OFF, so wire ==
    logical): a hash edge counts each live row ONCE at key-word + value
    width, broadcast counts payload x (n_peers - 1), the sink gather
    collects the join output once — matching the certifier's per-edge
    exchange model exactly."""
    mesh = _mesh()
    n = 100
    sales, dims = _det_tables(n)
    inputs = {"sales": sales, "dims": dims}
    with _env(SPARK_RAPIDS_TPU_EXCHANGE_PACK="off",
              SPARK_RAPIDS_TPU_BROADCAST_ROWS="1"):
        res = _parity(_join_plan(), inputs, mesh)
    ex = {m.label: m for m in res.metrics.values()
          if m.kind == "Exchange" and m.exchange_how}
    by_how = {}
    for m in ex.values():
        by_how.setdefault(m.exchange_how, []).append(m)
    # shuffle edges: live x (8 B key word + 8 B int64 value), once each
    hashes = sorted(m.exchange_bytes for m in by_how["hash"])
    assert hashes == [40 * 16, n * 16]
    # sink gather: join output (k, v, dk, grp — four non-null int64)
    (g,) = by_how["gather"]
    assert g.exchange_bytes == n * 32
    assert all(m.exchange_bytes == m.exchange_bytes_logical
               for m in ex.values())            # pack off: wire == logical
    # broadcast counts payload x (n_peers - 1), not x n_peers
    with _env(SPARK_RAPIDS_TPU_EXCHANGE_PACK="off"):
        res = _parity(_join_plan(), inputs, mesh)
    bc = next(m for m in res.metrics.values()
              if m.exchange_how == "broadcast")
    assert bc.exchange_bytes == 40 * 16 * (NDEV - 1)
    assert bc.exchange_bytes == bc.exchange_bytes_logical


def test_packed_exchanges_wire_under_logical_and_cert():
    """Packing on (the default): parity holds, at least one edge
    compresses (wire < logical), no edge's wire exceeds its logical, and
    every planned edge's wire stays at or under the certifier's per-edge
    payload bound (the `wire <= certified hi` inequality)."""
    from spark_rapids_tpu.analysis.footprint import check_observed
    mesh = _mesh()
    sales, dims = _det_tables(200)
    inputs = {"sales": sales, "dims": dims}
    b = PlanBuilder()
    s = b.scan("sales", schema=["k", "v"])
    d = b.scan("dims", schema=["dk", "grp"])
    plan = (s.join(d, left_on="k", right_on="dk")
             .aggregate(["k"], [("v", "sum", "t")]).sort(["k"]).build())
    with _env(SPARK_RAPIDS_TPU_BROADCAST_ROWS="1"):
        res = _parity(plan, inputs, mesh)
    edges = [m for m in res.metrics.values() if m.exchange_how]
    assert edges and all(m.exchange_bytes <= m.exchange_bytes_logical
                         for m in edges)
    assert any(m.exchange_bytes < m.exchange_bytes_logical
               for m in edges), "no edge compressed"
    assert any(m.exchange_codecs for m in edges)
    assert res.cert is not None
    assert check_observed(res.cert, res) is None
    # JSONL-facing dict carries both counters under explicit names
    row = next(m.to_dict() for m in edges)
    assert row["exchange_bytes_wire"] == row["exchange_bytes"]
    assert "exchange_bytes_logical" in row
    text = res.profile_text()
    assert "B moved" in text and "B logical" in text


def test_pack_off_and_codecs_none_restore_parity():
    """The knob contract: pack off is byte-identical legacy accounting
    (wire == logical everywhere); codecs=none keeps the packed layout but
    chooses no per-column encodings."""
    mesh = _mesh()
    sales, dims = _det_tables(150)
    inputs = {"sales": sales, "dims": dims}
    plan = _join_plan()
    ref = None
    for env in ({"SPARK_RAPIDS_TPU_EXCHANGE_PACK": "off"},
                {"SPARK_RAPIDS_TPU_EXCHANGE_CODECS": "none"},
                {"SPARK_RAPIDS_TPU_EXCHANGE_CODECS": "for,bitpack"}):
        with _env(**env):
            res = _parity(plan, inputs, mesh)
        out = res.table.to_pydict()
        ref = ref or out
        assert out == ref
        if env.get("SPARK_RAPIDS_TPU_EXCHANGE_PACK") == "off" or \
                env.get("SPARK_RAPIDS_TPU_EXCHANGE_CODECS") == "none":
            assert all(m.exchange_bytes == m.exchange_bytes_logical
                       for m in res.metrics.values() if m.exchange_how)


def test_async_exchange_overlap_and_parity():
    """SPARK_RAPIDS_TPU_EXCHANGE_ASYNC=on: the exchange's pack+transfer
    runs on a worker thread (PendingRel) and the consumer resolves it —
    bit-exact parity, and the deferred metric row (rows/bytes/wall +
    overlap-ms) is stamped by resolve time."""
    mesh = _mesh()
    sales, dims = _tables(seed=41)
    inputs = {"sales": sales, "dims": dims}
    b = PlanBuilder()
    s = b.scan("sales", schema=["k", "v"])
    d = b.scan("dims", schema=["dk", "grp"])
    plan = (s.join(d, left_on="k", right_on="dk")
             .aggregate(["grp", "k"], [("v", "sum", "t")])
             .sort(["k"]).build())
    with _env(SPARK_RAPIDS_TPU_EXCHANGE_ASYNC="on",
              SPARK_RAPIDS_TPU_BROADCAST_ROWS="1"):
        res = _parity(plan, inputs, mesh)
    hash_edges = [m for m in res.metrics.values()
                  if m.kind == "Exchange" and m.exchange_how == "hash"]
    assert hash_edges
    for m in hash_edges:
        assert m.rows_out > 0 and m.bytes_out > 0     # resolve stamped it
        assert m.wall_ms is not None and m.wall_ms > 0
        assert m.exchange_overlap_ms >= 0.0


def test_gather_cache_hit_reports_zero_bytes():
    """A DAG-shared gather: the first crossing carries (and charges) the
    payload; a cache-served gather moves nothing and must report zero
    bytes, or summed wire counters double-count the edge."""
    from spark_rapids_tpu.plan.distributed import DistContext, shard_table
    from spark_rapids_tpu.plan.metrics import OperatorMetrics
    mesh = _mesh()
    t = Table([_icol(np.arange(50)), _icol(np.arange(50) % 7)],
              names=["a", "b"])
    b = PlanBuilder()
    plan = b.scan("t", schema=["a", "b"]).build()
    ctx = DistContext(PlanExecutor(mesh=mesh), plan, {"t": t})
    rel = shard_table(mesh, "data", t)
    m1 = OperatorMetrics("e1", "Exchange")
    m2 = OperatorMetrics("e2", "Exchange")
    t1 = ctx._gather(rel, m1)
    t2 = ctx._gather(rel, m2)
    assert t1 is t2                       # served from the rel cache
    assert t1.to_pydict() == t.to_pydict()
    assert m1.exchange_bytes > 0
    assert m2.exchange_how == "gather" and m2.exchange_bytes == 0
    assert m2.exchange_bytes_logical == 0


def test_nds_q72_distributed_parity_pack_on_and_off():
    """NDS q72 through the distributed tier with packing forced on and
    forced off: identical results both ways (and identical to the
    single-device tier), with the packed run compressing at least one
    edge. One NDS plan keeps this inside the tier-1 budget."""
    from examples.nds import q72_inputs, q72_plan, q72_tables as bt72
    mesh = _mesh()
    inputs = q72_inputs(*bt72(4000, seed=5))
    plan = q72_plan()
    outs = {}
    for mode in ("on", "off"):
        with _env(SPARK_RAPIDS_TPU_EXCHANGE_PACK=mode):
            res = _parity(plan, inputs, mesh)
        outs[mode] = res.table.to_pydict()
        edges = [m for m in res.metrics.values() if m.exchange_how]
        if mode == "on":
            assert any(m.exchange_bytes < m.exchange_bytes_logical
                       for m in edges), "packing compressed no q72 edge"
        else:
            assert all(m.exchange_bytes == m.exchange_bytes_logical
                       for m in edges)
    assert outs["on"] == outs["off"]


def test_capped_mesh_rejected_per_plan_names_operator():
    mesh = object()       # never touched: the check fires before any work
    ex = PlanExecutor(mode="capped", mesh=mesh)
    b = PlanBuilder()
    t = Table([_icol([1, 2, 3])], names=["v"])
    plan = (b.scan("t", schema=["v"])
             .aggregate([], [("v", "sum", "s")]).build())
    sortplan = b.scan("t", schema=["v"]).sort(["v"]).build()
    with pytest.raises(PlanValidationError, match=r"Sort#\d+"):
        ex.execute(sortplan, {"t": t})
    # keyless aggregate-only plan: HashAggregate is still named
    with pytest.raises(PlanValidationError, match=r"HashAggregate#\d+"):
        ex.execute(plan, {"t": t})
