"""Streaming parquet scans: streaming-vs-materialized parity, stats-driven
row-group pruning exactness, pipelined prefetch, fault-injected degraded
replay, and out-of-core execution under a memory budget (docs/io.md).

Oracle strategy: every streaming result compares against the SAME plan
bound to materialized Tables — which the NDS parity tests already chain to
the pandas oracle — so streaming correctness is transitive to the ground
truth, not merely self-consistent.
"""
import json

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, Table, faultinj
from spark_rapids_tpu.io import ParquetSource
from spark_rapids_tpu.plan import PlanBuilder, PlanExecutor, Scan, col

N = 8_000


def _write_sources(tmp_path, inputs, row_groups=4):
    """Engine Tables -> parquet files -> ParquetSource bindings."""
    out = {}
    for name, t in inputs.items():
        pt = pa.table({n: np.asarray(t[n].data) for n in t.names})
        path = str(tmp_path / f"{name}.parquet")
        pq.write_table(pt, path,
                       row_group_size=max(1, t.num_rows // row_groups),
                       compression="NONE")
        out[name] = ParquetSource(path)
    return out


def _result(res):
    return (res.compact() if res.valid is not None else res.table).to_pydict()


# ---- NDS streaming-vs-materialized parity -----------------------------------

def test_nds_q5_parquet_parity_eager_and_capped(tmp_path):
    from examples.nds import q5_inputs, q5_plan, q5_tables as build_tables
    tabs, dates = build_tables(N, seed=3)
    inputs = q5_inputs(tabs, dates)
    plan = q5_plan()
    sources = _write_sources(tmp_path, inputs)
    for mode in ("eager", "capped"):
        ref = PlanExecutor(mode=mode).execute(plan, inputs)
        got = PlanExecutor(mode=mode).execute(plan, sources)
        assert _result(got) == _result(ref), f"{mode} tier diverged"


def test_nds_q72_parquet_parity_eager_and_capped(tmp_path):
    from examples.nds import (q72_inputs, q72_plan,
                              q72_tables as build_tables)
    inputs = q72_inputs(*build_tables(N, seed=5))
    plan = q72_plan()
    sources = _write_sources(tmp_path, inputs)
    for mode in ("eager", "capped"):
        ref = PlanExecutor(mode=mode).execute(plan, inputs)
        got = PlanExecutor(mode=mode).execute(plan, sources)
        assert _result(got) == _result(ref), f"{mode} tier diverged"


# ---- pruning exactness ------------------------------------------------------

def _seq_table(n=N, seed=0):
    rng = np.random.default_rng(seed)
    seq = np.arange(n, dtype=np.int64)
    key = rng.integers(0, 40, n).astype(np.int64)
    val = rng.integers(0, 10_000, n).astype(np.int64)
    t = Table([Column.from_numpy(seq), Column.from_numpy(key),
               Column.from_numpy(val)], names=["seq", "key", "val"])
    return t


def _plan_over(predicate, source_kw):
    b = PlanBuilder()
    return (b.scan("t", **source_kw)
             .filter(predicate)
             .aggregate(["key"], [("val", "sum", "s"),
                                  ("val", "count", "c")])
             .sort(["key"])
             .build())


def test_selective_predicate_prunes_and_stays_exact(tmp_path):
    t = _seq_table()
    sources = _write_sources(tmp_path, {"t": t}, row_groups=8)
    pred = (col("seq") < N // 4) & (col("key") >= 5)
    ref = PlanExecutor().execute(_plan_over(pred, {"schema": list(t.names)}),
                                 {"t": t})
    res = PlanExecutor().execute(_plan_over(pred, {"schema": list(t.names)}),
                                 sources)
    assert _result(res) == _result(ref)
    scan_m = next(m for m in res.metrics.values() if m.kind == "Scan")
    assert scan_m.io_row_groups_total == 8
    assert scan_m.io_row_groups_pruned > 0
    assert scan_m.io_bytes_skipped > 0
    assert res.optimizer["rules_fired"].get("scan_pruning") == 1
    # the EXECUTED scan carries the pruning predicate; the Filter is
    # retained above it (pruning-only lowering)
    scan_node = next(n for n in res.plan.nodes if isinstance(n, Scan))
    assert scan_node.predicate is not None
    kinds = [n.kind for n in res.plan.nodes]
    assert "Filter" in kinds or "FusedSelect" in kinds


def test_non_conjunct_predicate_declines_pruning(tmp_path):
    """Adversarial: an OR at the predicate root would OVER-prune if its
    branches leaked into Scan.predicate (row groups failing `seq < 100`
    still hold `key == 7` rows). The rule must decline, keep all groups,
    and stay exact."""
    t = _seq_table()
    sources = _write_sources(tmp_path, {"t": t}, row_groups=8)
    pred = (col("seq") < 100) | (col("key") == 7)
    ref = PlanExecutor().execute(_plan_over(pred, {"schema": list(t.names)}),
                                 {"t": t})
    res = PlanExecutor().execute(_plan_over(pred, {"schema": list(t.names)}),
                                 sources)
    assert _result(res) == _result(ref)
    assert not res.optimizer["rules_fired"].get("scan_pruning")
    scan_node = next(n for n in res.plan.nodes if isinstance(n, Scan))
    assert scan_node.predicate is None
    scan_m = next(m for m in res.metrics.values() if m.kind == "Scan")
    assert scan_m.io_row_groups_pruned == 0


def test_or_under_and_lowers_only_the_safe_conjunct(tmp_path):
    """(seq < cut) & (key == 1 | key == 2): only the range conjunct
    lowers — pruning on a SUBSET of an AND is conservative-exact."""
    t = _seq_table()
    sources = _write_sources(tmp_path, {"t": t}, row_groups=8)
    pred = (col("seq") < N // 4) & ((col("key") == 1) | (col("key") == 2))
    ref = PlanExecutor().execute(_plan_over(pred, {"schema": list(t.names)}),
                                 {"t": t})
    res = PlanExecutor().execute(_plan_over(pred, {"schema": list(t.names)}),
                                 sources)
    assert _result(res) == _result(ref)
    scan_m = next(m for m in res.metrics.values() if m.kind == "Scan")
    assert scan_m.io_row_groups_pruned > 0
    scan_node = next(n for n in res.plan.nodes if isinstance(n, Scan))
    assert "seq" in repr(scan_node.predicate)
    assert "key" not in repr(scan_node.predicate)


# ---- builder binding + prefetch knob ----------------------------------------

def test_builder_parquet_binding_validates_and_streams(tmp_path):
    from spark_rapids_tpu.plan import PlanValidationError
    t = _seq_table(1000)
    sources = _write_sources(tmp_path, {"t": t})
    path = sources["t"].source
    b = PlanBuilder()
    rel = b.scan("t", parquet=path)
    assert rel.node.schema == ("seq", "key", "val")
    assert rel.node.est_rows == 1000
    plan = (rel.filter(col("seq") < 500)
               .aggregate(["key"], [("val", "sum", "s")]).sort(["key"])
               .build())
    res = PlanExecutor().execute(plan)          # no inputs= needed
    b2 = PlanBuilder()
    tplan = (b2.scan("t", schema=list(t.names)).filter(col("seq") < 500)
               .aggregate(["key"], [("val", "sum", "s")]).sort(["key"])
               .build())
    ref = PlanExecutor().execute(tplan, {"t": t})
    assert _result(res) == _result(ref)
    with pytest.raises(PlanValidationError):
        b.scan("t", schema=["wrong", "names", "here"], parquet=path)


def test_prefetch_disabled_matches(tmp_path, monkeypatch):
    """SPARK_RAPIDS_TPU_IO_PREFETCH=0 decodes inline (no thread) with
    identical results and zero overlap."""
    t = _seq_table()
    sources = _write_sources(tmp_path, {"t": t}, row_groups=8)
    pred = col("key") >= 5
    ref = PlanExecutor().execute(_plan_over(pred, {"schema": list(t.names)}),
                                 {"t": t})
    monkeypatch.setenv("SPARK_RAPIDS_TPU_IO_PREFETCH", "0")
    res = PlanExecutor().execute(_plan_over(pred, {"schema": list(t.names)}),
                                 sources)
    assert _result(res) == _result(ref)
    scan_m = next(m for m in res.metrics.values() if m.kind == "Scan")
    assert scan_m.io_overlap_ms == 0.0
    assert scan_m.io_decode_ms > 0.0


def test_chunk_rows_morsels_match(tmp_path, monkeypatch):
    """SPARK_RAPIDS_TPU_IO_CHUNK_ROWS splits decoded row groups into
    bounded morsels without changing any result."""
    t = _seq_table()
    sources = _write_sources(tmp_path, {"t": t}, row_groups=2)
    pred = col("key") >= 5
    ref = PlanExecutor().execute(_plan_over(pred, {"schema": list(t.names)}),
                                 {"t": t})
    monkeypatch.setenv("SPARK_RAPIDS_TPU_IO_CHUNK_ROWS", "512")
    res = PlanExecutor().execute(_plan_over(pred, {"schema": list(t.names)}),
                                 sources)
    assert _result(res) == _result(ref)


def test_keyless_minmax_with_fully_filtered_morsels(tmp_path):
    """A morsel whose rows all fail the filter must not crash a keyless
    min/max partial aggregate (zero-size reduction) — the table-bound
    plan reduces over the whole non-empty relation and succeeds, so the
    streamed plan must too. Rows live only in the middle row groups, so
    both edge morsels filter to zero rows."""
    n = 4000
    t = _seq_table(n)

    def mkplan():
        b = PlanBuilder()
        # keep rows in [1000, 3000): chunks 0 and 3 (of 4) filter empty.
        # one conjunct only, so NO row-group pruning removes the empty
        # chunks before the filter does
        return (b.scan("t", schema=list(t.names))
                 .filter((col("seq") - 1000 < 2000) & (col("seq") >= 1000))
                 .aggregate([], [("val", "min", "lo"), ("val", "max", "hi"),
                                 ("val", "sum", "s")])
                 .build())

    sources = _write_sources(tmp_path, {"t": t}, row_groups=4)
    ref = PlanExecutor().execute(mkplan(), {"t": t})
    res = PlanExecutor().execute(mkplan(), sources)
    assert _result(res) == _result(ref)


# ---- fault injection: degraded tier replays the stream ----------------------

def test_fatal_fault_mid_stream_degrades_and_replays(tmp_path):
    """A fatal fault during streaming execution trips the breaker; the
    degraded CPU tier replays the scan's chunks from the source and the
    result still matches the fault-free materialized run."""
    t = _seq_table()
    sources = _write_sources(tmp_path, {"t": t}, row_groups=8)
    pred = col("key") >= 5
    ref = PlanExecutor().execute(_plan_over(pred, {"schema": list(t.names)}),
                                 {"t": t})
    cfg = tmp_path / "faultinj.json"
    cfg.write_text(json.dumps({
        "seed": 1,
        "computeFaults": {
            "plan.Filter": {"percent": 100, "injectionType": 0,
                            "interceptionCount": 1},
        },
    }))
    inj = faultinj.install(str(cfg))
    try:
        res = PlanExecutor().execute(
            _plan_over(pred, {"schema": list(t.names)}), sources)
    finally:
        faultinj.uninstall()
    assert inj.get_and_reset_injected() >= 1
    assert res.degraded
    assert _result(res) == _result(ref)
    assert all(m.degraded for m in res.metrics.values())


def test_transient_fault_mid_stream_retries_chunk(tmp_path):
    """A nonfatal (recoverable) fault on one chunk's operator retries just
    that unit — the stream continues on the device tier."""
    t = _seq_table()
    sources = _write_sources(tmp_path, {"t": t}, row_groups=8)
    pred = col("key") >= 5
    ref = PlanExecutor().execute(_plan_over(pred, {"schema": list(t.names)}),
                                 {"t": t})
    cfg = tmp_path / "faultinj.json"
    cfg.write_text(json.dumps({
        "seed": 1,
        "computeFaults": {
            "plan.Filter": {"percent": 100, "injectionType": 1,
                            "interceptionCount": 1},
        },
    }))
    faultinj.install(str(cfg))
    try:
        res = PlanExecutor().execute(
            _plan_over(pred, {"schema": list(t.names)}), sources)
    finally:
        faultinj.uninstall()
    assert not res.degraded
    assert res.retries >= 1
    assert _result(res) == _result(ref)


# ---- out-of-core: bigger-than-budget scans ----------------------------------

def test_out_of_core_scan_streams_under_budget(tmp_path):
    """A parquet-bound plan whose materialized read exceeds the memory
    budget completes via the streaming prefix: per-chunk working sets are
    admitted one morsel at a time, while the table-bound equivalent (one
    admitted whole-file read) exceeds the same budget up front."""
    from spark_rapids_tpu.io import read_parquet
    from spark_rapids_tpu.runtime import DeviceSession, HardOOM
    from spark_rapids_tpu.runtime.admission import active_session
    n = 60_000
    t = _seq_table(n)
    sources = _write_sources(tmp_path, {"t": t}, row_groups=10)
    path = sources["t"].source
    import os
    file_bytes = os.path.getsize(path)
    # read_parquet admits 3x the encoded size; the budget sits well below
    # that but far above any single morsel's working set
    limit = int(1.5 * file_bytes)
    pred = col("key") >= 5
    plan = _plan_over(pred, {"schema": list(t.names)})
    ref = PlanExecutor().execute(plan, {"t": t})
    with DeviceSession(limit) as session:
        with active_session(session):
            with pytest.raises(HardOOM):
                read_parquet(path)          # materialized: over budget
        res = PlanExecutor(session=session, degrade="off").execute(
            plan, {"t": ParquetSource(path)})
    assert _result(res) == _result(ref)
    scan_m = next(m for m in res.metrics.values() if m.kind == "Scan")
    assert scan_m.io_row_groups_total == 10


# ---- concat boundary: streamable prefix below a non-streamable op -----------

def test_stream_concat_boundary_below_join(tmp_path):
    """Scan -> Filter streams morsel-at-a-time, concatenates ONCE at the
    join boundary, and matches the materialized plan row for row."""
    t = _seq_table()
    rng = np.random.default_rng(9)
    dim = Table([Column.from_numpy(np.arange(40, dtype=np.int64)),
                 Column.from_numpy(rng.integers(0, 5, 40).astype(np.int64))],
                names=["dkey", "grp"])
    sources = _write_sources(tmp_path, {"t": t}, row_groups=8)

    def plan():
        b = PlanBuilder()
        fact = b.scan("t", schema=["seq", "key", "val"]) \
                .filter(col("seq") < N // 2)
        d = b.scan("dim", schema=["dkey", "grp"])
        return (fact.join(d, left_on="key", right_on="dkey")
                    .aggregate(["grp"], [("val", "sum", "s")])
                    .sort(["grp"]).build())

    ref = PlanExecutor().execute(plan(), {"t": t, "dim": dim})
    res = PlanExecutor().execute(plan(), {**sources, "dim": dim})
    assert _result(res) == _result(ref)
    scan_m = next(m for m in res.metrics.values()
                  if m.kind == "Scan" and "t" in m.describe)
    assert scan_m.io_row_groups_pruned > 0      # seq < N/2 prunes the tail
