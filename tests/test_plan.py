"""Physical-plan subsystem tests: builder validation, explain(), both
executor tiers with per-operator metrics, plan-granularity cap escalation,
faultinj-driven plan-level retry, and the distributed Exchange lowering."""
import json

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, Table, dtypes, faultinj
from spark_rapids_tpu.plan import (PlanBuilder, PlanExecutor,
                                   PlanValidationError, col, lit,
                                   scalar_max)


def _col(a):
    a = np.asarray(a, dtype=np.int64)
    return Column(dtype=dtypes.INT64, length=len(a), data=jnp.asarray(a))


def _tables(n=2000, seed=0):
    rng = np.random.default_rng(seed)
    sales = Table([_col(rng.integers(0, 50, n)),
                   _col(rng.integers(1, 100, n))], names=["k", "v"])
    dims = Table([_col(np.arange(50)), _col(np.arange(50) % 3)],
                 names=["dk", "grp"])
    return sales, dims


def _plan():
    b = PlanBuilder()
    s = b.scan("sales", schema=["k", "v"])
    d = b.scan("dims", schema=["dk", "grp"]).filter(col("grp") == 1)
    return (s.join(d, left_on="k", right_on="dk")
             .project({"grp": col("grp"), "rev": col("v") * lit(2)})
             .aggregate(["grp"], [("rev", "sum", "total"),
                                  ("rev", "size", "cnt")])
             .sort(["grp"])
             .build())


def _oracle(sales, dims):
    sdf = pd.DataFrame({"k": np.asarray(sales["k"].data),
                        "v": np.asarray(sales["v"].data)})
    ddf = pd.DataFrame({"dk": np.asarray(dims["dk"].data),
                        "grp": np.asarray(dims["grp"].data)})
    j = sdf.merge(ddf[ddf.grp == 1], left_on="k", right_on="dk")
    return (j.assign(rev=j.v * 2).groupby("grp")
             .agg(total=("rev", "sum"), cnt=("rev", "size")).reset_index())


# ---- builder validation -----------------------------------------------------

class TestValidation:
    def test_unknown_filter_column(self):
        b = PlanBuilder()
        with pytest.raises(PlanValidationError, match="nope"):
            b.scan("t", schema=["a"]).filter(col("nope") == 1).build()

    def test_unknown_join_key(self):
        b = PlanBuilder()
        l = b.scan("l", schema=["a"])
        r = b.scan("r", schema=["b"])
        with pytest.raises(PlanValidationError, match="right key"):
            l.join(r, left_on="a", right_on="zz").build()

    def test_join_key_arity_mismatch(self):
        b = PlanBuilder()
        l = b.scan("l", schema=["a", "b"])
        r = b.scan("r", schema=["c"])
        with pytest.raises(PlanValidationError, match="equal-length"):
            l.join(r, left_on=["a", "b"], right_on=["c"]).build()

    def test_join_name_collision(self):
        b = PlanBuilder()
        l = b.scan("l", schema=["a", "x"])
        r = b.scan("r", schema=["b", "x"])
        with pytest.raises(PlanValidationError, match="collision"):
            l.join(r, left_on="a", right_on="b").build()

    def test_bad_agg_op(self):
        b = PlanBuilder()
        with pytest.raises(PlanValidationError, match="median"):
            b.scan("t", schema=["a", "v"]).aggregate(
                ["a"], [("v", "median", "m")]).build()

    def test_duplicate_output_names(self):
        b = PlanBuilder()
        with pytest.raises(PlanValidationError, match="duplicate"):
            b.scan("t", schema=["a", "v"]).aggregate(
                ["a"], [("v", "sum", "a")]).build()

    def test_union_schema_mismatch(self):
        b = PlanBuilder()
        with pytest.raises(PlanValidationError, match="schemas differ"):
            b.scan("l", schema=["a"]).union(b.scan("r", schema=["b"])).build()

    def test_duplicate_scan_source(self):
        b = PlanBuilder()
        l = b.scan("t", schema=["a"])
        r = b.scan("t", schema=["a"])
        with pytest.raises(PlanValidationError, match="same input"):
            l.join(r, left_on="a", right_on="a", how="left_semi").build()

    def test_deferred_validation_at_bind(self):
        # no declared schema: build() passes, execute() validates and fails
        b = PlanBuilder()
        plan = b.scan("t").filter(col("nope") == 1).build()
        t = Table([_col([1, 2])], names=["a"])
        with pytest.raises(PlanValidationError, match="nope"):
            PlanExecutor().execute(plan, {"t": t})

    def test_unbound_input(self):
        plan = PlanBuilder().scan("t", schema=["a"]).build()
        with pytest.raises(PlanValidationError, match="unbound"):
            PlanExecutor().execute(plan, {})

    def test_bound_schema_mismatch(self):
        plan = PlanBuilder().scan("t", schema=["a", "b"]).build()
        t = Table([_col([1])], names=["a"])
        with pytest.raises(PlanValidationError, match="does not match"):
            PlanExecutor().execute(plan, {"t": t})


# ---- explain ----------------------------------------------------------------

def test_explain_tree_and_schemas():
    plan = _plan()
    txt = plan.explain()
    for kind in ("Scan", "Filter", "HashJoin", "Project", "HashAggregate",
                 "Sort"):
        assert kind in txt
    assert "-> [grp, total, cnt]" in txt          # resolved output schema
    assert "sales" in txt and "(grp == 1)" in txt


def test_explain_marks_shared_dag_nodes():
    b = PlanBuilder()
    t = b.scan("t", schema=["a", "v"])
    shared = t.aggregate(["a"], [("v", "sum", "s")])
    u = shared.union(shared.filter(col("s") > 0))
    txt = u.build().explain()
    assert "[ref HashAggregate#" in txt           # second occurrence is a ref


# ---- eager tier -------------------------------------------------------------

def test_eager_matches_oracle_with_metrics():
    sales, dims = _tables()
    plan = _plan()
    res = PlanExecutor(mode="eager").execute(
        plan, {"sales": sales, "dims": dims})
    ref = _oracle(sales, dims)
    got = res.table.to_pydict()
    assert got["total"] == ref["total"].tolist()
    assert got["cnt"] == ref["cnt"].tolist()

    prof = {m["label"]: m for m in res.profile()}
    assert len(prof) == len(plan.nodes)           # every operator measured
    join = next(m for m in prof.values() if m["kind"] == "HashJoin")
    n_join = int(ref["cnt"].sum())
    n_dims_live = int((np.asarray(dims["grp"].data) == 1).sum())
    assert join["rows_out"] == n_join
    assert join["rows_in"] == sales.num_rows + n_dims_live
    assert join["bytes_out"] == n_join * 8 * 4    # k, v, dk, grp int64
    assert all(m["wall_ms"] is not None and m["wall_ms"] >= 0
               for m in prof.values())
    assert all(m["retries"] == 0 and m["escalations"] == 0
               for m in prof.values())


def test_execute_takes_no_placement():
    """CPU/TPU co-placement went: a caller that still forces subtrees to
    the host fails here, not silently on the device."""
    sales, dims = _tables()
    with pytest.raises(TypeError, match="placement"):
        PlanExecutor(mode="eager").execute(
            _plan(), {"sales": sales, "dims": dims}, placement=["dims"])


def test_limit_both_tiers():
    sales, dims = _tables()
    b = PlanBuilder()
    plan = (b.scan("sales").sort(["v", "k"], ascending=[False, True])
             .limit(7).build())
    res = PlanExecutor().execute(plan, {"sales": sales})
    assert res.table.num_rows == 7
    resc = PlanExecutor(mode="capped").execute(plan, {"sales": sales})
    assert resc.compact().to_pydict() == res.table.to_pydict()


def test_scalar_agg_expression():
    b = PlanBuilder()
    plan = (b.scan("t", schema=["v"])
             .filter(col("v") >= scalar_max(col("v")))
             .build())
    t = Table([_col([3, 9, 1, 9])], names=["v"])
    res = PlanExecutor().execute(plan, {"t": t})
    assert res.table.to_pydict() == {"v": [9, 9]}
    resc = PlanExecutor(mode="capped").execute(plan, {"t": t})
    assert resc.compact().to_pydict() == {"v": [9, 9]}


# ---- capped tier ------------------------------------------------------------

def test_capped_matches_eager():
    sales, dims = _tables()
    plan = _plan()
    eager = PlanExecutor().execute(plan, {"sales": sales, "dims": dims})
    capped = PlanExecutor(mode="capped").execute(
        plan, {"sales": sales, "dims": dims})
    assert capped.compact().to_pydict() == eager.table.to_pydict()
    assert capped.attempts == 1
    prof = {m["label"]: m for m in capped.profile()}
    join = next(m for m in prof.values() if m["kind"] == "HashJoin")
    # live-row counts come back from the device with the result
    assert join["rows_out"] == eager.metrics[join["label"]].rows_out


def test_capped_escalation_grows_caps_at_plan_granularity():
    sales, dims = _tables()
    plan = _plan()
    eager = PlanExecutor().execute(plan, {"sales": sales, "dims": dims})
    ex = PlanExecutor(mode="capped", caps={"row_cap": 64, "key_cap": 2},
                      max_cap_attempts=8)
    res = ex.execute(plan, {"sales": sales, "dims": dims})
    assert res.attempts > 1                       # escalated, not corrupted
    assert res.caps["row_cap"] > 64               # every cap grew together
    assert res.caps["key_cap"] > 2
    assert res.compact().to_pydict() == eager.table.to_pydict()
    join = next(m for m in res.metrics.values() if m.kind == "HashJoin")
    assert join.escalations == res.attempts - 1


def test_capped_exhaustion_raises_not_corrupts():
    from spark_rapids_tpu.parallel.autoretry import CapacityOverflowError
    sales, dims = _tables()
    ex = PlanExecutor(mode="capped", caps={"row_cap": 2, "key_cap": 2},
                      max_cap_attempts=2)
    with pytest.raises(CapacityOverflowError):
        ex.execute(_plan(), {"sales": sales, "dims": dims})


def test_capped_escalated_caps_remembered_across_executes():
    """The second execute() of a plan starts from the escalated caps (per-
    plan memo), not the originals — no re-paying the overflow ladder."""
    sales, dims = _tables()
    plan = _plan()
    ex = PlanExecutor(mode="capped", caps={"row_cap": 64, "key_cap": 2},
                      max_cap_attempts=8)
    r1 = ex.execute(plan, {"sales": sales, "dims": dims})
    assert r1.attempts > 1
    # the programs that overflowed went with their cache entries (their
    # code lies in device memory and they never start again): one is left
    assert [dict(k[1]) for k in ex._jit_cache] == [r1.caps]
    r2 = ex.execute(plan, {"sales": sales, "dims": dims})
    assert r2.attempts == 1                   # grown caps were remembered
    assert r2.caps == r1.caps and r2.jit_cache_hits == 1
    assert r2.compact().to_pydict() == r1.compact().to_pydict()


def test_capped_caps_memo_never_undersizes_larger_inputs():
    """The memo skips re-learning, it must not UNDERSIZE: a plan learned
    on small inputs still derives its defaults from the bigger inputs."""
    small_sales, dims = _tables(n=64)
    sales, _ = _tables(n=4000)
    plan = _plan()
    ex = PlanExecutor(mode="capped", max_cap_attempts=4)
    ex.execute(plan, {"sales": small_sales, "dims": dims})
    ref = PlanExecutor().execute(plan, {"sales": sales, "dims": dims})
    res = ex.execute(plan, {"sales": sales, "dims": dims})
    assert res.attempts == 1                  # floored at the new defaults
    assert res.compact().to_pydict() == ref.table.to_pydict()


def test_capped_bytes_metrics_track_input_shape():
    """Re-running a cached plan with a previously-seen shape must report
    THAT shape's bytes, not the most recent trace's."""
    sales, dims = _tables(n=400)
    big_sales, _ = _tables(n=800)
    plan = _plan()
    ex = PlanExecutor(mode="capped")
    r_small = ex.execute(plan, {"sales": sales, "dims": dims})
    ex.execute(plan, {"sales": big_sales, "dims": dims})
    r_again = ex.execute(plan, {"sales": sales, "dims": dims})
    scan = next(m for m in r_small.metrics.values() if m.kind == "Scan"
                and "sales" in m.describe)
    scan2 = next(m for m in r_again.metrics.values() if m.kind == "Scan"
                 and "sales" in m.describe)
    assert scan2.bytes_out == scan.bytes_out


def test_capped_program_cache_reused():
    sales, dims = _tables()
    plan = _plan()
    ex = PlanExecutor(mode="capped")
    r1 = ex.execute(plan, {"sales": sales, "dims": dims})
    n_cached = len(ex._jit_cache)
    r2 = ex.execute(plan, {"sales": sales, "dims": dims})
    assert len(ex._jit_cache) == n_cached         # same program, no re-trace
    assert r1.compact().to_pydict() == r2.compact().to_pydict()


# ---- faultinj: operator faults surface as plan-level retries ----------------

def _write_cfg(tmp_path, cfg):
    p = tmp_path / "faultinj.json"
    p.write_text(json.dumps(cfg))
    return str(p)


@pytest.fixture
def _clean_faultinj():
    yield
    faultinj.uninstall()


def test_injected_operator_fault_retries_eager(tmp_path, _clean_faultinj):
    sales, dims = _tables()
    plan = _plan()
    ref = PlanExecutor().execute(plan, {"sales": sales, "dims": dims})
    faultinj.install(_write_cfg(tmp_path, {"computeFaults": {
        "plan.HashJoin": {"percent": 100, "injectionType": 1,
                          "interceptionCount": 1}}}))
    res = PlanExecutor().execute(plan, {"sales": sales, "dims": dims})
    # the fault became a bounded operator re-run, not corruption
    assert res.table.to_pydict() == ref.table.to_pydict()
    join = next(m for m in res.metrics.values() if m.kind == "HashJoin")
    assert join.retries == 1


def test_injected_operator_fault_retries_capped(tmp_path, _clean_faultinj):
    sales, dims = _tables()
    plan = _plan()
    ref = PlanExecutor().execute(plan, {"sales": sales, "dims": dims})
    faultinj.install(_write_cfg(tmp_path, {"computeFaults": {
        "plan.HashAggregate": {"percent": 100, "injectionType": 1,
                               "interceptionCount": 1}}}))
    res = PlanExecutor(mode="capped").execute(
        plan, {"sales": sales, "dims": dims})
    assert res.retries == 1                       # plan-level re-run
    assert res.compact().to_pydict() == ref.table.to_pydict()


def test_retry_exhaustion_reraises(tmp_path, _clean_faultinj):
    # degrade="off": exhausted retries propagate (legacy failure behavior)
    sales, dims = _tables()
    faultinj.install(_write_cfg(tmp_path, {"computeFaults": {
        "plan.HashJoin": {"percent": 100, "injectionType": 1}}}))
    with pytest.raises(faultinj.DeviceAssertError):
        PlanExecutor(op_retries=2, degrade="off").execute(
            _plan(), {"sales": sales, "dims": dims})


def test_retry_exhaustion_degrades_to_cpu(tmp_path, _clean_faultinj):
    # default policy: a persistently failing operator classifies STICKY,
    # trips the breaker, and the plan still completes on the CPU tier
    sales, dims = _tables()
    plan = _plan()
    ref = PlanExecutor().execute(plan, {"sales": sales, "dims": dims})
    faultinj.install(_write_cfg(tmp_path, {"computeFaults": {
        "plan.HashJoin": {"percent": 100, "injectionType": 1}}}))
    res = PlanExecutor(op_retries=2).execute(
        plan, {"sales": sales, "dims": dims})
    assert res.degraded
    assert res.breaker["state"] == "open"
    assert res.breaker["reason"] == "sticky"
    assert res.table.to_pydict() == ref.table.to_pydict()
    join = next(m for m in res.metrics.values() if m.kind == "HashJoin")
    assert join.retries > 0 and join.degraded and join.backoff_ms > 0


def test_fatal_fault_propagates_not_retried(tmp_path, _clean_faultinj):
    sales, dims = _tables()
    faultinj.install(_write_cfg(tmp_path, {"computeFaults": {
        "plan.HashJoin": {"percent": 100, "injectionType": 0,
                          "interceptionCount": 1}}}))
    # fatal poisons the device: no device retry may run (stop-on-dead-
    # device); with degradation off the fault propagates
    with pytest.raises(faultinj.DeviceFatalError):
        PlanExecutor(degrade="off").execute(
            _plan(), {"sales": sales, "dims": dims})
    assert faultinj.active().device_poisoned


def test_poisoned_device_degrades_every_plan(tmp_path, _clean_faultinj):
    """Poisoned-device case: after a fatal fault, EVERY intercepted device
    call fails fast — a fresh executor (fresh breaker) must still classify
    fatal on first touch and complete degraded, without device retries."""
    sales, dims = _tables()
    plan = _plan()
    ref = PlanExecutor().execute(plan, {"sales": sales, "dims": dims})
    faultinj.install(_write_cfg(tmp_path, {"computeFaults": {
        "plan.HashJoin": {"percent": 100, "injectionType": 0,
                          "interceptionCount": 1}}}))
    res1 = PlanExecutor().execute(plan, {"sales": sales, "dims": dims})
    assert res1.degraded and res1.breaker["reason"] == "fatal"
    assert res1.table.to_pydict() == ref.table.to_pydict()
    assert faultinj.active().device_poisoned
    # new executor, same dead device: the very first plan-level point
    # raises DeviceFatalError and the whole plan runs on the CPU tier
    res2 = PlanExecutor().execute(plan, {"sales": sales, "dims": dims})
    assert res2.degraded and res2.breaker["reason"] == "fatal"
    assert res2.table.to_pydict() == ref.table.to_pydict()
    join = next(m for m in res2.metrics.values() if m.kind == "HashJoin")
    assert join.retries == 0          # no retry storms against a dead device


def test_mid_plan_fault_attaches_partial_metrics(tmp_path, _clean_faultinj):
    """A failed plan is still debuggable: the raised exception carries the
    per-op metrics collected before the failure (err.plan_metrics)."""
    sales, dims = _tables()
    plan = _plan()
    faultinj.install(_write_cfg(tmp_path, {"computeFaults": {
        "plan.HashAggregate": {"percent": 100, "injectionType": 1}}}))
    with pytest.raises(faultinj.DeviceAssertError) as ei:
        PlanExecutor(degrade="off").execute(
            plan, {"sales": sales, "dims": dims})
    got = ei.value.plan_metrics
    done_kinds = {m.kind for m in got.values()}
    assert {"Scan", "Filter", "HashJoin", "Project"} <= done_kinds
    assert "HashAggregate" not in done_kinds      # the op that failed
    join = next(m for m in got.values() if m.kind == "HashJoin")
    assert join.rows_out > 0 and join.wall_ms is not None


# ---- distributed tier (Exchange + HashAggregate over the mesh) --------------

@pytest.mark.slow     # one whole-plan SPMD trace: minutes of jax tracing,
# excluded from the timed tier-1 verify like the distributed-tier suites
def test_exchange_aggregate_runs_distributed_and_matches_local():
    from spark_rapids_tpu.parallel import make_mesh
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    mesh = make_mesh(8)
    rng = np.random.default_rng(3)
    n = 8 * 512
    t = Table([_col(rng.integers(0, 100, n)),
               _col(rng.integers(-1000, 1000, n))], names=["k", "v"])
    b = PlanBuilder()
    rel = (b.scan("t").exchange(keys=["k"])
            .aggregate(["k"], [("v", "sum", "s"), ("v", "max", "mx"),
                               ("v", "count", "c")])
            .sort(["k"]))
    plan = rel.build()
    res = PlanExecutor(mesh=mesh).execute(plan, {"t": t})
    # oracle: the local tier of the same plan (no mesh -> Exchange no-ops)
    ref = PlanExecutor().execute(plan, {"t": t})
    assert res.table.to_pydict() == ref.table.to_pydict()
    agg = next(m for m in res.metrics.values() if m.kind == "HashAggregate")
    assert agg.escalations == 0


# ---- admission integration --------------------------------------------------

def test_executor_session_scopes_admission():
    """`session=` scopes a DeviceSession to the execution: the plan's
    kernels acquire budget through the arbiter (runtime/admission.py) and
    release it when the outputs die."""
    from spark_rapids_tpu.runtime import DeviceSession
    sales, dims = _tables(n=500)
    plan = _plan()
    with DeviceSession(device_limit_bytes=64 * 1024 * 1024,
                       watchdog=False) as session:
        res = PlanExecutor(session=session).execute(
            plan, {"sales": sales, "dims": dims})
        assert session.device.used > 0       # outputs hold reservations
        ref = _oracle(sales, dims)
        assert res.table.to_pydict()["total"] == ref["total"].tolist()
        del res
        import gc
        gc.collect()
        assert session.device.used == 0      # all reservations released


def test_anti_join_both_tiers():
    sales, dims = _tables(n=400)
    b = PlanBuilder()
    s = b.scan("sales", schema=["k", "v"])
    d = b.scan("dims", schema=["dk", "grp"]).filter(col("grp") == 1)
    plan = (s.join(d, left_on="k", right_on="dk", how="left_anti")
             .aggregate([], [("v", "count", "n")]).build())
    res = PlanExecutor().execute(plan, {"sales": sales, "dims": dims})
    keep = set(np.asarray(dims["dk"].data)[np.asarray(dims["grp"].data) == 1])
    ref = int(sum(1 for k in np.asarray(sales["k"].data) if k not in keep))
    assert res.table.to_pydict() == {"n": [ref]}
    resc = PlanExecutor(mode="capped").execute(
        plan, {"sales": sales, "dims": dims})
    assert resc.compact().to_pydict() == {"n": [ref]}


def test_node_level_cap_override_escalates():
    """A per-node row_cap/key_cap override is a STARTING value: it rides
    the shared escalation dict, so an undersized override grows
    geometrically instead of livelocking through identical attempts."""
    sales, dims = _tables(n=1000)
    b = PlanBuilder()
    s = b.scan("sales", schema=["k", "v"])
    d = b.scan("dims", schema=["dk", "grp"]).filter(col("grp") == 1)
    plan = (s.join(d, left_on="k", right_on="dk", row_cap=8)
             .aggregate(["grp"], [("v", "sum", "t")], key_cap=4)
             .build())
    ref = PlanExecutor().execute(plan, {"sales": sales, "dims": dims})
    ex = PlanExecutor(mode="capped", max_cap_attempts=10)
    res = ex.execute(plan, {"sales": sales, "dims": dims})
    assert res.attempts > 1
    # per-node caps key on the EXECUTED plan's toposort index (stable
    # across fingerprint-equal rebuilds, unlike labels)
    join_idx = next(i for i, n in enumerate(res.plan.nodes)
                    if getattr(n, "row_cap", None) is not None)
    assert res.caps[f"row_cap:{join_idx}"] > 8
    assert res.compact().to_pydict() == ref.table.to_pydict()


def test_scalar_agg_as_bare_projection():
    b = PlanBuilder()
    plan = (b.scan("t", schema=["v"])
             .project({"m": scalar_max(col("v")), "v": col("v")})
             .build())
    t = Table([_col([3, 9, 1])], names=["v"])
    res = PlanExecutor().execute(plan, {"t": t})
    assert res.table.to_pydict() == {"m": [9, 9, 9], "v": [3, 9, 1]}
    resc = PlanExecutor(mode="capped").execute(plan, {"t": t})
    assert resc.compact().to_pydict() == res.table.to_pydict()


def test_capped_executor_rejects_mesh_per_plan():
    """mesh + mode="capped" is a PER-PLAN error now: only a plan that
    actually contains a distributed-lowerable operator is rejected, and
    the error names the offending node; a trivial row-wise plan runs
    capped (the mesh is irrelevant to it)."""
    from spark_rapids_tpu.plan import PlanValidationError
    ex = PlanExecutor(mode="capped", mesh=object())   # no blanket raise
    sales, dims = _tables(n=100)
    with pytest.raises(PlanValidationError,
                       match=r"HashJoin#\d+.*eager tier"):
        ex.execute(_plan(), {"sales": sales, "dims": dims})
    b = PlanBuilder()
    rowwise = (b.scan("sales", schema=["k", "v"])
                .filter(col("v") > 0).limit(5).build())
    res = ex.execute(rowwise, {"sales": sales})
    assert res.compact().num_rows <= 5
