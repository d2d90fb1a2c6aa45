"""Operator-level CPU/TPU co-placement: the optimizer's `placement`
rule (plan/optimizer.py, docs/optimizer.md#placement), the executor's
overlapped host-subtree dispatch (plan/executor.py `_PendingHostRel`),
the serving layer's partial-placement over-quota policy
(serving/scheduler.py, docs/serving.md#partial-placement), and the
lockdep witness proof that the overlap join adds no lock-order edges
(docs/analysis.md#concurrency-invariants)."""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, Table, dtypes, faultinj
from spark_rapids_tpu.plan import (PlanBuilder, PlanExecutor, col,
                                   optimize)
from spark_rapids_tpu.plan import stats as stats_mod


def _col(a):
    a = np.asarray(a, dtype=np.int64)
    return Column(dtype=dtypes.INT64, length=len(a), data=jnp.asarray(a))


def _tables(n=4000, seed=0):
    rng = np.random.default_rng(seed)
    sales = Table([_col(rng.integers(0, 50, n)),
                   _col(rng.integers(1, 100, n))], names=["k", "v"])
    dims = Table([_col(np.arange(50)), _col(np.arange(50) % 3)],
                 names=["dk", "grp"])
    return sales, dims


def _plan():
    """Probe (sales, filtered on device) joins a dims build side whose
    scan+filter subtree is the placement candidate."""
    b = PlanBuilder()
    s = b.scan("sales", schema=["k", "v"]).filter(col("v") > 10)
    d = b.scan("dims", schema=["dk", "grp"]).filter(col("grp") >= 0)
    return (s.join(d, left_on="k", right_on="dk")
             .aggregate(["grp"], [("v", "sum", "total")])
             .sort(["grp"])
             .build())


def _bindings(sales, dims):
    """The binding kwargs execute() passes optimize() — the certified
    cold path needs dtypes to price the subtree's output bytes."""
    inputs = {"sales": sales, "dims": dims}
    return dict(
        bound={n: tuple(t.names) for n, t in inputs.items()},
        bound_rows={n: t.num_rows for n, t in inputs.items()},
        input_dtypes={n: {cn: c.dtype
                          for cn, c in zip(t.names, t.columns)}
                      for n, t in inputs.items()})


def _placed_ops(res):
    return sorted(l for l, m in res.metrics.items()
                  if m.placement == "host")


@pytest.fixture
def _placement_on(monkeypatch):
    monkeypatch.setenv("SPARK_RAPIDS_TPU_PLACEMENT", "on")


@pytest.fixture
def _no_store():
    with stats_mod.scoped_store(None):
        yield


# ---- the optimizer rule -----------------------------------------------------

class TestPlacementRule:
    def test_certified_build_side_places(self, _no_store):
        sales, dims = _tables()
        plan = _plan()
        opt, report = optimize(plan, placement=True,
                               **_bindings(sales, dims))
        assert report.placements, report.decision_sources
        (label, where), = report.placements.items()
        assert where == "host"
        # the annotated root is the join's build side
        join = next(n for n in opt.nodes if n.kind == "HashJoin")
        assert join.right.label == label
        src = report.decision_sources[f"{join.label}/placement"]
        assert src.startswith("host (certified:")

    def test_pure_annotation_tree_and_fingerprint_unchanged(self,
                                                            _no_store):
        from spark_rapids_tpu.plan import plan_fingerprint
        sales, dims = _tables()
        plan = _plan()
        opt_off, rep_off = optimize(plan, placement=False,
                                    **_bindings(sales, dims))
        opt_on, rep_on = optimize(plan, placement=True,
                                  **_bindings(sales, dims))
        assert not rep_off.placements and rep_on.placements
        # label-independent structural identity: compiled-program memos
        # key on this, so placement can never fork the program cache
        assert plan_fingerprint(opt_on) == plan_fingerprint(opt_off)
        assert [n.kind for n in opt_on.nodes] == \
            [n.kind for n in opt_off.nodes]

    def test_byte_threshold_keeps(self, _no_store):
        sales, dims = _tables()
        plan = _plan()
        _, report = optimize(plan, placement=True, placement_bytes=1,
                             **_bindings(sales, dims))
        assert not report.placements
        assert any(v.startswith("keep (certified:")
                   for k, v in report.decision_sources.items()
                   if k.endswith("/placement"))

    def test_shared_build_side_declines(self, _no_store):
        """A DAG-shared dimension (q5's shape) must never place: another
        consumer would synchronously read the deferred subtree."""
        sales, dims = _tables()
        b = PlanBuilder()
        d = b.scan("dims", schema=["dk", "grp"]).filter(col("grp") >= 0)
        s = b.scan("sales", schema=["k", "v"])
        s1 = s.join(d, left_on="k", right_on="dk")
        s2 = s.filter(col("v") > 50).join(d, left_on="k", right_on="dk")
        plan = (s1.union(s2)
                  .aggregate(["grp"], [("v", "sum", "t")]).build())
        _, report = optimize(plan, placement=True,
                             **_bindings(sales, dims))
        assert not report.placements

    def test_single_node_build_side_skipped(self, _no_store):
        """A bare scan has no host compute to overlap — only a round
        trip; the rule records no decision at all for it."""
        sales, dims = _tables()
        b = PlanBuilder()
        plan = (b.scan("sales", schema=["k", "v"])
                 .join(b.scan("dims", schema=["dk", "grp"]),
                       left_on="k", right_on="dk")
                 .aggregate(["grp"], [("v", "sum", "t")]).build())
        _, report = optimize(plan, placement=True,
                             **_bindings(sales, dims))
        assert not report.placements

    def test_warm_observed_wall_decides(self, _no_store):
        """After one placed run the stats store holds the subtree's
        wall under BOTH backends (the dispatch files host walls under
        "cpu"), and the warm decision source flips to observed."""
        sales, dims = _tables()
        inputs = {"sales": sales, "dims": dims}
        store = stats_mod.StatsStore(capacity=8, path="")
        with stats_mod.scoped_store(store):
            os.environ["SPARK_RAPIDS_TPU_PLACEMENT"] = "on"
            try:
                r1 = PlanExecutor(mode="eager").execute(_plan(), inputs)
                assert _placed_ops(r1)
                r2 = PlanExecutor(mode="eager").execute(_plan(), inputs)
            finally:
                os.environ.pop("SPARK_RAPIDS_TPU_PLACEMENT", None)
        srcs = [v for k, v in
                (r2.optimizer or {}).get("decision_sources").items()
                if k.endswith("/placement")]
        assert srcs and all("observed" in s for s in srcs), srcs


# ---- executor dispatch ------------------------------------------------------

class TestCoPlacementExecution:
    def test_parity_and_host_stamps(self, monkeypatch, _no_store):
        sales, dims = _tables()
        inputs = {"sales": sales, "dims": dims}
        plan = _plan()
        monkeypatch.setenv("SPARK_RAPIDS_TPU_PLACEMENT", "off")
        off = PlanExecutor(mode="eager").execute(plan, inputs)
        assert not _placed_ops(off)
        monkeypatch.setenv("SPARK_RAPIDS_TPU_PLACEMENT", "on")
        on = PlanExecutor(mode="eager").execute(plan, inputs)
        assert _placed_ops(on)
        assert on.table.to_pydict() == off.table.to_pydict()

    def test_overlap_stamped_on_consumer(self, _placement_on, _no_store):
        sales, dims = _tables()
        inputs = {"sales": sales, "dims": dims}
        res = PlanExecutor(mode="eager").execute(_plan(), inputs)
        placed = _placed_ops(res)
        assert placed, (res.optimizer or {}).get("decision_sources")
        # every placed op ran on the host thread and pinned cpu kernels
        for l in placed:
            assert res.metrics[l].placement == "host"
        join = next(m for m in res.metrics.values()
                    if m.kind == "HashJoin")
        # the join consumed the pending handle: overlap is measured
        # there (>= 0 by construction; > 0 is a timing, which no unit
        # test asserts)
        assert join.placement_overlap_ms >= 0.0
        assert res.optimizer["rules_fired"].get("placement", 0) >= 1

    def test_placement_off_is_default(self, _no_store):
        sales, dims = _tables()
        res = PlanExecutor(mode="eager").execute(
            _plan(), {"sales": sales, "dims": dims})
        assert not _placed_ops(res)
        assert not (res.optimizer or {}).get("placements")

    def test_profile_renders_placement(self, _placement_on, _no_store):
        sales, dims = _tables()
        res = PlanExecutor(mode="eager").execute(
            _plan(), {"sales": sales, "dims": dims})
        assert _placed_ops(res)
        assert "placement" in res.profile_text()


# ---- fault semantics on the host thread -------------------------------------

def _write_cfg(tmp_path, cfg):
    p = tmp_path / "faultinj.json"
    p.write_text(json.dumps(cfg))
    return str(p)


@pytest.fixture
def _clean_faultinj():
    yield
    faultinj.uninstall()


class TestHostFaults:
    def test_host_fault_retries_at_consumer(self, tmp_path,
                                            _clean_faultinj,
                                            _placement_on, _no_store):
        """Fault injection stays LIVE on the host thread; the failure
        surfaces at the consuming join, whose retry re-runs the subtree
        synchronously — bounded retry, not corruption. The dims build
        side holds the plan's only Filter fed by 'dims'."""
        sales, dims = _tables()
        inputs = {"sales": sales, "dims": dims}
        b = PlanBuilder()
        s = b.scan("sales", schema=["k", "v"])
        d = b.scan("dims", schema=["dk", "grp"]).filter(col("grp") >= 0)
        plan = (s.join(d, left_on="k", right_on="dk")
                 .aggregate(["grp"], [("v", "sum", "t")]).build())
        ref = PlanExecutor(mode="eager", optimize=False).execute(
            plan, inputs)
        faultinj.install(_write_cfg(tmp_path, {"computeFaults": {
            "plan.Filter": {"percent": 100, "injectionType": 1,
                            "interceptionCount": 1}}}))
        res = PlanExecutor(mode="eager").execute(plan, inputs)
        assert res.table.to_pydict() == ref.table.to_pydict()
        assert not res.degraded
        join = next(m for m in res.metrics.values()
                    if m.kind == "HashJoin")
        assert join.retries >= 1

    def test_fatal_mid_flight_salvage_drains(self, tmp_path,
                                             _clean_faultinj,
                                             _placement_on, _no_store):
        """A fatal device fault at the join (host subtree resolved or
        in flight) trips the breaker; the degraded salvage drains the
        pending host work and still produces the exact result."""
        sales, dims = _tables()
        inputs = {"sales": sales, "dims": dims}
        plan = _plan()
        ref = PlanExecutor(mode="eager", optimize=False).execute(
            plan, inputs)
        faultinj.install(_write_cfg(tmp_path, {"computeFaults": {
            "plan.HashJoin": {"percent": 100, "injectionType": 0,
                              "interceptionCount": 1}}}))
        res = PlanExecutor(mode="eager").execute(plan, inputs)
        assert res.degraded and res.breaker["reason"] == "fatal"
        assert res.table.to_pydict() == ref.table.to_pydict()
        faultinj.active().reset_device()


# ---- serving-forced placement (execute(placement=...) + remap) --------------

def _serving_shape(n_fact=50_000, n_probe=200, seed=1):
    """Build side = scan -> aggregate -> sort -> limit: the certified
    peak (the aggregate's residency) sits INSIDE the offloadable
    subtree, so partial placement can shrink the device footprint."""
    rng = np.random.default_rng(seed)
    fact = Table([_col(rng.integers(0, 3000, n_fact)),
                  _col(rng.integers(1, 50, n_fact))],
                 names=["fk", "fv"])
    probe = Table([_col(rng.integers(0, 3000, n_probe)),
                   _col(rng.integers(1, 9, n_probe))],
                  names=["k", "pv"])
    b = PlanBuilder()
    build = (b.scan("fact", schema=["fk", "fv"])
              .aggregate(["fk"], [("fv", "sum", "s")])
              .sort(["s"]).limit(10))
    plan = (b.scan("probe", schema=["k", "pv"])
             .join(build, left_on="k", right_on="fk")
             .build())
    return plan, {"fact": fact, "probe": probe}


class TestForcedPlacement:
    def test_forced_label_remaps_across_rewrite(self, _no_store):
        """The authored build root (Limit) is rewritten to TopK; the
        scan-source remap still lands the offload on the rebuilt
        subtree, and results stay bit-exact."""
        plan, inputs = _serving_shape()
        limit = next(n for n in plan.nodes if n.kind == "Limit")
        ref = PlanExecutor(mode="eager").execute(plan, inputs)
        res = PlanExecutor(mode="eager").execute(
            plan, inputs, placement=(limit.label,))
        placed = _placed_ops(res)
        assert placed and any(
            res.metrics[l].kind == "TopK" for l in placed), placed
        assert res.table.to_pydict() == ref.table.to_pydict()

    def test_unknown_label_silently_skipped(self, _no_store):
        plan, inputs = _serving_shape()
        ref = PlanExecutor(mode="eager").execute(plan, inputs)
        res = PlanExecutor(mode="eager").execute(
            plan, inputs, placement=("NoSuchNode#999",))
        assert not _placed_ops(res)
        assert res.table.to_pydict() == ref.table.to_pydict()


class TestServingPartial:
    def test_over_quota_partial_splits(self, _no_store):
        """A submit that can never fit whole-plan device quota executes
        with the heavy build subtree on host threads and the join on
        device — charge_source "partial", NOT the whole-plan CPU pin."""
        from spark_rapids_tpu.serving import ServingScheduler
        plan, inputs = _serving_shape()
        ref = PlanExecutor(mode="eager").execute(plan, inputs)
        sched = ServingScheduler(over_quota="partial",
                                 quota_bytes=2_000_000)
        try:
            s = sched.open_session("tenant-a")
            t = s.submit(plan, inputs)
            res = t.result(timeout=120)
        finally:
            sched.close()
        assert t.charge_source == "partial"
        assert not res.degraded
        placed = _placed_ops(res)
        assert placed, "partial policy placed nothing"
        device = [l for l, m in res.metrics.items()
                  if m.placement != "host"]
        assert any(res.metrics[l].kind == "HashJoin" for l in device)
        assert res.table.to_pydict() == ref.table.to_pydict()

    def test_degrade_policy_contrast_pins_whole_plan(self, _no_store):
        """Same shape, same quota, degrade policy: the legacy cliff —
        whole plan on the CPU tier, degraded=True. The partial test
        above is exactly this submission rescued onto the device."""
        from spark_rapids_tpu.serving import ServingScheduler
        plan, inputs = _serving_shape()
        ref = PlanExecutor(mode="eager").execute(plan, inputs)
        sched = ServingScheduler(over_quota="degrade",
                                 quota_bytes=2_000_000)
        try:
            s = sched.open_session("tenant-b")
            t = s.submit(plan, inputs)
            res = t.result(timeout=120)
        finally:
            sched.close()
        assert res.degraded
        assert not _placed_ops(res)
        assert res.table.to_pydict() == ref.table.to_pydict()

    def test_no_viable_split_falls_back_to_cpu(self, _no_store):
        """Quota below every possible device remainder: partial finds
        no split and degrades to the CPU pin instead of rejecting."""
        from spark_rapids_tpu.serving import ServingScheduler
        plan, inputs = _serving_shape()
        ref = PlanExecutor(mode="eager").execute(plan, inputs)
        sched = ServingScheduler(over_quota="partial", quota_bytes=1)
        try:
            s = sched.open_session("tenant-c")
            t = s.submit(plan, inputs)
            res = t.result(timeout=120)
        finally:
            sched.close()
        assert t.charge_source != "partial"
        assert res.degraded
        assert res.table.to_pydict() == ref.table.to_pydict()


# ---- concurrency: the overlap join adds no lock-order edges -----------------

class TestPlacementLockdep:
    def test_overlap_join_adds_no_lock_edges(self, monkeypatch,
                                             _no_store):
        """The co-placement join is lock-free by contract (a bare
        Thread.join, no engine lock held): under the lockdep witness, a
        placed run must add ZERO lock-order edge classes beyond the
        device-only baseline, and no cycles ever."""
        from spark_rapids_tpu.runtime import lockdep as ld
        sales, dims = _tables()
        inputs = {"sales": sales, "dims": dims}
        plan = _plan()
        installed = not ld.active()
        if installed:
            ld.install()
        try:
            monkeypatch.setenv("SPARK_RAPIDS_TPU_PLACEMENT", "off")
            PlanExecutor(mode="eager").execute(plan, inputs)
            baseline = set(ld.snapshot()["edges"])
            monkeypatch.setenv("SPARK_RAPIDS_TPU_PLACEMENT", "on")
            res = PlanExecutor(mode="eager").execute(plan, inputs)
            assert _placed_ops(res)
            after = ld.snapshot()
        finally:
            if installed:
                ld.uninstall()
        new = set(after["edges"]) - baseline
        assert not new, f"co-placement introduced lock edges: {new}"
        assert after["cycles"] == []
