"""Feedback-driven adaptive execution (plan/stats.py, docs/adaptive.md).

Covers the whole loop: stats round-trip + LRU bounds, the observed-
cardinality build-side flip (with verify_rewrite passing), cap seeding
across executor instances (zero escalation retries + a jit-cache hit on
the warm path), the kernel registry's stats tie-break and its
KernelChoice stamp, JSONL persistence on/off, stale-stats safety
(schema-changed fingerprints never match), and backend isolation — a
degraded (CPU-salvaged) run's stats must never drive device-side
decisions.

The suite-wide default is SPARK_RAPIDS_TPU_STATS=off (conftest):
everything here installs an explicit `scoped_store`, which outranks the
knob, so these tests are order-independent and leak nothing.
"""
import json

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_tpu import Column, Table, dtypes, faultinj
from spark_rapids_tpu.plan import (PlanBuilder, PlanExecutor, StatsStore,
                                   col, scoped_store,
                                   subtree_fingerprints)
from spark_rapids_tpu.plan import stats as stats_mod


def _col(a):
    a = np.asarray(a, dtype=np.int64)
    return Column(dtype=dtypes.INT64, length=len(a), data=jnp.asarray(a))


def _skew_tables(n_big=1000, n_small=1000, seed=0):
    """The skewed-join shape: the filtered side's static 0.5-selectivity
    estimate is WRONG (the filter actually keeps ~1%), so the static
    build-side rule keeps while observations swap."""
    rng = np.random.default_rng(seed)
    big = Table([_col(rng.integers(0, 10, n_big)),
                 _col(rng.integers(0, 100, n_big))], names=["k", "v"])
    small = Table([_col(rng.integers(0, 100, n_small)),
                   _col(rng.integers(0, 100, n_small))],
                  names=["sk", "sv"])
    return {"small": small, "big": big}


def _skew_plan():
    b = PlanBuilder()
    # est_rows hints mirror the bound sizes — deliberately useless: the
    # misestimate the store corrects is the FILTER's selectivity, which
    # no scan hint can express
    left = b.scan("small", schema=["sk", "sv"],
                  est_rows=1000).filter(col("sv") == 0)
    right = b.scan("big", schema=["k", "v"], est_rows=1000)
    return (left.join(right, left_on="sk", right_on="k")
                .aggregate(["sv"], [("v", "sum", "total")])
                .build())


def _fanout_tables(seed=0):
    rng = np.random.default_rng(seed)
    l = Table([_col(rng.integers(0, 20, 400)),
               _col(rng.integers(0, 100, 400))], names=["k", "v"])
    r = Table([_col(rng.integers(0, 20, 100))], names=["rk"])
    return {"l": l, "r": r}


def _fanout_plan():
    b = PlanBuilder()
    return (b.scan("l", schema=["k", "v"])
             .join(b.scan("r", schema=["rk"]), left_on="k", right_on="rk")
             .aggregate(["k"], [("v", "sum", "t")])
             .build())


# ---- store round-trip + bounds ----------------------------------------------

def test_store_round_trip_and_evict():
    store = StatsStore(capacity=2, path="")
    plans = []
    for n_cols in (2, 3, 4):        # three distinct fingerprints
        b = PlanBuilder()
        names = [f"c{i}" for i in range(n_cols)]
        plans.append(b.scan(f"s{n_cols}", schema=names)
                      .aggregate([names[0]], [(names[1], "sum", "t")])
                      .build())
    last = None
    with scoped_store(store):
        for p, n_rows in zip(plans, (40, 60, 80)):
            t = Table([_col(np.arange(n_rows) % 5)
                       for _ in range(len(p.scans[0].schema))],
                      names=list(p.scans[0].schema))
            last = PlanExecutor(mode="eager").execute(
                p, {p.scans[0].source: t})
    # lookup: the two most recent plan entries survive, the first evicted
    backend = "cpu"
    assert store.plan_runs(backend, plans[0].fingerprint) == 0
    assert store.plan_runs(backend, plans[1].fingerprint) == 1
    assert store.plan_runs(backend, plans[2].fingerprint) == 1
    # subtree observations round-trip with exact cardinalities — keyed by
    # the EXECUTED (optimizer-rewritten) plan's subtrees, which is what
    # the next optimization's fixpoint pass converges to and consults
    sub = subtree_fingerprints(last.plan.root)
    got = store.observed_rows(backend, sub[id(last.plan.root)])
    assert got is not None and got[0] == 5 and got[1] == 1  # 5 groups
    # per-op history round-trips too
    ops = store.op_stats(backend, plans[2].fingerprint)
    root_idx = len(last.plan.nodes) - 1
    assert ops[root_idx]["rows_out"] == 5
    assert ops[root_idx]["wall_ms"] is not None


# ---- observed-cardinality build-side flip -----------------------------------

def test_observed_build_side_flip_with_verified_rewrite():
    plan = _skew_plan()
    inputs = _skew_tables()
    store = StatsStore(capacity=8, path="")
    with scoped_store(store):
        cold = PlanExecutor(mode="eager").execute(plan, dict(inputs))
        assert not cold.optimizer["rules_fired"].get("build_side"), \
            "static estimates must NOT swap this join (the test's premise)"
        warm = PlanExecutor(mode="eager").execute(plan, dict(inputs))
    assert warm.optimizer["rules_fired"].get("build_side") == 1
    # decision provenance: the swap names the store as its source
    swaps = [v for k, v in warm.optimizer["decision_sources"].items()
             if k.endswith("/build_side") and v.startswith("swap")]
    assert swaps and "observed:1" in swaps[0]
    assert warm.optimizer["stats_driven"] is True
    # the rewrite passed the verify gate (VERIFY_PLANS is on suite-wide;
    # a violation would have raised) and did not fall back or revert
    assert not warm.optimizer["fell_back"]
    assert not warm.optimizer["stats_reverted"]
    # adaptivity changed HOW, never WHAT
    assert warm.compact().to_pydict() == cold.compact().to_pydict()


def test_stats_off_restores_static_decisions():
    plan = _skew_plan()
    inputs = _skew_tables()
    store = StatsStore(capacity=8, path="")
    with scoped_store(None):
        static = PlanExecutor(mode="eager").execute(plan, dict(inputs))
    with scoped_store(store):
        for _ in range(2):          # warm the store past the flip point
            PlanExecutor(mode="eager").execute(plan, dict(inputs))
    # a scoped None forces adaptivity off (the SPARK_RAPIDS_TPU_STATS=off
    # path) even though the store above holds flip-inducing observations:
    # byte-identical optimizer decisions to the never-recorded run
    with scoped_store(None):
        off = PlanExecutor(mode="eager").execute(plan, dict(inputs))
    assert off.optimizer == static.optimizer
    assert off.compact().to_pydict() == static.compact().to_pydict()


# ---- cap seeding ------------------------------------------------------------

def test_cap_seeding_skips_escalation_ladder():
    plan = _fanout_plan()
    inputs = _fanout_tables()
    store = StatsStore(capacity=8, path="")
    with scoped_store(store):
        cold_ex = PlanExecutor(mode="capped")
        cold = cold_ex.execute(plan, dict(inputs))
        assert cold.attempts > 1, \
            "fan-out join must overflow the default caps (test premise)"
        # a FRESH executor: only the store carries the escalated caps
        warm_ex = PlanExecutor(mode="capped")
        warm = warm_ex.execute(plan, dict(inputs))
        assert warm.attempts == 1          # zero cap-escalation retries
        assert warm.caps == cold.caps      # seeded at the high-water
        # the seeded caps land on the same fingerprint-keyed program, so
        # the next execute is a pure jit-cache hit
        again = warm_ex.execute(plan, dict(inputs))
        assert again.attempts == 1 and again.jit_cache_hits >= 1
    assert cold.compact().to_pydict() == warm.compact().to_pydict() \
        == again.compact().to_pydict()
    # stats off: the static ladder is back (fresh executor, no memo)
    with scoped_store(None):
        static = PlanExecutor(mode="capped").execute(plan, dict(inputs))
    assert static.attempts == cold.attempts


# ---- kernel tie-break -------------------------------------------------------

def test_kernel_tie_break_demotion_and_stamp():
    from spark_rapids_tpu.ops.registry import KernelRegistry, Signature
    reg = KernelRegistry()
    reg.register("fuzzop", "xla", fn=lambda: "xla", fallback=True)
    reg.register("fuzzop", "fancy", fn=lambda: "fancy", backends=("*",))
    sig = Signature.of(extras_tier="eager")
    store = StatsStore(capacity=8, path="")
    with scoped_store(store):
        # cold: the non-fallback candidate wins the rank order
        choice = reg.select("fuzzop", sig, backend="tpu")
        assert choice.name == "fancy" and not choice.stats_demoted
        # observed: fancy benches 5x slower than the fallback
        store.record_kernel("tpu", "fuzzop", sig, "fancy", 5.0)
        store.record_kernel("tpu", "fuzzop", sig, "xla", 1.0)
        choice = reg.select("fuzzop", sig, backend="tpu")
        assert choice.name == "xla" and choice.stats_demoted
        assert any(name == "fancy" and "stats" in why
                   for name, why in choice.declined)
        # a different signature is a different shape: no demotion
        other = Signature.of(extras_tier="capped")
        assert reg.select("fuzzop", other, backend="tpu").name == "fancy"
        # no signature at the call site: selection stays static
        assert not reg.select("fuzzop", None, backend="tpu").stats_demoted
    # store out of scope: selection is static again
    assert reg.select("fuzzop", sig, backend="tpu").name == "fancy"


def test_kernel_tie_break_hysteresis():
    from spark_rapids_tpu.ops.registry import KernelRegistry, Signature
    reg = KernelRegistry()
    reg.register("fuzzop2", "xla", fn=lambda: 0, fallback=True)
    reg.register("fuzzop2", "fancy", fn=lambda: 1, backends=("*",))
    sig = Signature.of()
    store = StatsStore(capacity=8, path="")
    with scoped_store(store):
        # 10% slower is inside the hysteresis margin: noise must not
        # flap the pick (and with it the capped tier's compiled programs)
        store.record_kernel("tpu", "fuzzop2", sig, "fancy", 1.1)
        store.record_kernel("tpu", "fuzzop2", sig, "xla", 1.0)
        assert reg.select("fuzzop2", sig, backend="tpu").name == "fancy"


def test_kernel_epoch_bumps_on_verdict_flip_without_reorder():
    """Regression: the capped tier's jit-cache key relies on
    `kernel_epoch` capturing every demotion-verdict change. An EWMA
    drift can cross the 1.25x margin WITHOUT changing the raw timing
    order — the epoch must still bump, or a compiled program keyed on
    the old epoch would keep serving the now-demoted kernel."""
    from spark_rapids_tpu.ops.registry import Signature
    store = StatsStore(capacity=8, path="")
    sig = Signature.of()
    store.record_kernel("tpu", "op", sig, "xla", 1.0)
    store.record_kernel("tpu", "op", sig, "fancy", 1.2)   # inside margin
    assert store.kernel_slower("tpu", "op", sig, "fancy", "xla") is None
    epoch = store.kernel_epoch
    # EWMA moves 1.2 -> 1.6: order unchanged (fancy was already slower),
    # but the verdict flips to demoted — the epoch must notice
    store.record_kernel("tpu", "op", sig, "fancy", 2.0)
    assert store.kernel_slower("tpu", "op", sig, "fancy", "xla") \
        is not None
    assert store.kernel_epoch > epoch


def test_fresh_store_ignores_env_persistence_path(tmp_path, monkeypatch):
    """Regression: isolated stores (the fuzzer's per-case stores, the
    adaptive bench's cold/warm pair, these tests) pass path="" and must
    neither load nor write SPARK_RAPIDS_TPU_STATS_PATH — a persisted
    file would pre-warm a run that documents itself as cold."""
    path = tmp_path / "operator.jsonl"
    monkeypatch.setenv("SPARK_RAPIDS_TPU_STATS_PATH", str(path))
    with scoped_store(StatsStore(capacity=8, path=str(path))):
        PlanExecutor(mode="eager").execute(_fanout_plan(),
                                           _fanout_tables())
    written = path.read_text()                # simulated operator state
    fresh = StatsStore(capacity=8, path="")
    assert fresh.path is None and fresh.generation == 0
    with scoped_store(fresh):
        PlanExecutor(mode="eager").execute(_fanout_plan(),
                                           _fanout_tables())
    assert path.read_text() == written        # nothing appended
    # while a path=None store DOES adopt the knob (the process default)
    assert StatsStore(capacity=8).path == str(path)


def test_eager_run_records_kernel_timings():
    b = PlanBuilder()
    plan = (b.scan("t", schema=["a", "b"])
             .filter(col("a") > 2)
             .project([("a", col("a"))])
             .build())            # select_fusion -> FusedSelect dispatch
    t = Table([_col(np.arange(50) % 7), _col(np.arange(50))],
              names=["a", "b"])
    store = StatsStore(capacity=8, path="")
    with scoped_store(store):
        res = PlanExecutor(mode="eager").execute(plan, {"t": t})
    assert any(m.kernel.endswith(":fused_select")
               for m in res.metrics.values())
    assert any(key[1] == "fused_select" for key in store._kernels), \
        "eager per-op wall should feed the kernel-timing table"


# ---- persistence ------------------------------------------------------------

def test_persistence_round_trip(tmp_path):
    path = str(tmp_path / "stats.jsonl")
    plan = _fanout_plan()
    inputs = _fanout_tables()
    st1 = StatsStore(capacity=8, path=path)
    with scoped_store(st1):
        res = PlanExecutor(mode="capped").execute(plan, dict(inputs))
    lines = [json.loads(x) for x in open(path).read().splitlines()]
    assert lines and lines[0]["backend"] == "cpu"
    # a NEW store replays the file: the warm run seeds caps from disk
    st2 = StatsStore(capacity=8, path=path)
    assert st2.observed_caps("cpu", plan.fingerprint) == dict(res.caps)
    with scoped_store(st2):
        warm = PlanExecutor(mode="capped").execute(plan, dict(inputs))
    assert warm.attempts == 1
    assert warm.compact().to_pydict() == res.compact().to_pydict()


def test_a_record_with_the_old_wall_table_still_loads(tmp_path):
    """A file written before the per-backend subtree walls went (they fed
    CPU/TPU co-placement alone) carries `subtree_walls`; the key is not
    read, and everything else of the line is."""
    path = str(tmp_path / "stats.jsonl")
    plan = _fanout_plan()
    with scoped_store(StatsStore(capacity=8, path=path)):
        res = PlanExecutor(mode="eager").execute(plan, _fanout_tables())
    (event,) = [json.loads(x) for x in open(path).read().splitlines()]
    assert "subtree_walls" not in event
    sfp = next(iter(event["subtrees"]))
    event["subtree_walls"] = {sfp: ["cpu", 1.25]}
    with open(path, "w") as f:
        f.write(json.dumps(event) + "\n")
    old = StatsStore(capacity=8, path=path)
    assert old.plan_runs("cpu", plan.fingerprint) == 1
    assert old.observed_rows("cpu", sfp) == (event["subtrees"][sfp], 1)
    root = len(res.plan.nodes) - 1
    assert old.op_stats("cpu", plan.fingerprint)[root]["rows_out"] \
        == res.table.num_rows


def test_persistence_knob_off_writes_nothing(tmp_path, monkeypatch):
    # no SPARK_RAPIDS_TPU_STATS_PATH: the store stays in-memory-only
    monkeypatch.delenv("SPARK_RAPIDS_TPU_STATS_PATH", raising=False)
    st = StatsStore(capacity=8, path="")
    assert st.path is None
    with scoped_store(st):
        PlanExecutor(mode="eager").execute(_fanout_plan(),
                                           _fanout_tables())
    assert list(tmp_path.iterdir()) == []


def test_default_store_reads_knobs(tmp_path, monkeypatch):
    path = str(tmp_path / "default.jsonl")
    monkeypatch.setenv("SPARK_RAPIDS_TPU_STATS", "on")
    monkeypatch.setenv("SPARK_RAPIDS_TPU_STATS_PATH", path)
    monkeypatch.setenv("SPARK_RAPIDS_TPU_STATS_CAPACITY", "7")
    stats_mod.reset_default_store()
    try:
        store = stats_mod.active_store()
        assert store is stats_mod.default_store()
        assert store.capacity == 7 and store.path == path
        PlanExecutor(mode="eager").execute(_fanout_plan(),
                                           _fanout_tables())
        assert open(path).read().strip()
        monkeypatch.setenv("SPARK_RAPIDS_TPU_STATS", "off")
        assert stats_mod.active_store() is None
        with pytest.raises(ValueError):
            monkeypatch.setenv("SPARK_RAPIDS_TPU_STATS", "maybe")
            stats_mod.active_store()       # strict-typo policy
    finally:
        stats_mod.reset_default_store()


# ---- stale-stats safety -----------------------------------------------------

def test_schema_changed_fingerprint_never_matches():
    def make(colname):
        b = PlanBuilder()
        return (b.scan("s", schema=["a", colname])
                 .filter(col("a") > 3)
                 .aggregate(["a"], [(colname, "sum", "t")])
                 .build())

    plan_a, plan_b = make("b"), make("c")
    assert plan_a.fingerprint != plan_b.fingerprint
    sub_a = subtree_fingerprints(plan_a.root)
    sub_b = subtree_fingerprints(plan_b.root)
    assert set(sub_a.values()).isdisjoint(sub_b.values()), \
        "a schema change must invalidate every enclosing subtree"
    # executor-level: stats recorded for A are invisible to B
    t_a = Table([_col(np.arange(60) % 9), _col(np.arange(60))],
                names=["a", "b"])
    t_b = Table([_col(np.arange(60) % 9), _col(np.arange(60))],
                names=["a", "c"])
    store = StatsStore(capacity=8, path="")
    with scoped_store(store):
        PlanExecutor(mode="eager").execute(plan_a, {"s": t_a})
        assert store.generation == 1
        for fp in sub_b.values():
            assert store.observed_rows("cpu", fp) is None
        res = PlanExecutor(mode="eager").execute(plan_b, {"s": t_b})
    assert "observed" not in "".join(
        res.optimizer["decision_sources"].values())


def test_est_rows_hint_change_still_matches():
    """`est_rows` is a pure hint (fingerprint-excluded): re-authoring the
    same plan with different hints must still hit the recorded stats —
    that is exactly the hints-are-wrong case the store corrects."""
    def make(est):
        b = PlanBuilder()
        return (b.scan("s", schema=["a", "b"], est_rows=est)
                 .filter(col("a") > 3)
                 .aggregate(["a"], [("b", "sum", "t")])
                 .build())

    sub1 = subtree_fingerprints(make(10).root)
    sub2 = subtree_fingerprints(make(999_999).root)
    assert sorted(sub1.values()) == sorted(sub2.values())


# ---- backend isolation ------------------------------------------------------

def test_store_is_backend_keyed():
    from spark_rapids_tpu.ops.registry import Signature
    store = StatsStore(capacity=8, path="")
    sig = Signature.of()
    store.record_kernel("cpu", "topk", sig, "pallas", 9.0)
    store.record_kernel("cpu", "topk", sig, "xla", 1.0)
    # cpu-recorded timings never demote on the device backend
    assert store.kernel_slower("tpu", "topk", sig, "pallas", "xla") is None
    assert store.kernel_slower("cpu", "topk", sig, "pallas", "xla") \
        is not None


def test_degraded_run_records_under_cpu_only(tmp_path):
    """Regression (ISSUE 11 satellite): a forced degraded run — the plan
    finishes on the CPU salvage tier after a fatal injected fault — must
    record its stats under backend="cpu", and those entries must never
    seed device-side caps or feed device kernel tie-breaks; the healthy
    run that follows behaves normally."""
    b = PlanBuilder()
    plan = (b.scan("l", schema=["k", "v"])
             .join(b.scan("r", schema=["rk"]), left_on="k", right_on="rk")
             .aggregate(["k"], [("v", "sum", "t")])
             .sort(["k"])
             .build())
    inputs = _fanout_tables()
    cfg = tmp_path / "faultinj.json"
    cfg.write_text(json.dumps({"computeFaults": {
        "plan.Sort": {"percent": 100, "injectionType": 0,
                      "interceptionCount": 1}}}))
    store = StatsStore(capacity=8, path="")
    try:
        faultinj.install(str(cfg))
        with scoped_store(store):
            res = PlanExecutor(mode="eager").execute(plan, dict(inputs))
        assert res.degraded
    finally:
        faultinj.uninstall()
    # everything the degraded run recorded filed under "cpu"
    assert store.generation == 1
    assert all(k[0] == "cpu" for k in store._plans)
    assert all(k[0] == "cpu" for k in store._subtrees)
    assert all(k[0] == "cpu" for k in store._kernels)
    # device-side consults see nothing from the salvage run
    assert store.observed_caps("tpu", plan.fingerprint) == {}
    sub = subtree_fingerprints(plan.root)
    assert all(store.observed_rows("tpu", fp) is None
               for fp in sub.values())
    # degraded results never contribute caps, even under "cpu" (they
    # describe failed device attempts, not a completed sizing)
    assert store.observed_caps("cpu", plan.fingerprint) == {}
    # a healthy run afterwards records and self-tunes normally
    with scoped_store(store):
        healthy = PlanExecutor(mode="eager").execute(plan, dict(inputs))
    assert not healthy.degraded and store.generation == 2


# ---- rendering --------------------------------------------------------------

def test_decision_sources_render_in_profile_and_explain():
    plan = _skew_plan()
    inputs = _skew_tables()
    store = StatsStore(capacity=8, path="")
    with scoped_store(store):
        PlanExecutor(mode="eager").execute(plan, dict(inputs))
        ex = PlanExecutor(mode="eager")
        warm = ex.execute(plan, dict(inputs))
        text = warm.profile_text()
        assert "decision" in text and "(observed:" in text
        shown = ex.explain(plan, optimized=True, inputs=dict(inputs))
        assert "decision sources" in shown and "(observed:" in shown
