"""Multi-tenant serving layer tests (serving/scheduler.py, docs/serving.md).

Unit level: fair-share dispatch (priority lanes, weighted DRR, the
starvation aging bound), bounded-queue backpressure in both postures,
quota admission (certified charge, reject + degrade policies), the
result cache (keying, TTL, copy isolation), and breaker-open drain +
half-open recovery under queued load.

Acceptance (the PR's tier-1 gate): >= 8 concurrent sessions submitting a
mixed NDS q3/q5 workload under a seeded faultinj config (transient storm
+ ONE fatal) — every session's every result bit-exact against solo
execution, no session starves (bounded max queue wait), over-quota plans
reject with an operator/session-labelled diagnostic before compilation,
and the result cache serves >= 1 parity-checked hit.
"""
import json
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, Table, dtypes, faultinj
from spark_rapids_tpu.plan import PlanBuilder, PlanExecutor, col
from spark_rapids_tpu.runtime.health import (CLOSED, HALF_OPEN,
                                             DeviceHealthMonitor)
from spark_rapids_tpu.serving import (ResultCache, ServingRejectedError,
                                      ServingScheduler, cache_key,
                                      cached_copy)


def _col(a):
    a = np.asarray(a, dtype=np.int64)
    return Column(dtype=dtypes.INT64, length=len(a), data=jnp.asarray(a))


def _table(n=400, seed=0):
    rng = np.random.default_rng(seed)
    return Table([_col(rng.integers(0, 50, n)),
                  _col(rng.integers(1, 100, n))], names=["k", "v"])


def _plan():
    b = PlanBuilder()
    return (b.scan("t", schema=["k", "v"]).filter(col("v") > 10)
            .aggregate(["k"], [("v", "sum", "total")])
            .sort(["k"]).build())


@pytest.fixture
def _clean_faultinj():
    yield
    faultinj.uninstall()


class _GateExecutor(PlanExecutor):
    """Executor whose first `hold` executions block on a gate and which
    records execution order — the deterministic lever for queue-shape
    tests (backpressure, aging) without sleeps-as-synchronization."""

    def __init__(self, hold=0, **kw):
        super().__init__(**kw)
        self.gate = threading.Event()
        self.order = []
        self._hold = hold
        self._seen = 0
        self._gate_lock = threading.Lock()

    def execute(self, plan, inputs=None, tier=None):
        from spark_rapids_tpu.runtime import sessionctx
        with self._gate_lock:
            self._seen += 1
            blocked = self._seen <= self._hold
        if blocked:
            assert self.gate.wait(timeout=30), "gate never released"
        self.order.append(sessionctx.current_session_id())
        return super().execute(plan, inputs, tier=tier)

    def wait_dispatched(self, n=1, timeout=5.0):
        """Block until `n` executions have ENTERED execute() — the
        deterministic 'worker holds the head job' precondition (without
        it, later submissions race the worker's first pick)."""
        t0 = time.monotonic()
        while self._seen < n:
            assert time.monotonic() - t0 < timeout, "dispatch never came"
            time.sleep(0.005)


# ---- fair share / stamps ----------------------------------------------------

def test_sessions_share_executor_with_parity_and_stamps():
    plan, t = _plan(), _table()
    ref = PlanExecutor(mode="eager").execute(plan, {"t": t}).table.to_pydict()
    with ServingScheduler(workers=3, cache_entries=0) as sched:
        handles = [sched.open_session(f"tenant-{i}") for i in range(4)]
        tickets = [h.submit(plan, {"t": t}) for h in handles for _ in range(2)]
        for tk in tickets:
            res = tk.result(timeout=120)
            assert res.table.to_pydict() == ref
            assert res.session == tk.session
            assert all(m.session == tk.session
                       for m in res.metrics.values())
            assert not res.cached
        m = sched.metrics()
        for i in range(4):
            s = m["sessions"][f"tenant-{i}"]
            assert s["submitted"] == s["completed"] == 2
            assert s["failed"] == s["rejected"] == 0


def test_weighted_fair_share_dispatch_order():
    """With one worker and a gated head job, a weight-3 session should
    dispatch ~3x the plans of a weight-1 session over the drained
    backlog (deficit round-robin, same lane)."""
    plan, t = _plan(), _table()
    ex = _GateExecutor(hold=1, mode="eager")
    with ServingScheduler(ex, workers=1, cache_entries=0,
                          starvation_ms=0) as sched:
        heavy = sched.open_session("heavy", weight=3.0)
        light = sched.open_session("light", weight=1.0)
        first = light.submit(plan, {"t": t})   # occupies the worker
        ex.wait_dispatched(1)
        hv = [heavy.submit(plan, {"t": t}) for _ in range(6)]
        lt = [light.submit(plan, {"t": t}) for _ in range(6)]
        ex.gate.set()
        for tk in [first] + hv + lt:
            tk.result(timeout=120)
        # drop the gated head; inspect the drained backlog's first 4
        order = ex.order[1:]
        assert order.count("heavy") == order.count("light") == 6
        head = order[:4]
        assert head.count("heavy") >= 2, (
            f"weight-3 session under-served in {order}")


def test_priority_lane_outranks_batch():
    """Interactive jobs queued behind a gated worker dispatch before
    batch jobs enqueued EARLIER (strict lanes; aging disabled)."""
    plan, t = _plan(), _table()
    ex = _GateExecutor(hold=1, mode="eager")
    with ServingScheduler(ex, workers=1, cache_entries=0,
                          starvation_ms=0) as sched:
        batch = sched.open_session("batch", priority="batch")
        inter = sched.open_session("inter", priority="interactive")
        first = batch.submit(plan, {"t": t})      # occupies the worker
        ex.wait_dispatched(1)
        b = [batch.submit(plan, {"t": t}) for _ in range(3)]
        i = [inter.submit(plan, {"t": t}) for _ in range(3)]
        ex.gate.set()
        for tk in [first] + b + i:
            tk.result(timeout=120)
        assert ex.order[1:4] == ["inter"] * 3, ex.order


def test_starvation_bound_ages_batch_job_past_lanes():
    """A batch job waiting past the starvation bound dispatches BEFORE
    younger interactive jobs — weighted lanes may skew throughput, never
    unbound a session's queue wait."""
    plan, t = _plan(), _table()
    ex = _GateExecutor(hold=1, mode="eager")
    with ServingScheduler(ex, workers=1, cache_entries=0,
                          starvation_ms=150.0) as sched:
        batch = sched.open_session("batch", priority="batch")
        inter = sched.open_session("inter", priority="interactive")
        first = inter.submit(plan, {"t": t})      # occupies the worker
        ex.wait_dispatched(1)
        starved = batch.submit(plan, {"t": t})
        time.sleep(0.4)                            # let it age past 150ms
        younger = [inter.submit(plan, {"t": t}) for _ in range(3)]
        ex.gate.set()
        for tk in [first, starved] + younger:
            tk.result(timeout=120)
        assert ex.order[1] == "batch", ex.order
        assert sched.metrics()["sessions"]["batch"]["aged_dispatches"] >= 1


# ---- backpressure -----------------------------------------------------------

def test_backpressure_blocks_then_drains():
    plan, t = _plan(), _table()
    ex = _GateExecutor(hold=1, mode="eager")
    with ServingScheduler(ex, workers=1, queue_depth=2,
                          cache_entries=0) as sched:
        s = sched.open_session("s")
        first = s.submit(plan, {"t": t})          # dispatched (gated)
        ex.wait_dispatched(1)
        queued = [s.submit(plan, {"t": t}) for _ in range(2)]  # fills queue
        done = threading.Event()
        extra = {}

        def blocked_submit():
            extra["ticket"] = s.submit(plan, {"t": t}, block=True)
            done.set()

        th = threading.Thread(target=blocked_submit)
        th.start()
        assert not done.wait(timeout=0.3), \
            "submit should have blocked on the full queue"
        ex.gate.set()                              # drain
        assert done.wait(timeout=60)
        th.join()
        for tk in [first] + queued + [extra["ticket"]]:
            assert tk.result(timeout=120) is not None


def test_backpressure_fast_reject_is_typed():
    plan, t = _plan(), _table()
    ex = _GateExecutor(hold=1, mode="eager")
    with ServingScheduler(ex, workers=1, queue_depth=1,
                          cache_entries=0) as sched:
        s = sched.open_session("s")
        first = s.submit(plan, {"t": t})          # dispatched (gated)
        ex.wait_dispatched(1)
        second = s.submit(plan, {"t": t})         # fills the queue
        with pytest.raises(ServingRejectedError) as ei:
            s.submit(plan, {"t": t}, block=False)
        assert ei.value.reason == "queue_full"
        assert ei.value.session == "s"
        ex.gate.set()
        first.result(timeout=120), second.result(timeout=120)
        assert sched.metrics()["sessions"]["s"]["rejected"] == 1


def test_reopen_closed_session_refused_while_draining():
    """Reopening a closed id whose jobs are still queued would orphan
    them (the dispatcher discovers work only through the session map):
    the scheduler refuses until the queue drains, then allows reuse."""
    plan, t = _plan(), _table()
    ex = _GateExecutor(hold=1, mode="eager")
    with ServingScheduler(ex, workers=1, cache_entries=0) as sched:
        s = sched.open_session("dup")
        first = s.submit(plan, {"t": t})
        ex.wait_dispatched(1)
        queued = s.submit(plan, {"t": t})      # still queued (gated)
        with pytest.raises(ValueError, match="already open"):
            sched.open_session("dup")
        s.close()
        with pytest.raises(ValueError, match="draining"):
            sched.open_session("dup")
        ex.gate.set()
        for tk in (first, queued):
            assert tk.result(timeout=120) is not None   # never orphaned
        s2 = sched.open_session("dup")          # drained: reuse is fine
        assert s2.run(plan, {"t": t}, timeout=120) is not None


# ---- quota admission --------------------------------------------------------

def test_over_quota_rejects_before_compilation_with_labels():
    plan, t = _plan(), _table()
    calls = []

    class _Spy(PlanExecutor):
        def _execute(self, *a, **kw):
            calls.append(1)
            return super()._execute(*a, **kw)

    with ServingScheduler(_Spy(mode="eager"), workers=1,
                          cache_entries=0) as sched:
        tiny = sched.open_session("tiny", quota_bytes=8)
        with pytest.raises(ServingRejectedError) as ei:
            tiny.submit(plan, {"t": t})
        assert ei.value.reason == "over_quota"
        assert ei.value.session == "tiny"
        assert ei.value.operator          # names the certified-peak op
        assert "certified" in str(ei.value)
        assert not calls, "rejection must precede any execution tier"
        assert sched.metrics()["sessions"]["tiny"]["rejected"] == 1


def test_over_quota_degrade_policy_runs_cpu_tier_with_parity():
    plan, t = _plan(), _table()
    ref = PlanExecutor(mode="eager").execute(plan, {"t": t}).table.to_pydict()
    with ServingScheduler(workers=1, cache_entries=0,
                          over_quota="degrade") as sched:
        tiny = sched.open_session("tiny", quota_bytes=8)
        res = tiny.run(plan, {"t": t}, timeout=120)
        assert res.degraded and res.table.to_pydict() == ref
        assert sched.metrics()["sessions"]["tiny"]["degraded"] == 1


@pytest.mark.parametrize("how", ["environment", "constructor"])
def test_over_quota_partial_is_no_policy(monkeypatch, how):
    """`partial` went with CPU/TPU co-placement: asking for it is the
    knob's typed error, which names the two values that are left."""
    from spark_rapids_tpu import config
    with pytest.raises(ValueError, match="reject or degrade") as ei:
        if how == "environment":
            monkeypatch.setenv("SPARK_RAPIDS_TPU_SERVING_OVER_QUOTA",
                               "partial")
            config.serving_over_quota()
        else:
            ServingScheduler(workers=1, over_quota="partial")
    assert "'partial'" in str(ei.value)


def test_quota_admits_within_bound():
    plan, t = _plan(), _table()
    cert = PlanExecutor(mode="eager")._certify(
        plan, {"t": t}, {"t": tuple(t.names)})
    assert cert is not None and cert.peak_bytes_hi is not None
    with ServingScheduler(workers=1, cache_entries=0) as sched:
        s = sched.open_session("s", quota_bytes=cert.peak_bytes_hi + 1)
        assert s.run(plan, {"t": t}, timeout=120) is not None


# ---- submit-side deadlines / ticket callbacks -------------------------------

def test_deadline_expired_in_queue_rejects_typed_before_compilation():
    plan, t = _plan(), _table()
    calls = []

    class _Spy(_GateExecutor):
        def _execute(self, *a, **kw):
            calls.append(1)
            return super()._execute(*a, **kw)

    ex = _Spy(hold=1, mode="eager")
    with ServingScheduler(ex, workers=1, cache_entries=0) as sched:
        s = sched.open_session("s")
        head = s.submit(plan, {"t": t})           # dispatched (gated)
        ex.wait_dispatched(1)
        doomed = s.submit(plan, {"t": t}, timeout=0.05)
        time.sleep(0.15)                          # deadline passes queued
        ex.gate.set()
        assert head.result(timeout=120) is not None
        with pytest.raises(ServingRejectedError) as ei:
            doomed.result(timeout=120)
        assert ei.value.reason == "deadline"
        assert ei.value.session == "s"
        assert len(calls) == 1, \
            "an expired job must never reach an execution tier"
        assert doomed.queue_wait_ms > 0
        m = sched.metrics()["sessions"]["s"]
        assert m["deadline_rejects"] == 1
        assert m["rejected"] == 1
        assert m["failed"] == 0, \
            "a caller-imposed deadline is not a scheduler failure"


def test_generous_deadline_still_executes():
    plan, t = _plan(), _table()
    with ServingScheduler(workers=1, cache_entries=0) as sched:
        s = sched.open_session("s")
        res = s.run(plan, {"t": t}, timeout=120)
        assert res is not None
        m = sched.metrics()["sessions"]["s"]
        assert m["deadline_rejects"] == 0 and m["completed"] == 1


def test_ticket_done_callbacks_fire_once_outside_locks():
    plan, t = _plan(), _table()
    ex = _GateExecutor(hold=1, mode="eager")
    fired = []
    with ServingScheduler(ex, workers=1, cache_entries=0) as sched:
        s = sched.open_session("s")
        tk = s.submit(plan, {"t": t})
        ex.wait_dispatched(1)
        # pre-completion registration: fires on complete, ticket arg
        tk.add_done_callback(lambda tkt: fired.append(("pre", tkt.done())))
        tk.add_done_callback(lambda tkt: 1 / 0)    # swallowed, not fatal
        ex.gate.set()
        assert tk.result(timeout=120) is not None
        t0 = time.monotonic()
        while len(fired) < 1 and time.monotonic() - t0 < 5:
            time.sleep(0.005)
        assert fired == [("pre", True)]
        # post-completion registration: fires immediately, same thread
        tk.add_done_callback(lambda tkt: fired.append(("post", tkt.done())))
        assert fired == [("pre", True), ("post", True)]


def test_pin_cpu_submit_runs_cpu_tier_with_parity():
    plan, t = _plan(), _table()
    ref = PlanExecutor(mode="eager").execute(plan, {"t": t}).table.to_pydict()
    with ServingScheduler(workers=1, cache_entries=0) as sched:
        s = sched.open_session("s")
        res = s.run(plan, {"t": t}, timeout=120, pin_cpu=True)
        assert res.degraded and res.table.to_pydict() == ref
        assert sched.metrics()["sessions"]["s"]["degraded"] == 1


# ---- result cache -----------------------------------------------------------

def test_cache_hit_parity_copy_isolation_and_stamp():
    plan, t = _plan(), _table()
    ref = PlanExecutor(mode="eager").execute(plan, {"t": t}).table.to_pydict()
    with ServingScheduler(workers=1) as sched:
        a = sched.open_session("a")
        b = sched.open_session("b")
        cold = a.run(plan, {"t": t}, timeout=120)
        assert not cold.cached
        tk = b.submit(plan, {"t": t})
        hot = tk.result(timeout=120)
        assert tk.cached and hot.cached
        assert hot.table.to_pydict() == ref
        assert hot.session == "b"                 # re-stamped per serve
        assert all(m.session == "b" for m in hot.metrics.values())
        # copy isolation: mutating the served metrics must not bleed into
        # the cache entry (or the original run's metrics)
        for m in hot.metrics.values():
            m.wall_ms = 1e9
            m.session = "mallory"
        again = b.run(plan, {"t": t}, timeout=120)
        assert again.cached
        assert all(m.wall_ms != 1e9 and m.session == "b"
                   for m in again.metrics.values())
        assert all(m.session in ("a", "") or m.session == "a"
                   for m in cold.metrics.values())
        # ...and mutating the ORIGINAL result after completion must not
        # poison future serves either (put freezes a copy)
        for m in cold.metrics.values():
            m.rows_out = -1
        final = b.run(plan, {"t": t}, timeout=120)
        assert final.cached
        assert all(m.rows_out != -1 for m in final.metrics.values())
        assert sched.metrics()["cache"]["hits"] >= 2


def test_cache_keys_on_data_digest_not_just_fingerprint():
    plan = _plan()
    t1, t2 = _table(seed=1), _table(seed=2)
    k1, k2 = cache_key(plan, {"t": t1}), cache_key(plan, {"t": t2})
    assert k1 is not None and k2 is not None
    assert k1[0] == k2[0]          # same canonical fingerprint
    assert k1 != k2                # different data digest
    with ServingScheduler(workers=1) as sched:
        s = sched.open_session("s")
        r1 = s.run(plan, {"t": t1}, timeout=120)
        r2 = s.run(plan, {"t": t2}, timeout=120)
        assert not r1.cached and not r2.cached
        assert r1.table.to_pydict() != r2.table.to_pydict()


def test_cache_ttl_and_eviction_counters():
    clock = {"t": 0.0}
    cache = ResultCache(entries=2, ttl_s=10.0, clock=lambda: clock["t"])
    plan, t = _plan(), _table()
    res = PlanExecutor(mode="eager").execute(plan, {"t": t})
    key = cache_key(plan, {"t": t})
    cache.put(key, res)
    assert cache.get(key) is not None          # fresh: hit
    clock["t"] = 11.0
    assert cache.get(key) is None              # past TTL: expired
    st = cache.stats()
    assert st["expirations"] == 1 and st["hits"] == 1
    # LRU eviction past `entries`
    cache.put(("fp1", "d1"), res)
    cache.put(("fp2", "d2"), res)
    cache.put(("fp3", "d3"), res)
    assert cache.stats()["evictions"] == 1
    assert cache.get(("fp1", "d1")) is None


def test_cache_byte_bound_evicts_and_refuses_oversize():
    """Cached tables are live buffers no quota charges: the cache bounds
    its own resident bytes (LRU eviction past the bound) and refuses any
    single result larger than the whole budget."""
    plan, t = _plan(), _table()
    res = PlanExecutor(mode="eager").execute(plan, {"t": t})
    from spark_rapids_tpu.runtime.admission import operand_nbytes
    nbytes = operand_nbytes(res.table)
    # budget fits exactly two results: the third put evicts the oldest
    cache = ResultCache(entries=64, ttl_s=0, max_bytes=2 * nbytes + 8)
    for i in range(3):
        cache.put((f"fp{i}", "d"), res)
    st = cache.stats()
    assert st["entries"] == 2 and st["evictions"] == 1
    assert st["resident_bytes"] <= 2 * nbytes + 8
    assert cache.get(("fp0", "d")) is None       # oldest evicted
    assert cache.get(("fp2", "d")) is not None
    # a result bigger than the whole budget never caches
    small = ResultCache(entries=64, ttl_s=0, max_bytes=max(1, nbytes // 2))
    small.put(("fp", "d"), res)
    assert small.stats()["entries"] == 0
    assert small.stats()["oversize_skips"] == 1


def test_closed_drained_sessions_are_reaped():
    """A long-running scheduler serving short-lived tenants must not
    accumulate per-session state forever: closed + drained sessions
    leave the map (and metrics())."""
    plan, t = _plan(), _table()
    with ServingScheduler(workers=1, cache_entries=0) as sched:
        for i in range(5):
            s = sched.open_session(f"ephemeral-{i}")
            assert s.run(plan, {"t": t}, timeout=120) is not None
            s.close()
        assert sched.metrics()["sessions"] == {}


def test_cached_copy_never_shares_metric_objects():
    plan, t = _plan(), _table()
    res = PlanExecutor(mode="eager").execute(plan, {"t": t})
    copy = cached_copy(res)
    assert copy.cached and not res.cached
    assert copy.metrics.keys() == res.metrics.keys()
    for label in res.metrics:
        assert copy.metrics[label] is not res.metrics[label]
        assert copy.metrics[label] == res.metrics[label]


# ---- breaker-open load (satellite: overload-graceful degradation) ----------

def test_breaker_open_drains_queue_degraded_then_recovers():
    """Open breaker: queued plans drain to the CPU tier with parity (the
    queue never stalls), and half-open recovery resumes device dispatch
    without dropping queued work."""
    plan, t = _plan(), _table()
    ref = PlanExecutor(mode="eager").execute(plan, {"t": t}).table.to_pydict()
    health = DeviceHealthMonitor(probe=lambda: True, cooldown_s=0)
    ex = PlanExecutor(mode="eager", health=health)
    with ServingScheduler(ex, workers=2, cache_entries=0) as sched:
        handles = [sched.open_session(f"s{i}") for i in range(3)]
        health.trip("fatal")                   # quarantine the device
        tickets = [h.submit(plan, {"t": t}) for h in handles
                   for _ in range(2)]
        for tk in tickets:
            res = tk.result(timeout=120)       # no deadlock, no drops
            assert res.degraded
            assert res.table.to_pydict() == ref
        m = sched.metrics()
        assert sum(s["degraded"] for s in m["sessions"].values()) == 6
        assert sum(s["completed"] for s in m["sessions"].values()) == 6
        # operator intervention: half-open probation, probe closes, and
        # the very next dispatched plan runs the device tier again
        health.reset_device()
        assert health.breaker.state == HALF_OPEN
        res = handles[0].run(plan, {"t": t}, timeout=120)
        assert not res.degraded
        assert res.table.to_pydict() == ref
        assert health.breaker.state == CLOSED


def test_breaker_reopens_midload_without_dropping_queued_work():
    """Queued work submitted BEFORE a trip still completes (degraded,
    parity-exact) when the breaker opens while the queue is nonempty."""
    plan, t = _plan(), _table()
    ref = PlanExecutor(mode="eager").execute(plan, {"t": t}).table.to_pydict()
    health = DeviceHealthMonitor(probe=lambda: False, cooldown_s=0)
    ex = _GateExecutor(hold=1, mode="eager", health=health)
    with ServingScheduler(ex, workers=1, cache_entries=0) as sched:
        s = sched.open_session("s")
        first = s.submit(plan, {"t": t})       # gated on the worker
        ex.wait_dispatched(1)
        queued = [s.submit(plan, {"t": t}) for _ in range(4)]
        health.trip("sticky")                  # trips while 4 are queued
        ex.gate.set()
        for tk in [first] + queued:
            res = tk.result(timeout=120)
            assert res.table.to_pydict() == ref
        assert all(tk.result().degraded for tk in queued)


# ---- acceptance: 8 concurrent sessions, mixed NDS, chaos -------------------

def test_eight_sessions_mixed_nds_chaos_soak(tmp_path, _clean_faultinj):
    """The PR's acceptance gate (ISSUE 15): >= 8 concurrent sessions, a
    mixed NDS q3/q5 workload, seeded transient faults + ONE fatal —
    per-session bit-exact parity vs solo execution, bounded queue wait
    for every session, an over-quota reject labelled with operator +
    session before compilation, and >= 1 parity-checked cache hit."""
    from examples.nds import (q3_inputs, q3_plan, q3_tables, q5_inputs,
                              q5_plan, q5_tables)
    sales, dates3, items = q3_tables(2000, seed=7)
    tabs, dates5 = q5_tables(2000, seed=3)
    workload = {"q3": (q3_plan(), q3_inputs(sales, dates3, items)),
                "q5": (q5_plan(), q5_inputs(tabs, dates5))}
    # solo references, fault-free (and compile warm-up)
    solo = PlanExecutor(mode="eager")
    refs = {q: solo.execute(p, i).table.to_pydict()
            for q, (p, i) in workload.items()}

    cfg = {"seed": 20260805, "computeFaults": {
        "plan.HashJoin": {"percent": 15, "injectionType": 1,
                          "interceptionCount": 1000},
        "plan.Project": {"percent": 5, "injectionType": 2,
                         "substituteReturnCode": 2,
                         "interceptionCount": 1000},
        "plan.Sort": {"percent": 100, "injectionType": 0,
                      "interceptionCount": 1}}}
    path = tmp_path / "chaos.json"
    path.write_text(json.dumps(cfg))
    inj = faultinj.install(str(path))

    health = DeviceHealthMonitor(backoff_base_ms=1, backoff_max_ms=8,
                                 cooldown_s=0)
    ex = PlanExecutor(mode="eager", health=health)
    with ServingScheduler(ex, workers=3) as sched:
        handles = [sched.open_session(
            f"tenant-{i}",
            priority=("interactive" if i % 2 == 0 else "batch"),
            weight=1.0 + (i % 3),
            quota_bytes=1 << 50)   # the certifier's sound join bound is
            #                        cross-product loose on q3 — quota
            #                        sizing is the tiny-quota session's job
            for i in range(8)]
        assert len(handles) >= 8
        tickets = []
        for i, h in enumerate(handles):
            for q in (("q3", "q5") if i % 2 == 0 else ("q5", "q3")):
                plan, inputs = workload[q]
                tickets.append((h.id, q, h.submit(plan, inputs)))
        degraded = 0
        for sid, q, tk in tickets:
            res = tk.result(timeout=300)
            # bit-exact per-session parity vs solo, chaos and all
            assert res.table.to_pydict() == refs[q], \
                f"parity MISS for {sid}/{q} (degraded={res.degraded})"
            assert res.session == sid
            degraded += int(res.degraded)
        faults = inj.get_and_reset_injected()
        assert faults > 0, "chaos config injected nothing"
        assert degraded >= 1, "the fatal fault never degraded a plan"
        m = sched.metrics()
        for sid, s in m["sessions"].items():
            assert s["completed"] == 2 and s["failed"] == 0, (sid, s)
            # no session starves: queue wait bounded for every tenant
            assert s["queue_wait_ms"]["max"] < 60_000, (sid, s)
        # over-quota reject: operator/session-labelled, pre-compilation
        # (uncached inputs so the result cache cannot short-circuit)
        tiny = sched.open_session("tiny-quota", quota_bytes=64)
        s2, d2, i2 = q3_tables(512, seed=11)
        with pytest.raises(ServingRejectedError) as ei:
            tiny.submit(q3_plan(), q3_inputs(s2, d2, i2))
        assert ei.value.reason == "over_quota"
        assert ei.value.session == "tiny-quota" and ei.value.operator
        # recovery: quarantine is not permanent — stop injecting, reset
        # + half-open probe, and the device tier serves again; only
        # device-tier results populate the cache, so the parity-checked
        # hit is earned on the recovered path
        faultinj.uninstall()
        health.reset_device()
        plan, inputs = workload["q3"]
        rec = handles[0].run(plan, inputs, timeout=300)
        assert not rec.degraded
        assert rec.table.to_pydict() == refs["q3"]
        tk = handles[1].submit(plan, inputs)
        hot = tk.result(timeout=300)
        assert tk.cached and hot.cached and not hot.degraded
        assert hot.table.to_pydict() == refs["q3"]
        assert sched.metrics()["cache"]["hits"] >= 1
    # and q5 re-runs clean on the recovered device tier too
    res = ex.execute(*workload["q5"])
    assert not res.degraded
    assert res.table.to_pydict() == refs["q5"]
