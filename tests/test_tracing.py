"""Program spans and operator names (utils/tracing.py, docs/plan.md
"Reading a profile"): one span helper that records whenever a profiler
session runs, request numbers that join the submitting thread's spans to
the worker's, operator scopes inside the capped program, and
`PlanExecutor.device_op_owners`. All on the CPU with tiny plans — what the
chip's trace prints is checked in chipbench/tests/test_program_spans.py on
a trace recorded there."""
import ast
import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, Table, dtypes
from spark_rapids_tpu.plan import PlanBuilder, PlanExecutor, col
from spark_rapids_tpu.plan import stats as stats_mod
from spark_rapids_tpu.plan.executor import _scope_owners
from spark_rapids_tpu.runtime import sessionctx
from spark_rapids_tpu.serving import ServingScheduler
from spark_rapids_tpu.utils import span
from spark_rapids_tpu.utils.tracing import text

from examples.nds import q3_inputs, q3_plan

PKG = os.path.dirname(os.path.abspath(spark_rapids_tpu.__file__))


# ---- reading a session back ---------------------------------------------------

class Spans(list):
    """The program's spans of one profiler session: dicts with name,
    thread, t0, t1 (ns on the profiler's clock) and the attributes."""

    def named(self, name):
        return [s for s in self if s["name"] == name]

    def one(self, name):
        found = self.named(name)
        assert len(found) == 1, (name, len(found))
        return found[0]


def inside(child, parent) -> bool:
    return (child["thread"] == parent["thread"]
            and parent["t0"] <= child["t0"] and child["t1"] <= parent["t1"])


@pytest.fixture
def session(tmp_path):
    """-> record(fn): run `fn` under a profiler session started with the
    benchmark's options and give back the spans it left."""
    def record(fn) -> Spans:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        out = str(tmp_path / f"trace{len(os.listdir(tmp_path))}")
        jax.profiler.start_trace(out, profiler_options=opts)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        spans = Spans()
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            # a thread is a line; lines have no id, their names repeat
            for thread, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(("serving.", "plan.", "ops.",
                                          "test.")):
                        t0 = int(e.start_ns)
                        spans.append(dict(
                            dict(e.stats), name=e.name, thread=thread,
                            t0=t0, t1=t0 + int(e.duration_ns)))
        return spans
    return record


# ---- tiny plans -----------------------------------------------------------------

def _col(a):
    a = np.asarray(a, dtype=np.int64)
    return Column(dtype=dtypes.INT64, length=len(a), data=jnp.asarray(a))


def _fact(n=400, seed=0):
    rng = np.random.default_rng(seed)
    return Table([_col(rng.integers(0, 50, n)),
                  _col(rng.integers(1, 100, n))], names=["k", "v"])


def _dim():
    return Table([_col(np.arange(50)), _col(np.arange(50) % 7)],
                 names=["dk", "g"])


def _join_plan():
    b = PlanBuilder()
    fact = b.scan("t", schema=["k", "v"]).filter(col("v") > 10)
    dim = b.scan("d", schema=["dk", "g"])
    return (fact.join(dim, left_on="k", right_on="dk")
            .aggregate(["g"], [("v", "sum", "total")])
            .sort(["g"]).build())


def _tiny_q3(n=2000):
    from examples.nds import q3_tables as build_tables
    return q3_plan(), q3_inputs(*build_tables(n, seed=7))


# ---- the helper -------------------------------------------------------------------

def test_span_without_a_session_is_inert_and_cheap():
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        with span("test.inert", i=i) as sp:
            pass
    per_span_us = (time.perf_counter() - t0) / n * 1e6
    sp.set_metadata(late=1)                 # no session: nothing to record
    # 0.7-0.9 us here; the bound only has to catch a recorder sneaking in
    assert per_span_us < 20, per_span_us
    assert not hasattr(spark_rapids_tpu.utils.tracing, "enabled")


def test_session_records_name_request_and_attributes(session):
    def work():
        with sessionctx.request_scope(7), span("test.outer", rows=3) as sp:
            with span("test.inner", site="a.b"):
                time.sleep(0.001)
            sp.set_metadata(bytes=36_000_000, hit=0)
        with span("test.unscoped"):
            pass
    spans = session(work)
    outer, inner = spans.one("test.outer"), spans.one("test.inner")
    assert (outer["request"], outer["rows"], outer["bytes"],
            outer["hit"]) == (7, 3, 36_000_000, 0)
    assert inner["request"] == 7 and inner["site"] == "a.b"
    assert inside(inner, outer) and inner["t1"] - inner["t0"] >= 1_000_000
    assert spans.one("test.unscoped")["request"] == -1


def test_text_keeps_a_plan_label_from_cutting_the_attributes(session):
    assert text("HashJoin#12") == "HashJoin:12"
    def work():
        with span("test.label", label=text("HashJoin#12"), tier="host"):
            pass
    got = session(work).one("test.label")
    assert (got["label"], got["tier"]) == ("HashJoin:12", "host")


def test_request_scope_nests_and_is_per_thread():
    seen = []
    assert sessionctx.current_request() == -1
    with sessionctx.request_scope(3):
        with sessionctx.request_scope(4):
            assert sessionctx.current_request() == 4
        t = threading.Thread(
            target=lambda: seen.append(sessionctx.current_request()))
        t.start()
        t.join()
        assert sessionctx.current_request() == 3
    assert seen == [-1] and sessionctx.current_request() == -1


# ---- one serving request -------------------------------------------------------------

NESTING = [("serving.submit", ["serving.digest", "serving.admit",
                               "serving.enqueue"]),
           ("serving.dispatch", ["plan.execute"]),
           ("plan.execute", ["plan.optimize", "plan.verify", "plan.certify",
                             "plan.stats", "plan.run"]),
           ("plan.run", ["plan.attempt"])]


def test_serving_request_spans_nest_and_share_one_request(session):
    plan, inputs = _join_plan(), {"t": _fact(), "d": _dim()}
    sched = ServingScheduler(PlanExecutor(mode="capped"), workers=1,
                             stats_store=stats_mod.StatsStore())
    sess = sched.open_session("tenant")
    try:
        sess.submit(plan, inputs).result(timeout=120)     # compile outside
        tickets = []
        spans = session(lambda: tickets.append(
            sess.submit(plan, {"t": _fact(seed=1), "d": inputs["d"]}))
            or tickets[0].result(timeout=120))
    finally:
        sess.close()
        sched.close()
    request = tickets[0].request
    assert request == 1                      # the second ticket of this scheduler
    assert {s["request"] for s in spans} == {request}
    for parent, children in NESTING:
        p = spans.one(parent)
        for child in children:
            found = [c for c in spans.named(child) if inside(c, p)]
            assert found, f"no {child} inside {parent}"
    # the submitter's and the worker's spans lie on different threads
    assert spans.one("serving.submit")["thread"] \
        != spans.one("serving.dispatch")["thread"]
    # admission certifies on the submitting thread, execute on the worker
    certs = spans.named("plan.certify")
    assert len(certs) == 2
    assert sum(inside(c, spans.one("serving.admit")) for c in certs) == 1
    digest = spans.one("serving.digest")
    # the fresh fact table is folded where it lives: its bytes are
    # counted as before, none of them crosses to the host
    assert digest["hit"] == 0 and digest["bytes"] == 400 * 8 * 2
    assert digest["host_bytes"] == 0
    assert spans.one("serving.dispatch")["queue_wait_ms"] >= 0
    attempt = spans.one("plan.attempt")
    assert (attempt["attempt"], attempt["hit"]) == (1, 1)


def test_repeat_submit_digests_nothing_and_hits(session):
    plan, inputs = _join_plan(), {"t": _fact(), "d": _dim()}
    sched = ServingScheduler(PlanExecutor(mode="eager"), workers=1)
    sess = sched.open_session("tenant")
    try:
        sess.submit(plan, inputs).result(timeout=120)
        spans = session(lambda: sess.submit(plan, inputs).result(timeout=60))
    finally:
        sess.close()
        sched.close()
    digest = spans.one("serving.digest")
    assert (digest["hit"], digest["bytes"], digest["host_bytes"]) == (1, 0, 0)
    assert not spans.named("serving.admit") and not spans.named("plan.execute")


def test_concurrent_requests_get_different_numbers(session):
    plan, dim = _join_plan(), _dim()
    sched = ServingScheduler(PlanExecutor(mode="capped"), workers=2)
    sess = sched.open_session("tenant")
    tickets = []

    def submit(seed):
        t = sess.submit(plan, {"t": _fact(seed=seed), "d": dim})
        tickets.append(t)
        t.result(timeout=120)

    def both():
        threads = [threading.Thread(target=submit, args=(s,)) for s in (1, 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    try:
        submit(0)                                         # compile outside
        del tickets[:]
        spans = session(both)
    finally:
        sess.close()
        sched.close()
    numbers = {t.request for t in tickets}
    assert len(numbers) == 2
    for name in ("serving.submit", "serving.dispatch", "plan.execute",
                 "plan.attempt"):
        assert {s["request"] for s in spans.named(name)} == numbers, name


def test_direct_execute_takes_the_executors_own_numbers(session):
    plan, inputs = _join_plan(), {"t": _fact(), "d": _dim()}
    ex = PlanExecutor(mode="capped")
    ex.execute(plan, inputs)
    spans = session(lambda: [ex.execute(plan, inputs) for _ in range(2)])
    assert [s["request"] for s in spans.named("plan.execute")] == [1, 2]
    assert {s["request"] for s in spans} == {1, 2}
    # under a scope (a serving worker's) the scoped number wins
    with sessionctx.request_scope(41):
        scoped = session(lambda: ex.execute(plan, inputs))
    assert {s["request"] for s in scoped} == {41}


# ---- the tiers ------------------------------------------------------------------------

def test_escalated_capped_run_shows_two_attempts_under_one_run(session):
    plan, inputs = _join_plan(), {"t": _fact(), "d": _dim()}
    ex = PlanExecutor(mode="capped", caps={"row_cap": 128, "key_cap": 4})
    res = []
    spans = session(lambda: res.append(ex.execute(plan, inputs)))
    assert res[0].attempts >= 2
    run = spans.one("plan.run")
    attempts = spans.named("plan.attempt")
    assert [a["attempt"] for a in attempts] \
        == list(range(1, res[0].attempts + 1))
    assert all(inside(a, run) for a in attempts)
    assert all(a["hit"] == 0 for a in attempts)      # each cap set compiled


def test_eager_tier_one_op_span_per_operator_and_the_joins_host_sync(session):
    plan, inputs = _join_plan(), {"t": _fact(), "d": _dim()}
    ex = PlanExecutor(mode="eager")
    res = []
    spans = session(lambda: res.append(ex.execute(plan, inputs)))
    ops = spans.named("plan.op")
    nodes = res[0].plan.nodes
    assert [o["op"] for o in ops] == [f"{i}.{n.kind}"
                                      for i, n in enumerate(nodes)]
    assert [o["label"] for o in ops] == [text(n.label) for n in nodes]
    assert {o["tier"] for o in ops} == {"device"}
    assert all(inside(o, spans.one("plan.run")) for o in ops)
    (join,) = [o for o in ops if o["op"].endswith(".HashJoin")]
    syncs = spans.named("ops.host_sync")
    assert syncs and all(any(inside(s, o) for o in ops) for s in syncs)
    in_join = [s for s in syncs if inside(s, join)]
    assert in_join and {s["site"] for s in in_join} \
        <= {"join.inner", "join_pallas.inner", "gather.has_negative"}
    # the filter packs the rows it keeps and the aggregate reads its group
    # count: each a number the next shape waits for
    assert {"gather.kept_rows", "groupby.groups"} \
        <= {s["site"] for s in syncs}
    assert {s["request"] for s in syncs} == {join["request"]}


def test_cpu_tier_op_spans_say_degraded(session):
    plan, inputs = _join_plan(), {"t": _fact(), "d": _dim()}
    ex = PlanExecutor(mode="eager")
    spans = session(lambda: ex.execute(plan, inputs, tier="cpu"))
    ops = spans.named("plan.op")
    assert ops and {o["tier"] for o in ops} == {"degraded"}


# ---- the leaves of a request (PR 38) ----------------------------------------------

CAPPED_LEAVES = ["plan.bind", "plan.caps", "plan.program", "plan.launch",
                 "plan.wait", "plan.readback", "plan.result", "plan.result"]
LEAF_PARENT = {"plan.bind": "plan.execute", "plan.caps": "plan.run",
               "plan.program": "plan.attempt", "plan.launch": "plan.attempt",
               "plan.wait": "plan.run", "plan.readback": "plan.run"}


def _leaves_of_a_capped_request(spans):
    """The capped tier's leaves in the order they ran, each inside the
    bracket docs/plan.md names; `plan.result` twice: the tier's epilogue
    inside `plan.run`, the stamps after it."""
    leaves = sorted((s for s in spans if s["name"] in set(CAPPED_LEAVES)),
                    key=lambda s: s["t0"])
    assert [s["name"] for s in leaves] == CAPPED_LEAVES
    for leaf in leaves[:-2]:
        assert inside(leaf, spans.one(LEAF_PARENT[leaf["name"]])), leaf
    run, execute = spans.one("plan.run"), spans.one("plan.execute")
    epilogue, stamps = leaves[-2:]
    assert inside(epilogue, run) and inside(stamps, execute)
    assert stamps["t0"] >= run["t1"]
    assert all(inside(st, execute) and st["t0"] >= stamps["t1"]
               for st in spans.named("plan.stats") if st["t0"] > run["t1"])
    nodes = len(_join_plan().nodes)
    # two scalars an operator, the decimal overflow's pair and a join's tail
    assert spans.one("plan.readback")["scalars"] >= 2 * nodes + 2
    assert spans.one("plan.wait")["site"] == "capped"
    # the overflow flag is the read that waits for the program
    (flag,) = [s for s in spans.named("ops.host_sync")
               if s["site"] == "autoretry.overflow"]
    assert inside(flag, run) and spans.one("plan.launch")["t1"] \
        <= flag["t0"] and flag["t1"] <= spans.one("plan.wait")["t0"]
    return leaves


def test_capped_request_shows_its_leaves_once_and_in_order(session):
    plan, inputs = _join_plan(), {"t": _fact(), "d": _dim()}
    ex = PlanExecutor(mode="capped")
    ex.execute(plan, inputs)                              # compile outside
    spans = session(lambda: ex.execute(plan, inputs))
    leaves = _leaves_of_a_capped_request(spans)
    assert {s["request"] for s in leaves} \
        == {spans.one("plan.execute")["request"]}
    assert spans.one("plan.launch")["hit"] == 1


def test_served_request_has_consult_and_complete_around_execute(session):
    plan, inputs = _join_plan(), {"t": _fact(), "d": _dim()}
    sched = ServingScheduler(PlanExecutor(mode="capped"), workers=1,
                             stats_store=stats_mod.StatsStore())
    sess = sched.open_session("tenant")
    try:
        sess.submit(plan, inputs).result(timeout=120)     # compile outside
        tickets = []
        spans = session(lambda: tickets.append(
            sess.submit(plan, {"t": _fact(seed=1), "d": inputs["d"]}))
            or tickets[0].result(timeout=120))
    finally:
        sess.close()
        sched.close()
    _leaves_of_a_capped_request(spans)
    dispatch, execute = spans.one("serving.dispatch"), \
        spans.one("plan.execute")
    consult, complete = spans.one("serving.consult"), \
        spans.one("serving.complete")
    assert inside(consult, dispatch) and inside(complete, dispatch)
    assert consult["t1"] <= execute["t0"] and execute["t1"] <= complete["t0"]
    assert {consult["request"], complete["request"], execute["request"]} \
        == {tickets[0].request}
    assert consult["thread"] != spans.one("serving.submit")["thread"]
    # the digest's 16 bytes are a wait on the device like any other
    (read,) = [s for s in spans.named("ops.host_sync")
               if s["site"] == "digest"]
    assert inside(read, spans.one("serving.digest"))


def test_a_cache_hit_at_dispatch_still_completes_under_its_span(session):
    """The worker's two leaves hold the whole of `serving.dispatch` when
    the dispatch-time consult answers and nothing executes."""
    plan, inputs = _join_plan(), {"t": _fact(), "d": _dim()}
    sched = ServingScheduler(PlanExecutor(mode="eager"), workers=1)
    sess = sched.open_session("tenant")
    try:
        sess.submit(plan, inputs).result(timeout=120)
        job_key = []
        real_get = sched.cache.get

        def miss_at_submit(key, **kw):     # the twin was still queued
            job_key.append(key)
            return real_get(key, **kw) if kw else None
        sched.cache.get = miss_at_submit
        done = []
        spans = session(lambda: done.append(
            sess.submit(plan, inputs)) or done[0].result(timeout=60))
    finally:
        sess.close()
        sched.close()
    assert done[0].cached and not spans.named("plan.execute")
    dispatch = spans.one("serving.dispatch")
    assert inside(spans.one("serving.consult"), dispatch)
    assert inside(spans.one("serving.complete"), dispatch)


def test_eager_request_has_one_wait_under_every_operator(session):
    plan, inputs = _join_plan(), {"t": _fact(), "d": _dim()}
    ex = PlanExecutor(mode="eager")
    ex.execute(plan, inputs)
    spans = session(lambda: ex.execute(plan, inputs))
    ops, waits = spans.named("plan.op"), spans.named("plan.wait")
    assert ops
    for op in ops:
        mine = [w for w in waits if inside(w, op) and w["site"] == "op"]
        assert len(mine) == 1, op["op"]
        # the operator's last act: nothing of its own follows the wait
        assert op["t1"] - mine[0]["t1"] < 2_000_000, op["op"]
    # the aggregate's kernel blocks inside its own span as well
    (g,) = spans.named("ops.groupby")
    assert [w["site"] for w in waits if inside(w, g)] == ["groupby"]
    # and so does the join, inside its
    (j,) = spans.named("ops.join")
    assert [w["site"] for w in waits if inside(w, j)] == ["join"]
    assert len(waits) == len(ops) + 2
    # the epilogue and the stamps: the eager tier's two `plan.result`
    results = spans.named("plan.result")
    assert len(results) == 2 and inside(results[0], spans.one("plan.run"))
    assert inside(spans.one("plan.bind"), spans.one("plan.execute"))
    assert not spans.named("plan.caps") and not spans.named("plan.readback")


def test_cpu_tier_waits_and_result_take_the_same_names(session):
    plan, inputs = _join_plan(), {"t": _fact(), "d": _dim()}
    ex = PlanExecutor(mode="eager")
    spans = session(lambda: ex.execute(plan, inputs, tier="cpu"))
    ops = spans.named("plan.op")
    assert [w["site"] for w in spans.named("plan.wait")
            if w["site"] not in ("groupby", "join")] \
        == ["degraded_op"] * len(ops)
    assert len(spans.named("plan.result")) == 2


def _fresh_capped_plan(tag: int):
    """A plan no executor has seen: the literal is part of its
    fingerprint."""
    b = PlanBuilder()
    return (b.scan("t", schema=["k", "v"]).filter(col("v") > 10 + tag)
            .aggregate(["k"], [("v", "sum", "total")]).build())


def test_lowerings_land_on_the_span_that_caused_them(session):
    plan, inputs = _fresh_capped_plan(1), {"t": _fact()}
    ex = PlanExecutor(mode="capped")
    done = []
    cold = session(lambda: done.append(ex.execute(plan, inputs)))
    warm = session(lambda: done.append(ex.execute(plan, inputs)))
    first, second = done
    assert first.lowerings >= 1 and first.lowering_ms > 0
    assert (second.lowerings, second.lowering_ms) == (0, 0.0)
    execute = cold.one("plan.execute")
    assert execute["lowerings"] == first.lowerings
    assert abs(execute["lowering_ms"] - first.lowering_ms) < 0.01
    missed = [a for a in cold.named("plan.attempt") if a["hit"] == 0]
    assert missed and sum(a["lowerings"] for a in missed) >= 1
    assert any("capped_plan" in a["lowered"] for a in missed)
    # a bracket's numbers include its children's, and no more
    assert sum(a["lowerings"] for a in cold.named("plan.attempt")) \
        <= execute["lowerings"]
    assert "lowered" in execute and "," not in execute["lowered"]
    w = warm.one("plan.execute")
    assert (w["lowerings"], w["lowering_ms"]) == (0, 0) and "lowered" not in w
    (a,) = warm.named("plan.attempt")
    assert (a["hit"], a["lowerings"]) == (1, 0)


def test_an_eager_operator_carries_the_lowering_of_its_new_program(session):
    """A shape no program of this process has seen: the per-operator
    program of the filter is lowered under that operator's `plan.op`."""
    n = 1237                                    # no other test's length
    plan, inputs = _fresh_capped_plan(2), {"t": _fact(n=n)}
    ex = PlanExecutor(mode="eager")
    done = []
    spans = session(lambda: done.append(ex.execute(plan, inputs)))
    ops = spans.named("plan.op")
    assert all("lowerings" in o for o in ops)
    assert sum(o["lowerings"] for o in ops) >= 1
    assert done[0].lowerings >= sum(o["lowerings"] for o in ops)
    assert done[0].lowerings == spans.one("plan.execute")["lowerings"]
    again = ex.execute(plan, inputs)
    assert again.lowerings == 0


def test_two_threads_lowerings_do_not_mix():
    """The pair is per thread: a request that compiles beside one that
    does not leaves the other's result at 0 (no profiler needed)."""
    warm_plan, inputs = _fresh_capped_plan(3), {"t": _fact()}
    ex = PlanExecutor(mode="capped")
    ex.execute(warm_plan, inputs)
    cold, warm, stop = [], [], threading.Event()

    def compile_one():
        try:
            cold.append(ex.execute(_fresh_capped_plan(4), inputs))
        finally:
            stop.set()

    def run_warm():
        while not stop.is_set() or not warm:
            warm.append(ex.execute(warm_plan, inputs))
    threads = [threading.Thread(target=f) for f in (run_warm, compile_one)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert cold[0].lowerings >= 1
    assert warm and {r.lowerings for r in warm} == {0}
    assert {r.lowering_ms for r in warm} == {0.0}


# ---- names inside the capped program ------------------------------------------------------

@pytest.fixture(scope="module")
def capped_q3():
    plan, inputs = _tiny_q3()
    ex = PlanExecutor(mode="capped")
    res = ex.execute(plan, inputs)
    return ex, plan, inputs, res


def test_capped_program_names_every_operator_of_q3(capped_q3):
    ex, plan, inputs, res = capped_q3
    ((fn, _, _),) = [v for v in ex._jit_cache.values()]
    hlo = fn.lower(dict(inputs)).compile().as_text()
    assert hlo.startswith("HloModule jit_capped_plan")
    for i, node in enumerate(res.plan.nodes):
        scope = f"jit(capped_plan)/{i}.{node.kind}/"
        if node.kind in ("Scan", "Project"):
            continue     # binding a table and renaming columns compute
            #              nothing: no instruction carries their scope
        assert scope in hlo, scope


def test_device_op_owners_gives_a_sort_and_a_fusion_to_a_join(capped_q3):
    ex, plan, inputs, res = capped_q3
    lowered = len(ex._jit_cache)
    owners = ex.device_op_owners(plan, inputs)
    assert len(ex._jit_cache) == lowered      # the program execute() ran
    scopes = {f"{i}.{n.kind}" for i, n in enumerate(res.plan.nodes)}
    assert owners and set(owners.values()) <= scopes
    joins = {name for name, o in owners.items() if o.endswith(".HashJoin")}
    assert any(n.startswith("sort") for n in joins), sorted(joins)[:20]
    assert any("fusion" in n for n in joins), sorted(joins)[:20]
    assert any(o.endswith(".HashAggregate") for o in owners.values())


def test_device_op_sources_names_the_line_that_traced_a_sort(capped_q3):
    """What an instruction is, beside whose: the union sort of a join is
    `sort` traced in ops/join.py:_union_sort, under its join's scope."""
    ex, plan, inputs, res = capped_q3
    sources = ex.device_op_sources(plan, inputs)
    owners = ex.device_op_owners(plan, inputs)
    assert {n: o for n, (o, _, _) in sources.items() if o} \
        == {n: o for n, o in owners.items() if n in sources}
    union = [(owner, prim, where) for owner, prim, where in sources.values()
             if where.endswith(" _union_sort") and prim == "sort"]
    assert union and all(o.endswith(".HashJoin") for o, _, _ in union)
    file_line = union[0][2].split(" ")[0]
    assert file_line.startswith("ops/join.py:") \
        and int(file_line.split(":")[1]) > 0


def test_plan_execute_span_counts_the_joins_by_tail(session):
    """Which tail a capped sort join took (ops/join.py decides on the
    device) lands on `plan.execute` beside `decimal_overflow_rows`, and on
    the join's kernel label: a dimension with one row a key is
    many-to-one, the same dimension twice over fans out."""
    plan, ex = _join_plan(), PlanExecutor(mode="capped")
    twice = Table([_col(np.arange(100) % 50), _col(np.arange(100) % 7)],
                  names=["dk", "g"])
    for dim, tails, label in ((_dim(), (1, 0), "xla:hash_join/unique"),
                              (twice, (0, 1), "xla:hash_join/expand")):
        inputs = {"t": _fact(), "d": dim}
        ex.execute(plan, inputs)                          # compile outside
        done = []
        spans = session(lambda: done.append(ex.execute(plan, inputs)))
        got = spans.one("plan.execute")
        assert (got["unique_joins"], got["expand_joins"]) == tails
        assert (done[0].unique_joins, done[0].expand_joins) == tails
        assert [m.kernel for m in done[0].metrics.values()
                if m.kind == "HashJoin"] == [label]


@pytest.mark.parametrize("plan_is", ["a_few_keys_against_a_fact", "q3"])
def test_plan_execute_span_counts_the_small_side_joins(session, plan_is):
    """An eager join with a side of a few rows against a large one takes
    the small-side path (ops/join.py): `lookup_joins` and `lookup_compares`
    (small rows x large rows) on `plan.execute`, on the result, and
    `xla:lookup` as the join's kernel. q3's shape (a fact of 2,000
    rows, under the path's floor) takes none: both read 0 and the join
    keeps the registry's label."""
    from spark_rapids_tpu.ops.join_lookup import LOOKUP_LARGE
    if plan_is == "q3":
        (plan, inputs), want = _tiny_q3(), (0, 0)
        labels = ["xla:hash_join"] * 2
    else:
        n = LOOKUP_LARGE + 10           # rows the plan's filter leaves
        plan, want = _join_plan(), (1, 50 * n)
        inputs = {"t": Table([_col(np.arange(n) % 5000),
                              _col(np.arange(n) + 11)],
                             names=["k", "v"]), "d": _dim()}
        labels = ["xla:lookup"]
    ex = PlanExecutor(mode="eager")
    ex.execute(plan, inputs)                              # compile outside
    done = []
    spans = session(lambda: done.append(ex.execute(plan, inputs)))
    res, got = done[0], spans.one("plan.execute")
    assert (got["lookup_joins"], got["lookup_compares"]) == want
    assert (res.lookup_joins, res.lookup_compares) == want
    joins = [m for m in res.metrics.values() if m.kind == "HashJoin"]
    assert [m.kernel for m in joins] == labels
    assert sum(m.lookup_compares for m in joins) == want[1]
    if want[0]:
        assert "kernel: xla:lookup" in res.profile_text()
        assert spans.named("ops.host_sync") and any(
            s.get("site") == "join.lookup" for s in spans.named("ops.host_sync"))


@pytest.mark.parametrize("tier, keeps", [("eager", "most"), ("eager", "few"),
                                         ("eager", "all"), ("capped", "most")])
def test_filter_span_says_how_its_rows_moved(session, monkeypatch, tier,
                                             keeps):
    """An eager `Filter` / `FusedSelect` compacts (ops/gather.py): its
    `plan.op` span and its metrics say which way the rows went
    (`compact`, with `rows_in` / `rows_out`), and `plan.execute` and the
    result count the compactions and the frame rows that went through a
    sort or by the kept rows' positions. A filter that keeps every row
    moves none and is not counted; in the capped tier a filter is a mask:
    no attribute, every counter 0. (The floor under which every frame goes
    by positions is lowered: 400 rows lie under it.)"""
    from spark_rapids_tpu.ops import gather
    monkeypatch.setattr(gather, "KEPT_FLOOR", 0)
    n = 400
    v = {"most": np.arange(n) % 100 + 1, "all": np.full(n, 50),
         "few": np.where(np.arange(n) % 100 == 0, 50, 0)}[keeps]
    inputs = {"t": Table([_col(np.arange(n) % 50), _col(v)],
                         names=["k", "v"]), "d": _dim()}
    kept = int((v > 10).sum())
    path = {"most": "sort", "few": "positions", "all": "none"}[keeps]
    plan = _join_plan()
    ex = PlanExecutor(mode=tier, **({"caps": dict(row_cap=512, key_cap=16)}
                                    if tier == "capped" else {}))
    ex.execute(plan, inputs)                              # compile outside
    done = []
    spans = session(lambda: done.append(ex.execute(plan, inputs)))
    res, got = done[0], spans.one("plan.execute")
    want = {"eager": {"sort": (1, n, 0), "positions": (1, 0, n),
                      "none": (0, 0, 0)}[path], "capped": (0, 0, 0)}[tier]
    assert (got["compactions"], got["compact_sorted_rows"],
            got["compact_position_rows"]) == want
    assert (res.compactions, res.compact_sorted_rows,
            res.compact_position_rows) == want
    filters = [m for m in res.metrics.values()
               if m.kind in ("Filter", "FusedSelect")]
    assert len(filters) == 1
    ops_said = [o for o in spans.named("plan.op") if "compact" in o]
    if tier == "capped":
        assert not ops_said and filters[0].compact == ""
        return
    (op,) = ops_said
    assert op["op"].split(".")[1] == filters[0].kind
    assert (op["compact"], op["rows_in"], op["rows_out"]) == (path, n, kept)
    assert filters[0].compact == path
    assert [m.compact for m in res.metrics.values()
            if m is not filters[0]] == [""] * (len(res.metrics) - 1)


@pytest.mark.parametrize("tier", ["eager", "capped"])
def test_groupby_span_and_the_requests_group_counters(session, tier):
    """A keyed aggregate's kernel and finish run inside `ops.groupby`
    (rows, groups, kernel, planes), below the operator; `plan.execute`
    carries the rows in, the groups out and the slots the finish ran
    over, summed over the request's keyed aggregates: the groups in the
    eager tier, the key cap in the capped one. The capped tier's program
    is traced once, so its span is a trace-time one and its name is the
    scope `device_op_owners(nested=True)` reads back."""
    plan, inputs = _join_plan(), {"t": _fact(), "d": _dim()}
    caps = dict(row_cap=512, key_cap=16)
    ex = PlanExecutor(mode=tier, **({"caps": caps} if tier == "capped"
                                    else {}))
    ex.execute(plan, inputs)                              # compile outside
    done = []
    spans = session(lambda: done.append(ex.execute(plan, inputs)))
    res, got = done[0], spans.one("plan.execute")
    (agg,) = [m for m in res.metrics.values() if m.kind == "HashAggregate"]
    assert (res.group_rows, res.groups) == (agg.rows_in, agg.rows_out) \
        and res.groups == 7
    assert res.group_slots == (7 if tier == "eager" else 16)
    assert (got["group_rows"], got["groups"], got["group_slots"]) \
        == (res.group_rows, res.groups, res.group_slots)
    # a frame of a few hundred rows: the one key column (a plane) is
    # gathered through the groups' first rows, over the finish's slots
    assert got["group_key_slots_gathered"] == res.group_slots \
        == res.group_key_slots_gathered == agg.key_slots_gathered
    if tier == "eager":
        (op,) = [o for o in spans.named("plan.op")
                 if o["op"].endswith(".HashAggregate")]
        g = spans.one("ops.groupby")
        assert inside(g, op) and g["request"] == op["request"]
        assert (g["rows"], g["groups"], g["planes"]) == (agg.rows_in, 7, 0)
        assert g["kernel"] in ("scan", "scatter") and g["keys"] == "take"
    else:
        assert not spans.named("ops.groupby")     # one program, run warm
        owners = ex.device_op_owners(plan, inputs, nested=True)
        held = {o for o in owners.values() if o.endswith("/ops.groupby")}
        assert len(held) == 1 and held.pop().split("/")[0] \
            .endswith(".HashAggregate")


@pytest.mark.parametrize("tier,key_cap,keys", [
    ("eager", None, "ride"), ("eager", None, "take"),
    ("capped", 64, "ride"), ("capped", 16, "take")])
def test_groupby_says_how_its_keys_came_back(session, monkeypatch, tier,
                                             key_cap, keys):
    """`ops.groupby` says `keys=`: `ride` (the `scan` kernel's compaction
    sort carried the key operands; nothing gathered), `take` (through the
    groups' first rows) or `ride+take`; `plan.execute` counts the key
    planes x slots gathered, `group_key_slots_gathered`, 0 where every
    key rode. The choice is `ops/gather.py:words_ride`'s arithmetic: the
    eager tier rides over `KEPT_FLOOR` rows (lowered here), the capped
    one where the cap's slots are dear enough beside the frame's rows (a
    frame of 512: a cap of 64 rides, one of 16 gathers 16 slots). The
    capped program's span is a trace-time one: the run is a cold one."""
    from spark_rapids_tpu.ops import gather
    monkeypatch.setenv("SPARK_RAPIDS_TPU_GROUPBY_KERNEL", "scan")
    if (tier, keys) != ("eager", "take"):
        monkeypatch.setattr(gather, "KEPT_FLOOR", 0)
    plan, inputs = _join_plan(), {"t": _fact(), "d": _dim()}
    ex = PlanExecutor(mode=tier, **({"caps": dict(row_cap=512,
                                                   key_cap=key_cap)}
                                    if tier == "capped" else {}))
    done = []
    spans = session(lambda: done.append(ex.execute(plan, inputs)))
    res, got = done[0], spans.one("plan.execute")
    (agg,) = [m for m in res.metrics.values() if m.kind == "HashAggregate"]
    assert {g["keys"] for g in spans.named("ops.groupby")} == {keys}
    want = 0 if keys == "ride" else res.group_slots
    assert (got["group_key_slots_gathered"], res.group_key_slots_gathered,
            agg.key_slots_gathered) == (want,) * 3
    assert res.groups == 7 and res.compact()["g"].to_pylist() \
        == list(range(7))
    # a warm run of the capped program says the same without a trace
    again = ex.execute(plan, inputs)
    assert again.group_key_slots_gathered == want


def _outer_plan():
    b = PlanBuilder()
    return (b.scan("d", schema=["dk", "g"])
            .join(b.scan("t", schema=["k", "v"]).filter(col("v") > 10),
                  left_on="dk", right_on="k", how="left_outer")
            .aggregate(["dk"], [("v", "count", "n")])
            .sort(["dk"]).build())


def _outer_inputs():
    # keys 0..49 on the left, 0..29 on the right: twenty rows null-extended
    # at least
    fact = _fact()
    return {"d": _dim(), "t": Table([_col(np.asarray(fact["k"].data) % 30),
                                     fact["v"]], names=["k", "v"])}


@pytest.mark.parametrize("tier", ["eager", "capped", "cpu"])
def test_join_span_and_the_requests_outer_join_counters(session, tier):
    """An eager join's maps, its output columns' gathers and the wait for
    them run inside `ops.join` (how, rows_left, rows_right, matched,
    unmatched, kernel), below the operator, whose `plan.op` says `how`
    too; the request's `plan.execute` carries `outer_joins` and
    `outer_unmatched_rows` in every tier; the capped tier's join is the
    scope `<idx>.HashJoin` of its one program, as before."""
    plan, inputs = _outer_plan(), _outer_inputs()
    ex = PlanExecutor(mode="capped" if tier == "capped" else "eager",
                      **({"caps": dict(row_cap=1024, key_cap=64)}
                         if tier == "capped" else {}))
    run = lambda: ex.execute(plan, inputs,
                             tier="cpu" if tier == "cpu" else None)
    run()                                                 # compile outside
    done = []
    spans = session(lambda: done.append(run()))
    res, got = done[0], spans.one("plan.execute")
    (join,) = [m for m in res.metrics.values() if m.kind == "HashJoin"]
    assert res.outer_joins == 1 and res.outer_unmatched_rows >= 20
    assert join.unmatched_rows == res.outer_unmatched_rows
    assert (got["outer_joins"], got["outer_unmatched_rows"]) \
        == (1, res.outer_unmatched_rows)
    if tier == "capped":
        assert not spans.named("ops.join")        # one program, run warm
        assert (got["join_planes_gathered"], res.join_slots_gathered,
                join.left_out, join.right_out) == (0, 0, "", "")
        owners = set(ex.device_op_owners(plan, inputs).values())
        assert sum(o.endswith(".HashJoin") for o in owners) == 1
        return
    (op,) = [o for o in spans.named("plan.op")
             if o["op"].endswith(".HashJoin")]
    j = spans.one("ops.join")
    assert inside(j, op) and j["request"] == op["request"]
    assert op["how"] == "left_outer"
    assert (j["how"], j["rows_left"], j["rows_right"]) \
        == ("left_outer", 50, join.rows_in - 50)
    assert (j["matched"], j["unmatched"]) \
        == (join.rows_out - join.unmatched_rows, join.unmatched_rows)
    assert j["kernel"] == join.kernel and "hash_join" in j["kernel"]
    # how each side's output columns were made (a dimension row's matches
    # fan out: the left side is gathered; 1,000 matched rows lie under
    # the floor: the right side writes them into a null frame), and the
    # planes that still went through a frame-long `take`: `d`'s
    assert (j["left_out"], j["right_out"]) == ("take", "sparse") \
        == (join.left_out, join.right_out)
    assert (got["join_planes_gathered"], got["join_slots_gathered"]) \
        == (res.join_planes_gathered, res.join_slots_gathered) \
        == (join.planes_gathered, join.planes_gathered * join.rows_out)
    assert join.planes_gathered >= 1
    # the join's one read and its wait lie inside the span
    assert [s["site"] for s in spans.named("ops.host_sync")
            if inside(s, j)] == ["join.left"]
    assert [w["site"] for w in spans.named("plan.wait")
            if inside(w, j)] == ["join"]


def test_join_span_says_the_right_side_rode_a_sort(session, monkeypatch):
    """An outer join whose counts say that every right row has one partner
    at most says `right_out=sort` on `ops.join`: the dimension's key is
    distinct, so every fact row has one slot at most (the floor under
    which every count goes by positions is lowered for the test alone).
    Only the left side's planes still count as gathered, and the join's
    one read, which brought those counts, lies inside the span."""
    from spark_rapids_tpu.ops import gather
    monkeypatch.setattr(gather, "KEPT_FLOOR", 4)
    plan, inputs = _outer_plan(), _outer_inputs()
    ex = PlanExecutor(mode="eager")
    ex.execute(plan, inputs)                              # compile outside
    done = []
    spans = session(lambda: done.append(ex.execute(plan, inputs)))
    res, got = done[0], spans.one("plan.execute")
    (join,) = [m for m in res.metrics.values() if m.kind == "HashJoin"]
    j = spans.one("ops.join")
    assert (j["left_out"], j["right_out"]) == ("take", "sort") \
        == (join.left_out, join.right_out)
    # what is read of `d` is its key, which has no mask: one plane
    # through `lmap`; the right side's `k` and `v` count for nothing
    assert (got["join_planes_gathered"], got["join_slots_gathered"]) \
        == (res.join_planes_gathered, res.join_slots_gathered) \
        == (1, join.rows_out)
    assert [s["site"] for s in spans.named("ops.host_sync")
            if inside(s, j)] == ["join.left"]
    t = res.compact()
    assert t["n"].to_pylist()[30:] == [0] * 20      # the null-extended rows


def test_join_span_of_an_inner_and_a_semi_join(session):
    """Every eager join has the span; `how` tells them apart."""
    inputs = {"t": _fact(), "d": _dim()}
    b = PlanBuilder()
    fact, dim = b.scan("t", schema=["k", "v"]), b.scan("d",
                                                       schema=["dk", "g"])
    plan = (fact.join(dim, left_on="k", right_on="dk")
            .join(dim.filter(col("g") > 2).select(["dk"])
                  .project({"sk": col("dk")}),
                  left_on="k", right_on="sk", how="left_semi").build())
    ex = PlanExecutor(mode="eager")
    ex.execute(plan, inputs)
    done = []
    spans = session(lambda: done.append(ex.execute(plan, inputs)))
    joins = spans.named("ops.join")
    assert sorted(j["how"] for j in joins) == ["inner", "left_semi"]
    by_how = {j["how"]: j for j in joins}
    assert by_how["inner"]["matched"] == 400 \
        and by_how["inner"]["unmatched"] == 0
    assert by_how["left_semi"]["matched"] == done[0].table.num_rows
    assert done[0].outer_joins == 0 and done[0].outer_unmatched_rows == 0
    assert spans.one("plan.execute")["outer_joins"] == 0
    # only an outer join says how it made its sides, and counts
    assert not any("left_out" in j or "right_out" in j for j in joins)
    assert (spans.one("plan.execute")["join_planes_gathered"],
            done[0].join_slots_gathered) == (0, 0)


def _full_plan():
    from spark_rapids_tpu.plan import is_not_null, is_null, when
    b = PlanBuilder()
    return (b.scan("d", schema=["dk", "g"]).distinct(["dk"])
            .join(b.scan("t", schema=["k", "v"]).filter(col("v") > 10)
                  .distinct(["k"]),
                  left_on="dk", right_on="k", how="full_outer")
            .project({"only_d": when(is_not_null(col("dk"))
                                     & is_null(col("k")), 1, 0),
                      "only_t": when(is_null(col("dk")), 1, 0)})
            .aggregate([], [("only_d", "sum", "only_d"),
                            ("only_t", "sum", "only_t")]).build())


def _full_inputs():
    # keys 0..49 on the left, 20..59 on the right: twenty lonely rows a side
    fact = _fact()
    return {"d": _dim(),
            "t": Table([_col(np.asarray(fact["k"].data) % 40 + 20),
                        fact["v"]], names=["k", "v"])}


@pytest.mark.parametrize("tier", ["eager", "capped", "cpu"])
def test_full_join_span_and_the_requests_counters(session, tier):
    """A `full_outer` join's `ops.join` says `unmatched_right` beside
    `matched` and `unmatched`; the request's `plan.execute` carries
    `full_joins` and the unmatched rows of each side in every tier; the
    DISTINCTs' `ops.groupby` spans say `planes=0`; the projection's
    `plan.op` says how many of its inputs can hold a null."""
    plan, inputs = _full_plan(), _full_inputs()
    ex = PlanExecutor(mode="capped" if tier == "capped" else "eager",
                      **({"caps": dict(row_cap=1024, key_cap=64)}
                         if tier == "capped" else {}))
    run = lambda: ex.execute(plan, inputs,
                             tier="cpu" if tier == "cpu" else None)
    run()                                                 # compile outside
    done = []
    spans = session(lambda: done.append(run()))
    res, got = done[0], spans.one("plan.execute")
    (join,) = [m for m in res.metrics.values() if m.kind == "HashJoin"]
    assert (res.full_joins, res.full_unmatched_rows,
            res.full_unmatched_right_rows) == (1, 20, 10)
    assert (join.unmatched_rows, join.unmatched_right_rows) == (20, 10)
    assert (got["full_joins"], got["full_unmatched_rows"],
            got["full_unmatched_right_rows"]) == (1, 20, 10)
    assert (got["outer_joins"], got["outer_unmatched_rows"]) == (0, 0)
    t = res.compact()
    assert (t["only_d"].to_pylist(), t["only_t"].to_pylist()) \
        == ([20], [10])
    if tier == "capped":
        assert not spans.named("ops.join")        # one program, run warm
        return
    j = spans.one("ops.join")
    assert (j["how"], j["rows_left"], j["rows_right"]) \
        == ("full_outer", 50, 40)
    assert (j["matched"], j["unmatched"], j["unmatched_right"]) \
        == (30, 20, 10)
    # distinct keys on both sides: the left columns as they stand, the
    # right side's 30 matched slots written into a null frame, its 10
    # lonely rows by their positions; nothing is gathered over the frame
    assert (j["left_out"], j["right_out"]) == ("as_is", "sparse/positions") \
        == (join.left_out, join.right_out)
    assert (got["join_planes_gathered"], got["join_slots_gathered"],
            res.join_planes_gathered, res.join_slots_gathered) == (0,) * 4
    (op,) = [o for o in spans.named("plan.op")
             if o["op"].endswith(".HashJoin")]
    assert op["how"] == "full_outer" and inside(j, op)
    # ONE read for both sides' counts, inside the span
    assert [s["site"] for s in spans.named("ops.host_sync")
            if inside(s, j)] == ["join.full"]
    assert [g["planes"] for g in spans.named("ops.groupby")] == [0, 0]
    nullable = {o["op"].split(".")[1]: o["nullable_inputs"]
                for o in spans.named("plan.op") if "nullable_inputs" in o}
    # the projection reads both keys, nullable after the join; the filter
    # below the right side reads a column that has no mask
    assert nullable["Project"] == 2
    assert nullable.get("Filter", nullable.get("FusedSelect")) == 0


def test_a_plan_without_them_counts_no_full_join_and_no_nullable_input(
        session):
    plan, inputs = _join_plan(), {"t": _fact(), "d": _dim()}
    ex = PlanExecutor(mode="eager")
    ex.execute(plan, inputs)
    done = []
    spans = session(lambda: done.append(ex.execute(plan, inputs)))
    got = spans.one("plan.execute")
    assert (got["full_joins"], got["full_unmatched_rows"],
            got["full_unmatched_right_rows"]) == (0, 0, 0)
    assert (done[0].full_joins, done[0].full_unmatched_right_rows) == (0, 0)
    assert "unmatched_right" not in spans.one("ops.join")
    assert all(o.get("nullable_inputs", 0) == 0
               for o in spans.named("plan.op"))
    assert all("nullable_inputs" not in o for o in spans.named("plan.op")
               if o["op"].split(".")[1] in ("HashJoin", "HashAggregate",
                                            "Sort", "Scan"))


def test_a_request_with_an_outer_join_is_tiled_by_its_spans(session):
    """The walk over a request: every span lies inside `plan.execute`,
    every operator's children lie inside it, and what a `plan.op` leaves
    uncovered (its own time: dispatch) is small beside the request."""
    plan, inputs = _outer_plan(), _outer_inputs()
    ex = PlanExecutor(mode="eager")
    ex.execute(plan, inputs)
    spans = session(lambda: ex.execute(plan, inputs))
    root = spans.one("plan.execute")
    mine = [s for s in spans if s.get("request") == root["request"]
            and s is not root]
    assert mine and all(inside(s, root) for s in mine)
    ops = spans.named("plan.op")
    leaves = [s for s in mine if s["name"] in (
        "plan.wait", "ops.host_sync", "plan.bind", "plan.optimize",
        "plan.verify", "plan.certify", "plan.result", "plan.stats")]
    # every blocking read or wait of the walk lies under an operator, and
    # the join's under its `ops.join`
    run = spans.one("plan.run")
    j = spans.one("ops.join")
    for s in leaves:
        if s["name"] in ("plan.wait", "ops.host_sync") and inside(s, run):
            assert any(inside(s, o) for o in ops), s
    (op,) = [o for o in ops if o["op"].endswith(".HashJoin")]
    under = [s for s in mine if inside(s, op) and s is not op]
    assert j in under and all(inside(s, j) or s["name"] == "plan.wait"
                              for s in under if s is not j)
    # siblings do not overlap: the spans tile, they do not pile up
    same = sorted((s for s in mine if s["thread"] == root["thread"]),
                  key=lambda s: (s["t0"], -s["t1"]))
    open_ = [root]
    for s in same:
        while not inside(s, open_[-1]):
            open_.pop()
            assert open_, s
        open_.append(s)


def _window_plan():
    b = PlanBuilder()
    return (b.scan("t", schema=["k", "v"])
            .aggregate(["k", "v"], [("v", "size", "n")])
            .window([("run", "sum", "n")], partition_by=["k"],
                    order_by=["v"])
            .join(b.scan("d", schema=["dk", "g"]), left_on="k",
                  right_on="dk")
            .window([("top", "max", "run"), ("seen", "count", "run")],
                    partition_by=["g"], order_by=["v", "k"],
                    ascending=[False, True])
            .build())


@pytest.mark.parametrize("tier", ["eager", "capped"])
def test_window_span_and_the_requests_window_counters(session, tier):
    """A window's kernel, its finish and the wait for them run inside
    `ops.window` (rows, partitions, functions, frame, kernel, planes: the
    32-bit words riding the sort, sorted: `sort`, or `child` where the
    child's order was taken), below the operator; `plan.execute` carries
    the request's windows, the rows into them and, in the eager tier, the
    partitions they held."""
    plan, inputs = _window_plan(), {"t": _fact(), "d": _dim()}
    ex = PlanExecutor(mode=tier, **({"caps": dict(row_cap=512, key_cap=512)}
                                    if tier == "capped" else {}))
    ex.execute(plan, inputs)                              # compile outside
    done = []
    spans = session(lambda: done.append(ex.execute(plan, inputs)))
    res, got = done[0], spans.one("plan.execute")
    wins = [m for m in res.metrics.values() if m.kind == "Window"]
    assert len(wins) == 2 and all(m.kernel == "xla:sort_scan" for m in wins)
    assert (res.windows, res.window_rows) == (
        2, sum(m.rows_in for m in wins))
    assert (got["windows"], got["window_rows"], got["window_partitions"]) \
        == (res.windows, res.window_rows, res.window_partitions)
    if tier == "capped":
        assert res.window_partitions == 0 and not spans.named("ops.window")
        owners = ex.device_op_owners(plan, inputs, nested=True)
        held = {o.split("/")[0] for o in owners.values()
                if o.endswith("/ops.window")}
        assert len(held) == 2 and all(o.endswith(".Window") for o in held)
        return
    ops = [o for o in spans.named("plan.op") if o["op"].endswith(".Window")]
    said = sorted(spans.named("ops.window"), key=lambda s: s["t0"])
    assert len(ops) == len(said) == 2
    for w, op, m in zip(said, sorted(ops, key=lambda s: s["t0"]), wins):
        assert inside(w, op) and w["request"] == op["request"]
        assert (w["rows"], w["frame"], w["kernel"]) == (
            m.rows_in, "running", "sort_scan")
        assert (w["partitions"], w["sorted"]) == (m.window_partitions,
                                                  m.window_sorted)
        # a window that sorts reads its key operands' ranges first (one
        # packed sort key where they fit), then both read their partitions
        under = [s for s in spans if inside(s, w) and s is not w]
        assert [s["name"] for s in sorted(under, key=lambda s: s["t0"])] \
            == ["ops.host_sync"] * (2 if w["sorted"] == "sort" else 1) \
            + ["plan.wait"]
        assert w["key"] == m.window_key
    first, second = said
    # over the sorted group-by's own keys nothing rides and nothing sorts;
    # after the join: `g`, `v` and `k` are read back from their operands,
    # `n`, `run` and `dk` ride (two words each)
    assert (first["sorted"], first["planes"], first["functions"],
            first["partitions"]) == ("child", 0, 1, 50)
    assert (second["sorted"], second["planes"], second["functions"],
            second["partitions"], second["key"]) == ("sort", 6, 2, 7,
                                                     "packed")
    assert res.window_partitions == 57


def test_a_request_with_a_window_is_tiled_by_its_spans(session):
    """The walk over a request that holds two windows: every span lies
    inside `plan.execute`, every blocking read of the run lies under an
    operator, a window's under its `ops.window`, and siblings do not
    overlap."""
    plan, inputs = _window_plan(), {"t": _fact(), "d": _dim()}
    ex = PlanExecutor(mode="eager")
    ex.execute(plan, inputs)
    spans = session(lambda: ex.execute(plan, inputs))
    root = spans.one("plan.execute")
    mine = [s for s in spans if s.get("request") == root["request"]
            and s is not root]
    assert mine and all(inside(s, root) for s in mine)
    ops, run = spans.named("plan.op"), spans.one("plan.run")
    for s in mine:
        if s["name"] in ("plan.wait", "ops.host_sync") and inside(s, run):
            assert any(inside(s, o) for o in ops), s
    for op in (o for o in ops if o["op"].endswith(".Window")):
        (w,) = [s for s in spans.named("ops.window") if inside(s, op)]
        under = [s for s in mine if inside(s, op) and s is not op]
        assert all(s is w or inside(s, w) or s["name"] == "plan.wait"
                   for s in under)
    same = sorted((s for s in mine if s["thread"] == root["thread"]),
                  key=lambda s: (s["t0"], -s["t1"]))
    open_ = [root]
    for s in same:
        while not inside(s, open_[-1]):
            open_.pop()
            assert open_, s
        open_.append(s)


def test_plan_execute_span_counts_the_slots_the_joins_gathered(session):
    """A capped join gathers its output columns over whole chunks of its
    live rows (ops/gather.py:gather_live), not over its cap: the first
    join of this plan keeps every fact row (three chunks of four), the
    second under one chunk, and `gather_slots` / `cap_slots` say so on the
    result and on `plan.execute`."""
    from spark_rapids_tpu.ops.gather import live_chunk
    cap = 4096
    assert live_chunk(cap) == 1024
    fact = _fact(n=3000)
    few = int((np.asarray(fact["k"].data) % 7 == 0).sum())
    assert 0 < few < 1024
    b = PlanBuilder()
    plan = (b.scan("t", schema=["k", "v"])
             .join(b.scan("d", schema=["dk", "g"]), left_on="k",
                   right_on="dk")
             .join(b.scan("z", schema=["zk", "w"]), left_on="g",
                   right_on="zk")
             .aggregate(["w"], [("v", "sum", "total")]).build())
    inputs = {"t": fact, "d": _dim(),
              "z": Table([_col([0]), _col([5])], names=["zk", "w"])}
    ex = PlanExecutor(mode="capped", optimize=False,
                      caps=dict(row_cap=cap, key_cap=64))
    ex.execute(plan, inputs)                              # compile outside
    done = []
    spans = session(lambda: done.append(ex.execute(plan, inputs)))
    (res,), got = done, spans.one("plan.execute")
    assert [m.rows_out for m in res.metrics.values()
            if m.kind == "HashJoin"] == [3000, few]
    want = (3072 + 1024, 2 * cap)
    assert (res.gather_slots, res.cap_slots) == want
    assert (got["gather_slots"], got["cap_slots"]) == want
    assert int(np.asarray(res.compact()["total"].data)[0]) == int(
        np.asarray(fact["v"].data)[np.asarray(fact["k"].data) % 7 == 0].sum())


@pytest.mark.parametrize("join", ["expand", "unique", "pallas"])
def test_plan_execute_span_counts_the_slots_the_joins_expanded(
        session, monkeypatch, join):
    """A join that fans out expands over the left rows that emit and the
    output slots that are live (ops/join.py:expand_rows), in whole chunks:
    1,100 of 3,000 left rows match, every second one up to row 2,198, and
    match twice (three chunks of eight output slots). `expand_slots` /
    `expand_cap_slots` say so on the result and on `plan.execute`. The
    sort join's general tail packs the rows that emit (two chunks of left
    rows) and gathers three planes over the slots; the Pallas join visits
    the frame up to the last row that emits (all three chunks) and gathers
    `starts` and an int64 key's two words; a many-to-one join expands
    nothing."""
    from spark_rapids_tpu.ops.gather import live_chunk
    n, cap, hits = 3000, 8192, 1100
    assert live_chunk(n) == live_chunk(cap) == 1024
    if join == "pallas":
        monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNELS", "hash_join=pallas")
    row = np.arange(n)
    match = (row < 2 * hits) & (row % 2 == 0)
    fact = Table([_col(np.where(match, row % 50, 1000)), _col(row)],
                 names=["k", "v"])
    dim = _dim() if join == "unique" else Table(
        [_col(np.arange(100) % 50), _col(np.arange(100) % 7)],
        names=["dk", "g"])
    b = PlanBuilder()
    plan = (b.scan("t", schema=["k", "v"])
             .join(b.scan("d", schema=["dk", "g"]), left_on="k",
                   right_on="dk")
             .aggregate(["g"], [("v", "sum", "total")]).build())
    inputs = {"t": fact, "d": dim}
    ex = PlanExecutor(mode="capped", optimize=False,
                      caps=dict(row_cap=cap, key_cap=64))
    ex.execute(plan, inputs)                              # compile outside
    done = []
    spans = session(lambda: done.append(ex.execute(plan, inputs)))
    (res,), got = done, spans.one("plan.execute")
    (rows_out,) = [m.rows_out for m in res.metrics.values()
                   if m.kind == "HashJoin"]
    assert rows_out == hits * (1 if join == "unique" else 2)
    want = {"unique": (0, 0), "expand": (2048 + 3 * 3072, n + 3 * cap),
            "pallas": (n + 3 * 3072, n + 3 * cap)}[join]
    assert (res.expand_slots, res.expand_cap_slots) == want
    assert (got["expand_slots"], got["expand_cap_slots"]) == want
    assert int(np.asarray(res.compact()["total"].data).sum()) == \
        rows_out // hits * int(row[match].sum())


def test_device_op_owners_is_the_capped_tiers():
    plan, inputs = _join_plan(), {"t": _fact(), "d": _dim()}
    with pytest.raises(Exception, match="capped tier"):
        PlanExecutor(mode="eager").device_op_owners(plan, inputs)


@pytest.mark.parametrize("line, owner", [
    ('  %fusion.32 = s64[8]{0} fusion(%p), kind=kLoop, calls=%fc, '
     'metadata={op_name="jit(capped_plan)/3.HashJoin/jit(take)/gather" '
     'stack_frame_id=4}', ("fusion.32", "3.HashJoin")),
    ('  ROOT %sort.90 = (s64[8]{0}) sort(%a), dimensions={0}, '
     'metadata={op_name="jit(capped_plan)/5.HashAggregate/jit(sort)/sort"}',
     ("sort.90", "5.HashAggregate")),
    ('  %pallas_hash_join_probe.1 = u32[4]{0} custom-call(%x), '
     'custom_call_target="tpu_custom_call", metadata={op_name='
     '"jit(capped_plan)/4.HashJoin/pallas_hash_join_probe/pallas_call"}',
     ("pallas_hash_join_probe.1", "4.HashJoin")),
    ('  %fusion.7 = s64[8]{0} fusion(%p), kind=kLoop, calls=%fc, '
     'metadata={op_name="jit(capped_plan)/5.HashAggregate/ops.groupby/'
     'jit(_groupby_kernel)/cumsum"}', ("fusion.7", "5.HashAggregate")),
    ('  %copy.3 = s64[8]{0} copy(%p), metadata={op_name='
     '"jit(capped_plan)/jit(main)/copy"}', None),
    ('  %param.1 = s64[8]{0} parameter(0)', None),
])
def test_scope_owners_reads_an_executables_text(line, owner):
    assert _scope_owners("HloModule jit_capped_plan\n" + line + "\n") \
        == (dict([owner]) if owner else {})


@pytest.mark.parametrize("path, owner", [
    ("5.HashAggregate/ops.groupby/jit(_groupby_kernel)/sort",
     "5.HashAggregate/ops.groupby"),
    ("5.HashAggregate/ops.groupby/jit(_total_limbs)/decimal.sum/add",
     "5.HashAggregate/decimal.sum"),
    ("2.Project/decimal.mul/mul", "2.Project/decimal.mul"),
    ("4.HashJoin/jit(take)/gather", "4.HashJoin"),
])
def test_scope_owners_nested_gives_the_innermost_kernel_scope(path, owner):
    line = ('  %fusion.1 = s64[8]{0} fusion(%p), kind=kLoop, calls=%fc, '
            'metadata={op_name="jit(capped_plan)/' + path + '"}')
    assert _scope_owners("HloModule jit_capped_plan\n" + line + "\n",
                         nested=True) == {"fusion.1": owner}


PALLAS_SITES = {
    ("ops/join_pallas.py", "pallas_hash_join_build"),
    ("ops/join_pallas.py", "pallas_hash_join_probe"),
    ("ops/select_pallas.py", "pallas_fused_select"),
    ("ops/topk_pallas.py", "pallas_topk"),
    ("ops/hash_pallas.py", "pallas_hash"),
    ("parallel/partition_pallas.py", "pallas_partition_counts"),
}


def _pallas_call_names():
    """(file, name=) of every `pallas_call(...)` in the package; the name
    is None where the call passes none or not a literal."""
    found = []
    for path in glob.glob(os.path.join(PKG, "**", "*.py"), recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and getattr(node.func, "attr", "") == "pallas_call":
                name = next((k.value.value for k in node.keywords
                             if k.arg == "name"
                             and isinstance(k.value, ast.Constant)), None)
                found.append((os.path.relpath(path, PKG), name))
    return found


@pytest.mark.parametrize("site", sorted(PALLAS_SITES))
def test_pallas_call_site_has_its_stable_name(site):
    assert site in _pallas_call_names()


def test_no_pallas_call_is_unnamed():
    assert set(_pallas_call_names()) == PALLAS_SITES


# ---- the SPMD walk's exchanges ---------------------------------------------------

def test_exchange_spans_carry_their_edges_and_sum_to_the_execution(
        session, monkeypatch):
    """Every movement of data between devices is one `plan.exchange` span
    inside the `plan.op` of the operator that moves it, with what
    `OperatorMetrics` says of the edge; `plan.execute` carries the
    execution's totals."""
    from examples.nds import q5_inputs, q5_plan, q5_tables
    monkeypatch.setenv("SPARK_RAPIDS_TPU_BROADCAST_ROWS", "64")
    ex = PlanExecutor(mesh=4)
    plan, inputs = q5_plan(), q5_inputs(*q5_tables(3000, 3))
    ex.execute(plan, inputs)
    out = {}
    spans = session(lambda: out.update(res=ex.execute(plan, inputs)))
    res = out["res"]
    execute = spans.one("plan.execute")
    edges = spans.named("plan.exchange")
    ops = spans.named("plan.op")
    assert edges and all(any(inside(e, op) for op in ops) for e in edges)
    assert {e["how"] for e in edges} == {"hash", "broadcast", "reduce",
                                         "range", "gather"}
    for e in edges:
        assert e["peers"] == 4 and e["request"] == execute["request"]
        assert 0 < e["bytes"] <= e["bytes_logical"] * 4 and e["codec"]
    assert sum(e["bytes"] for e in edges) == execute["exchange_bytes"] \
        == res.exchange_bytes
    assert execute["exchange_edges"] == len(edges) == res.exchange_edges
    assert execute["dist_ops"] == res.dist_ops == len(res.plan.nodes) - 1
    assert execute["local_ops"] == 0 and execute["dist_cap_escalations"] == 0
    by_metric = sum(m.exchange_bytes for m in res.metrics.values())
    assert by_metric == res.exchange_bytes


def test_collectives_run_under_an_exchange_scope():
    """The all-to-all of a hash exchange carries `exchange.hash` in the
    compiled program's op names, as the decimal kernels carry theirs."""
    from spark_rapids_tpu.parallel import make_mesh
    from spark_rapids_tpu.parallel.keys import KeySpec
    from spark_rapids_tpu.parallel.relational import (
        distributed_reduce, distributed_repartition_keyed)
    mesh = make_mesh(4)
    k = jnp.arange(64, dtype=jnp.int64)
    spec = [KeySpec(dtypes.INT64, 1, False)]
    text_of = lambda fn, *a: jax.jit(fn).lower(*a).compile().as_text()  # noqa
    hashed = text_of(lambda k, v: distributed_repartition_keyed(
        mesh, [k], spec, [v], cap=32), k, k)
    assert "exchange.hash" in hashed and "all-to-all" in hashed
    reduced = text_of(lambda v, a: distributed_reduce(
        mesh, [v], [(0, "sum")], a), k, k > 3)
    assert "exchange.reduce" in reduced and "all-reduce" in reduced


# ---- every wait on the device has one of three names ----------------------------------
#
# The rule that keeps PR 38's account whole: in the modules a request runs
# through, a call that makes the host wait for the device (`block_until_
# ready`, `device_get`, `.item()`, `.tolist()`, and `int(` / `bool(` /
# `float(` / `np.asarray(` over a device value) lies inside a `with
# span("ops.host_sync" | "plan.wait" | "plan.readback", ...)`, or is listed
# in EXEMPT with its reason. What holds a device value is decided from the
# syntax alone: a `jnp.` / `lax.` / `jax.` call, a program from `_jitted`,
# a column's or a relation's buffers, and the names assigned from those
# earlier in the same function.

WALKED = ["plan/executor.py", "plan/distributed.py", "parallel/relational.py",
          "parallel/autoretry.py", "ops/join.py", "ops/join_lookup.py",
          "ops/join_pallas.py", "ops/select_pallas.py", "ops/gather.py",
          "ops/aggregate.py", "ops/window.py", "serving/scheduler.py",
          "serving/cache.py"]
HOLDERS = {"ops.host_sync", "plan.wait", "plan.readback"}
DEVICE_ROOTS = {"jnp", "lax", "jax"}
HOST_ATTRS = {"shape", "dtype", "ndim", "num_rows", "length", "nbytes", "size", "names", "padded_rows"}
DEVICE_ATTRS = {"valid", "data", "validity", "offsets", "planes", "alive"}
DEVICE_CALLS = {"fn", "fn1", "spans", "emit", "_retry", "_fold_buffers",
                "_member", "auto_retry_overflow", "_window_kernel"}
DEVICE_PARAMS = {"lost", "valid", "mask", "idx", "nulled"}
PASS_THROUGH = {"list", "tuple", "sum", "zip", "max", "min"}
CONVERT = {"int", "bool", "float"}


def _root(expr):
    while isinstance(expr, (ast.Attribute, ast.Subscript, ast.Call)):
        expr = expr.func if isinstance(expr, ast.Call) else expr.value
    return expr.id if isinstance(expr, ast.Name) else None


def _reads(call):
    """The kind of host read a Call is, or None."""
    f = call.func
    if isinstance(f, ast.Attribute):
        if f.attr in ("block_until_ready", "device_get"):
            return f.attr
        if f.attr in ("item", "tolist") and not call.args:
            return f.attr
        if f.attr in ("asarray", "array") and _root(f) == "np":
            return "np." + f.attr
    elif isinstance(f, ast.Name) and f.id in CONVERT:
        return f.id
    return None


class Func:
    """One function's assignments in line order, to say whether an
    expression holds a device value at a line."""
    def __init__(self, fn):
        self.assigned = []      # (line, name, value expr)
        self.params = {a.arg for a in fn.args.args} & DEVICE_PARAMS
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    self._bind(t, node.value, node.lineno)
            elif isinstance(node, (ast.For, ast.comprehension)):
                # a comprehension binds before the line that uses it
                line = getattr(node, "lineno", node.iter.lineno - 1)
                self._bind(node.target, node.iter, line)
        self.assigned.sort(key=lambda a: a[0])

    def _bind(self, target, value, line):
        for n in ast.walk(target):
            if isinstance(n, ast.Name):
                self.assigned.append((line, n.id, value))

    def device(self, expr, line, depth=0) -> bool:
        if depth > 6:
            return False
        d = lambda e: self.device(e, line, depth + 1)
        if isinstance(expr, ast.Name):
            last = [(l, v) for l, n, v in self.assigned
                    if n == expr.id and l < line]
            if not last:
                return expr.id in self.params
            return self.device(last[-1][1], last[-1][0], depth + 1)
        if isinstance(expr, ast.Attribute):
            if expr.attr in HOST_ATTRS:
                return False
            return expr.attr in DEVICE_ATTRS or d(expr.value)
        if isinstance(expr, ast.Subscript):
            return d(expr.value)
        if isinstance(expr, ast.Call):
            if _reads(expr) not in (None, "block_until_ready"):
                return False            # a conversion's result is the host's
            f = expr.func
            if _root(f) in DEVICE_ROOTS:
                return True
            if isinstance(f, ast.Call) and _root(f) == "_jitted":
                return True
            name = f.attr if isinstance(f, ast.Attribute) else getattr(f, "id", "")
            if name in DEVICE_CALLS:
                return True
            if name in PASS_THROUGH:
                return any(d(a) for a in expr.args)
            if isinstance(f, ast.Attribute) and _root(f) != "np":
                return d(f.value)       # a method of a device value
            return False
        if isinstance(expr, (ast.Tuple, ast.List)):
            return any(d(e) for e in expr.elts)
        if isinstance(expr, ast.BinOp):
            return d(expr.left) or d(expr.right)
        if isinstance(expr, ast.UnaryOp):
            return d(expr.operand)
        if isinstance(expr, ast.Compare):
            return d(expr.left) or any(d(c) for c in expr.comparators)
        if isinstance(expr, ast.IfExp):
            return d(expr.body) or d(expr.orelse)
        if isinstance(expr, (ast.GeneratorExp, ast.ListComp)):
            return d(expr.elt)
        if isinstance(expr, ast.Starred):
            return d(expr.value)
        return False


def blocking_reads(rel, source=None):
    """[(file, function, kind, nth of its kind there, held)] of one module:
    every call that makes the host wait for a device value, and whether a
    `with span(<one of HOLDERS>, ...)` holds it."""
    if source is None:
        with open(os.path.join(PKG, rel)) as f:
            source = f.read()
    tree = ast.parse(source)
    parents = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    out, seen = [], {}
    funcs = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        kind = _reads(node)
        if kind is None:
            continue
        chain, p = [], node
        while p in parents:
            p = parents[p]
            chain.append(p)
        fns = [c for c in chain if isinstance(c, (ast.FunctionDef, ast.AsyncFunctionDef))]
        if not fns:
            continue
        outer = fns[-1]
        if kind not in ("block_until_ready", "device_get"):
            flow = funcs.setdefault(outer, Func(outer))
            arg = node.func.value if kind in ("item", "tolist") else (node.args[0] if node.args else None)
            if arg is None or not flow.device(arg, node.lineno):
                continue
        held = any(isinstance(c, ast.With) and any(
            isinstance(i.context_expr, ast.Call)
            and getattr(i.context_expr.func, "id", "") == "span"
            and i.context_expr.args
            and getattr(i.context_expr.args[0], "value", None) in HOLDERS
            for i in c.items) for c in chain)
        name = ".".join(f.name for f in reversed(fns))
        n = seen[(name, kind)] = seen.get((name, kind), 0) + 1
        out.append((rel, name, kind, n, held))
    return out


# (file, function, kind, nth) -> why it may wait outside the three names
EXEMPT = {
    ("plan/executor.py", "compact", "np.asarray", 1):
        "PlanResult.compact is the client's call after execute() returned: "
        "not on the request's path",
}

SITES = [site for rel in WALKED for site in blocking_reads(rel)]


@pytest.mark.parametrize(
    "site", SITES, ids=[f"{f}:{fn}:{kind}:{n}" for f, fn, kind, n, _ in SITES])
def test_blocking_read_is_held_by_a_wait_span(site):
    *where, held = site
    assert held or tuple(where) in EXEMPT, (
        f"{where}: a read that waits for the device outside ops.host_sync, "
        "plan.wait and plan.readback (docs/plan.md, Reading a profile)")


def test_the_walk_finds_what_the_request_path_is_known_to_read():
    found = {(f, fn, kind) for f, fn, kind, _, _ in SITES}
    for known in [("plan/executor.py", "_execute_capped", "block_until_ready"),
                  ("plan/executor.py", "_execute_capped", "np.asarray"),
                  ("plan/executor.py", "_execute_eager", "block_until_ready"),
                  ("plan/distributed.py", "num_rows", "int"),
                  ("plan/distributed.py", "_repartition_rel", "np.asarray"),
                  ("parallel/autoretry.py", "auto_retry_overflow", "bool"),
                  ("ops/join.py", "_sort_inner_join", "int"),
                  ("ops/join.py", "outer_join_parts", "device_get"),
                  ("ops/join_lookup.py", "member_mask", "int"),
                  ("ops/gather.py", "_count_kept", "int"),
                  ("ops/gather.py", "take", "device_get"),
                  ("ops/aggregate.py", "_groupby", "int"),
                  ("ops/window.py", "_window", "int"),
                  ("serving/cache.py", "_table_digest", "device_get")]:
        assert known in found, known
    assert len(SITES) >= 40
    assert set(EXEMPT) <= {tuple(s[:4]) for s in SITES if not s[4]}, \
        "an exemption that exempts nothing"


@pytest.mark.parametrize("body, want", [
    ("n = int(jnp.sum(mask))", [("int", False)]),
    ("with span('ops.host_sync', site='x'):\n        n = int(jnp.sum(mask))",
     [("int", True)]),
    ("with span('plan.exchange'):\n        jax.block_until_ready(out)",
     [("block_until_ready", False)]),
    ("counts = fn(mask)\n    host = np.asarray(counts)\n"
     "    return int(host.max())", [("np.asarray", False)]),
    ("total = jnp.sum(mask)\n    return total.item()", [("item", False)]),
    ("return int(mask.shape[0]) + len(mask)", []),
])
def test_the_walk_tells_a_device_read_from_a_host_number(body, want):
    source = "def f(mask, out, fn):\n    " + body + "\n"
    assert [(kind, held) for _, _, kind, _, held
            in blocking_reads("x.py", source)] == want
