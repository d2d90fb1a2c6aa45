"""`chipbench/host_account.py` (PR 38): over hand-made spans and idle
intervals (two threads, a wait that overlaps another thread's leaf, a
request cut by the window's edge, four device planes) and over the trace
PR 26 recorded (none of the new spans: every new reader gives None). The
trace `record_program_trace.py` records on the chip with the program as
PR 38 leaves it is 1.71 MB, over `program.xplane.pb`'s 1.27 MB, and is not
committed: the readers' numbers on the chip are in PERF.md section 5.

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q -p no:cacheprovider
"""
import json
import os
import shutil
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from chipbench import harness, host_account as ha  # noqa: E402
from chipbench import program_spans as ps  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    NEW_METRICS = [m["name"] for m in json.load(_f)["per_layer"]][-12:]


def span(name, thread, t0, t1, request=0, **attrs):
    return {"name": name, "thread": thread, "t0": t0, "t1": t1,
            "attrs": dict(attrs, request=request)}


# ---- interval arithmetic -----------------------------------------------------

def test_subtract_and_idle_intervals():
    assert ha._subtract([(0, 10), (20, 30)], [(5, 22), (25, 26)]) \
        == [(0, 5), (22, 25), (26, 30)]
    assert ha._subtract([(0, 10)], []) == [(0, 10)]
    assert ha._subtract([(0, 10)], [(0, 10)]) == []
    assert ha.idle_intervals([(5, 8), (50, 70), (110, 130)], 0, 120) \
        == [(0, 5), (8, 50), (70, 110)]
    assert ha.idle_intervals([], 3, 9) == [(3, 9)]


# ---- two threads: a wait that overlaps another thread's leaf ---------------------

def two_threads():
    """Thread 1 executes: it waits for the device over [0, 100], then its
    operator dispatches until 105, then only brackets are open until 120.
    Thread 2 digests (a leaf of its own) over [40, 60]."""
    return [span("plan.execute", 1, 0, 120), span("plan.run", 1, 0, 110),
            span("plan.op", 1, 0, 105, op="2.HashJoin"),
            span("plan.wait", 1, 0, 100, site="op"),
            span("serving.submit", 2, 38, 62, request=1),
            span("serving.digest", 2, 40, 60, request=1)]


def test_host_states_tell_waiting_from_named_work():
    waiting, working = ha.host_states(two_threads())
    assert waiting == [(0, 100)]
    assert working == [(40, 60), (100, 105)]


def test_idle_while_the_host_waits_counts_only_where_no_leaf_runs():
    # the device is busy over [50, 70]: 100 ns idle in a window of 120
    idle = ha.idle_intervals([(50, 70)], 0, 120)
    shares = ha.idle_shares(two_threads(), idle)
    # waiting and nothing else: [0, 40] + [70, 100]; the digest's [40, 50]
    # and the dispatch's [100, 105] are named work; [105, 120] is brackets
    assert shares == {"wait": 70.0, "work": 15.0, "unnamed": 15.0}
    assert ha.idle_shares(two_threads(), []) is None


def test_an_ops_host_sync_is_a_wait_and_a_bracket_alone_is_unnamed():
    spans = [span("plan.execute", 1, 0, 100), span("plan.run", 1, 10, 90),
             span("plan.attempt", 1, 20, 60),
             span("ops.host_sync", 1, 60, 80, site="autoretry.overflow")]
    assert ha.idle_shares(spans, [(0, 100)]) \
        == {"wait": 20.0, "work": 0.0, "unnamed": 80.0}


# ---- an account over hand-made requests ----------------------------------------------

def capped_request(r, thread, t, launch=1_000_000, **execute_attrs):
    """One capped request on one thread from `t` (ns): 0.2 ms bind, 0.3 ms
    optimize, 0.1 ms certify, 0.5 ms caps with a 0.1 ms stats child, the
    attempt (0.1 ms program, `launch` ns launch), a 2 ms overflow read, a
    0.05 ms wait, 0.4 ms readback, 0.3 ms epilogue, 0.2 ms stamps, 0.1 ms
    stats; 10 us of every bracket's own time between children."""
    out, g = [], 10_000
    cursor = [t + g]

    def leaf(name, ns, **attrs):
        s = span(name, thread, cursor[0], cursor[0] + ns, r, **attrs)
        out.append(s)
        cursor[0] += ns + g
        return s
    leaf("plan.bind", 200_000)
    leaf("plan.optimize", 300_000)
    leaf("plan.certify", 100_000)
    run0 = cursor[0]
    cursor[0] += g
    caps = leaf("plan.caps", 500_000)
    out.append(span("plan.stats", thread, caps["t0"] + g,
                    caps["t0"] + g + 100_000, r))
    attempt0 = cursor[0]
    cursor[0] += g
    leaf("plan.program", 100_000)
    leaf("plan.launch", launch, hit=1)
    out.append(span("plan.attempt", thread, attempt0, cursor[0], r,
                    attempt=1, hit=1, lowerings=0, lowering_ms=0))
    cursor[0] += g
    leaf("ops.host_sync", 2_000_000, site="autoretry.overflow")
    leaf("plan.wait", 50_000, site="capped")
    leaf("plan.readback", 400_000, scalars=16)
    leaf("plan.result", 300_000)
    out.append(span("plan.run", thread, run0, cursor[0], r))
    cursor[0] += g
    leaf("plan.result", 200_000)
    leaf("plan.stats", 100_000)
    out.append(span("plan.execute", thread, t, cursor[0], r,
                    **execute_attrs))
    return out, cursor[0]


def reduced(spans, w0, w1, busy=()):
    ops = [("jit_capped_plan", "fusion.1", "fusion", s, e,
            "%fusion.1 = s64[8]{0} fusion(%p)") for s, e in busy]
    return ps.Reduced({"spans": spans, "marks": [w0, w1], "devices": [ops],
                       "skew": {"ns": 0, "lo": None, "hi": None}}, None)


@pytest.fixture
def account():
    """Requests 1 and 2 whole on two worker threads (the second launches
    for 3 ms and lowered once), request 3 cut by the window's end, the
    admission's certify of request 1 on a submitter's thread."""
    one, end1 = capped_request(1, 1, 1_000_000, lowerings=0, lowering_ms=0)
    two, end2 = capped_request(2, 2, 1_500_000, launch=3_000_000,
                               lowerings=1, lowering_ms=2.5)
    cut, _ = capped_request(3, 1, end1 + 100_000, lowerings=7,
                            lowering_ms=9)
    admit = [span("serving.submit", 3, 500_000, 900_000, 1),
             span("plan.certify", 3, 600_000, 800_000, 1)]
    w1 = end2 + 500_000                       # inside request 3
    red = reduced(one + two + cut + admit, 0, w1,
                  busy=[(2_500_000, 4_000_000)])
    return ha.Account(red, {"spans": one + two + cut + admit,
                            "busy": [[(2_500_000, 4_000_000)]]})


def test_a_request_cut_by_the_windows_edge_does_not_count(account):
    assert account.requests == [1, 2] and account.capped
    assert account.attr_mean("plan.execute", "lowerings") == 0.5
    assert account.attr_mean("plan.execute", "lowering_ms") == 1.25
    assert account.attr_mean("plan.execute", "no_such") is None


def test_medians_are_over_a_requests_summed_spans(account):
    assert account.median_ms("plan.bind") == pytest.approx(0.2)
    # the epilogue and the stamps are one name: summed per request
    assert account.median_ms("plan.result") == pytest.approx(0.5)
    assert account.median_ms("plan.caps") == pytest.approx(0.5)
    assert account.median_ms("plan.caps", own=True) == pytest.approx(0.4)
    assert account.median_ms(("plan.program", "plan.launch")) \
        == pytest.approx((1.1 + 3.1) / 2)
    assert account.median_ms("plan.readback") == pytest.approx(0.4)
    assert account.median_ms("plan.wait") == pytest.approx(0.05)
    assert account.median_ms("serving.consult") is None     # not in it
    assert account.median_ms(("serving.consult", "serving.complete")) is None


def test_unnamed_is_the_brackets_own_time(account):
    # gaps of 10 us before each child and after the last: execute has 6
    # children, run 6, attempt 2
    assert account.unnamed_ms() == pytest.approx(0.07 + 0.07 + 0.03)


def test_plan_path_parts_leave_out_the_submitters_certify(account):
    parts = dict(account.plan_path())
    assert list(parts) == ["bind", "optimize", "verify", "certify", "caps",
                           "readback", "result", "stats"]
    assert parts["certify"] == pytest.approx(0.1)     # not 0.3
    assert parts["caps"] == pytest.approx(0.4) \
        and parts["stats"] == pytest.approx(0.2)
    assert parts["verify"] == 0.0
    lines = account.lines(host_plan_ms=2.5)
    (line,) = [ln for ln in lines if ln.startswith("host_plan_ms = ")]
    assert line.endswith("2.500 = 0.200 + 0.300 + 0.000 + 0.100 + 0.400 + "
                         "0.400 + 0.500 + 0.200 + 0.400")


def test_idle_shares_of_the_account(account):
    shares = account.idle_shares()
    assert shares and sum(shares.values()) == pytest.approx(100.0)
    assert shares["wait"] > 0 and shares["unnamed"] > 0
    assert any(ln.startswith("device idle time by what the host was in")
               for ln in account.lines())


def test_eager_operators_split_dispatch_wait_and_host_sync():
    spans = []
    for r, t in ((5, 0), (6, 10_000_000)):
        spans += [
            span("plan.execute", 1, t, t + 9_000_000, r, lowerings=2,
                 lowering_ms=1.5),
            span("plan.bind", 1, t + 100_000, t + 200_000, r),
            span("plan.run", 1, t + 300_000, t + 8_500_000, r),
            span("plan.op", 1, t + 400_000, t + 1_400_000, r, op="0.Filter",
                 lowerings=0, lowering_ms=0),
            span("ops.host_sync", 1, t + 500_000, t + 900_000, r,
                 site="gather.kept_rows"),
            span("plan.wait", 1, t + 1_000_000, t + 1_300_000, r, site="op"),
            span("plan.op", 1, t + 1_500_000, t + 8_000_000, r,
                 op="1.HashAggregate", lowerings=2, lowering_ms=1.5),
            span("ops.groupby", 1, t + 1_600_000, t + 7_600_000, r),
            span("ops.host_sync", 1, t + 2_000_000, t + 6_000_000, r,
                 site="groupby.groups"),
            span("plan.wait", 1, t + 7_000_000, t + 7_500_000, r,
                 site="groupby"),
            span("plan.wait", 1, t + 7_700_000, t + 7_900_000, r, site="op"),
            span("plan.result", 1, t + 8_100_000, t + 8_400_000, r),
            span("plan.result", 1, t + 8_600_000, t + 8_800_000, r)]
    acc = ha.Account(reduced(spans, -1, 20_000_000))
    assert not acc.capped
    rows = dict(acc.operators())
    assert list(rows) == ["0.Filter", "1.HashAggregate"]
    f, g = rows["0.Filter"], rows["1.HashAggregate"]
    assert (f["n"], f["total"]) == (1, pytest.approx(1.0))
    assert (f["dispatch"], f["wait"], f["host_sync"]) \
        == (pytest.approx(0.3), pytest.approx(0.3), pytest.approx(0.4))
    assert g["dispatch"] == pytest.approx(0.3)      # outside ops.groupby
    assert g["inner"] == pytest.approx(1.5)         # ops.groupby's own
    assert (g["wait"], g["host_sync"]) \
        == (pytest.approx(0.7), pytest.approx(4.0))
    assert (g["lowerings"], g["lowering_ms"]) == (2, 1.5)
    # `plan.op`'s own time is dispatch and is not unnamed
    assert acc.unnamed_ms() == pytest.approx(
        (9.0 - 0.1 - 8.2 - 0.2) + (8.2 - 1.0 - 6.5 - 0.3))
    # the eager epilogue lies inside the wall time: only the stamps count
    assert dict(acc.plan_path())["result"] == pytest.approx(0.2)
    assert acc.idle_shares() is None                # no pass was given


def test_four_device_planes_read_times_and_counts_but_no_idle_share():
    """`q5.shuffle`: the walk's spans read as any eager request's; the two
    idle shares wait for a skew per device plane (PERF.md section 7) and
    the extra pass over the trace is not made."""
    t = 1_000_000
    spans = [span("plan.execute", 1, 0, 9 * t, 7, lowerings=0,
                  lowering_ms=0),
             span("plan.bind", 1, t // 10, t // 2, 7),
             span("plan.run", 1, t, 8 * t, 7),
             span("plan.op", 1, t, 7 * t, 7, op="3.HashJoin", lowerings=0,
                  lowering_ms=0),
             span("plan.exchange", 1, 2 * t, 6 * t, 7, how="hash"),
             span("ops.host_sync", 1, 2 * t, 3 * t, 7,
                  site="dist.part_counts"),
             span("plan.wait", 1, 4 * t, 6 * t, 7, site="dist.repartition"),
             span("plan.result", 1, 7 * t, 8 * t, 7)]
    ops = [("jit_repart", "fusion.1", "fusion", 4 * t, 5 * t,
            "%fusion.1 = s64[8]{0} fusion(%p)")]
    red = ps.Reduced({"spans": spans, "marks": [-1, 10 * t],
                      "devices": [ops, ops, ops, ops],
                      "skew": {"ns": 0, "lo": None, "hi": None}}, None)
    run = types.SimpleNamespace(trace={"devices": 4}, trace_dir="/nowhere",
                                executes=[], t_window0=0, _program_spans=red)
    acc = ha.of(run)
    assert acc.idle_shares() is None           # and /nowhere was not read
    assert harness.read_layer_metric("idle_wait_share", run) is None
    assert harness.read_layer_metric("device_wait_ms", run) == 2.0
    assert harness.read_layer_metric("bind_ms", run) == 0.4
    assert harness.read_layer_metric("lowerings_per_request", run) == 0
    (row,) = acc.operators()
    assert row[0] == "3.HashJoin" and row[1]["inner"] == pytest.approx(1.0)
    assert row[1]["dispatch"] == pytest.approx(2.0)


# ---- recorded traces ---------------------------------------------------------------------

def fake_run(tmp_path, name):
    """A traced run as the readers see it, over one recorded trace."""
    where = tmp_path / "plugins" / "profile" / "x"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, name), where / name)
    loaded = ps.load(str(where / name))
    run = types.SimpleNamespace(
        trace={"devices": len(loaded["devices"])}, trace_dir=str(tmp_path),
        executes=[], t_window0=0, window_compiles=(0, 0, 0.0))
    run._program_spans = ps.Reduced(loaded, None)
    return run


def test_a_trace_without_the_new_spans_gives_none_from_every_reader(
        tmp_path):
    run = fake_run(tmp_path, "program.xplane.pb")      # PR 26's program
    assert ha.of(run) is None
    assert len(NEW_METRICS) == 12 and "bind_ms" in NEW_METRICS
    for name in NEW_METRICS:
        assert harness.read_layer_metric(name, run) is None, name


def test_an_untraced_run_gives_none(tmp_path):
    run = types.SimpleNamespace(trace=None)
    assert ha.of(run) is None
    assert harness.read_layer_metric("idle_wait_share", run) is None
