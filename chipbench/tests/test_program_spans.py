"""`chipbench/program_spans.py` on the trace recorded on the chip by
`record_program_trace.py` (PR 26): three served requests into a capped
program of two operators, then two eager executions, with the program's
own spans in the trace and the owner map `device_op_owners` gave. Span
durations, self time, idle attribution, per-operator device time, a share
that cannot pass 100% — and the interval arithmetic on its own.

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q -p no:cacheprovider
"""
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from chipbench import program_spans as ps  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def loaded():
    return ps.load(os.path.join(DATA, "program.xplane.pb"))


@pytest.fixture(scope="module")
def owners():
    with open(os.path.join(DATA, "program.owners.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def capped(loaded, owners):
    return ps.Reduced(loaded, owners)


@pytest.fixture(scope="module")
def eager(loaded):
    return ps.Reduced(loaded, None)


# ---- the recorded trace ---------------------------------------------------------

def test_trace_holds_the_programs_spans_and_two_marks(loaded):
    names = {s["name"] for s in loaded["spans"]}
    assert {"serving.submit", "serving.digest", "serving.admit",
            "serving.enqueue", "serving.dispatch", "plan.execute",
            "plan.optimize", "plan.certify", "plan.run", "plan.attempt",
            "plan.op", "ops.host_sync"} <= names
    assert len(loaded["marks"]) == 2 and len(loaded["devices"]) == 1
    modules = {m for m, *_ in loaded["devices"][0]}
    assert ps.CAPPED_MODULE in modules and "jit_fn" not in modules


def test_requests_and_span_durations(capped):
    assert capped.requests["serving.submit"] == [1, 2, 3]
    assert capped.requests["plan.execute"] == [1, 2, 3, 100, 101]
    digest = capped.request_ms("serving.digest")
    assert len(digest) == 3 and all(0.5 < d < 100 for d in digest)
    # a served request certifies twice (admission, execute), an eager
    # execution once; every one of them is a span of the request
    assert capped.spans["plan.certify"]["count"] == 3 * 2 + 2
    assert capped.spans["serving.digest"]["attrs"]["bytes"] \
        == [2 * 8 * (1 << 16)] * 3
    assert capped.spans["serving.digest"]["attrs"]["hit"] == [0, 0, 0]
    assert capped.spans["plan.attempt"]["attrs"]["attempt"] == [1, 1, 1]
    assert capped.median_ms("plan.verify") == 0.0     # the gate was off
    syncs = capped.request_ms("ops.host_sync")
    assert syncs[:3] == [0.0, 0.0, 0.0] and all(s > 0 for s in syncs[3:])


def test_self_time_is_the_span_minus_its_children(capped):
    sub, run = capped.spans["serving.submit"], capped.spans["plan.run"]
    children = sum(capped.spans[n]["total_s"] for n in
                   ("serving.digest", "serving.admit", "serving.enqueue"))
    assert sub["self_s"] == pytest.approx(sub["total_s"] - children,
                                          abs=1e-6)
    assert 0 <= sub["self_s"] < sub["total_s"]
    leaf = capped.spans["ops.host_sync"]
    assert leaf["self_s"] == pytest.approx(leaf["total_s"])
    assert run["self_s"] < run["total_s"]


def test_idle_time_goes_to_the_deepest_span(capped):
    idle = {n: row["idle_s"] for n, row in capped.spans.items()}
    window_s = (capped.w1 - capped.w0) / 1e9
    assert capped.busy_s + capped.idle_s == pytest.approx(window_s)
    assert all(0 <= v <= capped.idle_s + 1e-9 for v in idle.values())
    # a digest hashes on the host with nothing on the device: its whole
    # duration is idle time; a parent only keeps what no child covers
    digest = capped.spans["serving.digest"]
    assert digest["idle_s"] == pytest.approx(digest["total_s"], rel=0.02)
    assert idle["serving.submit"] <= capped.spans["serving.submit"]["self_s"] \
        + 1e-9
    assert idle["plan.execute"] <= capped.spans["plan.execute"]["self_s"] \
        + 1e-9


def test_device_time_per_operator_capped_tier(capped, owners):
    assert set(owners.values()) >= {"2.HashJoin", "4.HashAggregate"}
    s = capped.owner_s
    assert s["2.HashJoin"] > 0 and s["4.HashAggregate"] > 0
    assert sum(s.values()) == pytest.approx(capped.busy_s, rel=1e-6)
    # the eager executions' programs are not the capped program's: unowned
    assert s[ps.UNOWNED] > 0
    for share in (capped.named_share(), capped.kind_share("HashJoin"),
                  capped.kind_share("HashAggregate")):
        assert 0 < share < 100
    assert capped.named_share() == pytest.approx(
        capped.kind_share("HashJoin") + capped.kind_share("HashAggregate")
        + capped.kind_share("Scan") + capped.kind_share("Project"))


def test_device_time_per_operator_eager_tier(eager):
    # by containment in the one caller's plan.op spans: the eager ops get
    # their operators, the capped program's (run under no plan.op) none
    s = eager.owner_s
    assert s["2.HashJoin"] > 0 and s["4.HashAggregate"] > 0
    assert s[ps.UNOWNED] > 0
    assert 0 < eager.named_share() < 100
    assert sum(s.values()) == pytest.approx(eager.busy_s, rel=1e-6)


def test_device_line_is_moved_onto_the_hosts_clock(loaded, capped, eager):
    """The trace's device line lay 1.4 ms before the host's: every program
    started on it before the host had begun to enqueue it. The shift comes
    from that causality, per run; with it each eager op lies inside its
    operator's span, so only the capped program's ops stay without one."""
    skew = loaded["skew"]
    assert 1_000_000 < skew["lo"] <= skew["ns"] <= skew["hi"] < 2_000_000
    capped_program = sum(s for o, s in capped.owner_s.items()
                         if o != ps.UNOWNED)
    assert eager.owner_s[ps.UNOWNED] == pytest.approx(capped_program,
                                                      rel=5e-3)


def test_device_skew_is_the_least_shift_causality_allows():
    launched = {"1": (1000, 1500), "2": (3000, 3200), "3": (9000, 9100)}
    enqueued = {"1": 1900, "2": 4400, "3": 9000}      # run 2: 1400 late
    completed = {"1": 3100, "2": 4800}                 # run 2: 1600 spare
    assert ps.device_skew(launched, enqueued, completed) \
        == {"ns": 1400, "lo": 1400, "hi": 1600}
    assert ps.device_skew(launched, enqueued, {"2": 4300})["ns"] == 1250
    assert ps.device_skew(launched, {}, completed) \
        == {"ns": 0, "lo": None, "hi": 1600}


def test_pallas_join_kernels_by_name_and_under_the_peak(capped):
    names = set(capped.kernels)
    assert any(n.startswith("pallas_hash_join_build") for n in names), names
    assert any(n.startswith("pallas_hash_join_probe") for n in names), names
    seconds, nbytes = capped.kernel("pallas_hash_join_")
    assert seconds > 0 and nbytes > 0
    share = 100.0 * nbytes / seconds / 819e9
    assert 0 < share < 100, share


# ---- the arithmetic on its own -----------------------------------------------------

def test_hlo_bytes_counts_each_buffer_once():
    text = ("%pallas_hash_join_probe.1 = u32[512,1,128]{2,1,0:T(1,128)} "
            "custom-call(u32[512,1,128]{2,1,0:T(1,128)} %a, "
            "u32[512,1,128]{2,1,0:T(1,128)S(1)} %b, "
            "u32[512,1,128]{2,1,0:T(1,128)} %a, f32[8,128]{1,0:T(8,128)} "
            "%tbl.3), custom_call_target=\"tpu_custom_call\", "
            "operand_layout_constraints={u32[512,1,128]{2,1,0}}")
    block = 512 * 128 * 4
    assert ps.hlo_bytes(text) == block + 2 * block + 8 * 128 * 4
    pair = ("%sort.6 = (s32[1024]{0:T(1024)}, s64[1024]{0}) sort(s32[1024]"
            "{0:T(1024)S(1)} %x, s64[1024]{0} %iota), dimensions={0}")
    assert ps.hlo_bytes(pair) == 2 * (1024 * 4 + 1024 * 8)
    assert ps.shape_bytes("pred[]") == 1


def test_nesting_self_time_and_deepest_segments():
    def span(name, t0, t1, thread=1):
        return {"name": name, "thread": thread, "t0": t0, "t1": t1,
                "attrs": {}}
    spans = [span("a", 0, 100), span("b", 10, 30), span("c", 15, 20),
             span("b", 50, 60), span("a", 40, 70, thread=2)]
    segments = sorted(ps._nest(spans))
    assert [s["self_ns"] for s in spans] == [70, 15, 5, 10, 30]
    assert segments == sorted([
        (0, 10, "a"), (10, 15, "b"), (15, 20, "c"), (20, 30, "b"),
        (30, 50, "a"), (50, 60, "b"), (60, 100, "a"), (40, 70, "a")])


def test_intersection_of_interval_lists():
    assert ps._intersect([(0, 10), (20, 30)], [(5, 25), (28, 40)]) \
        == [(5, 10), (20, 25), (28, 30)]
    assert ps._intersect([(0, 1)], [(1, 2)]) == []


def test_a_program_without_spans_reads_as_nothing(tmp_path):
    """The parent's traces hold no program span (tests/data/small.xplane.pb
    is one): `of` gives None, once, and a reader reports nothing."""
    import shutil
    from chipbench.harness import read_layer_metric
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "small.xplane.pb"), where)

    class Run:
        trace = {"busy_s": 1.0}
        trace_dir = str(tmp_path)
    run = Run()
    assert ps.of(run) is None and run._program_spans is None
    for metric in ("digest_ms", "certify_ms", "named_op_share",
                   "digest_idle_share", "pallas_join_bw_share"):
        assert read_layer_metric(metric, run) is None
