"""Static plan verifier, plan fuzzer and hazard linter
(spark_rapids_tpu/analysis/, tools/lint_hazards.py, docs/analysis.md).

The regression tests here are the PR-review bug museum, machine-checked:
each historical finding (the PR 5 stale-partitioning-claim elision, the
fp build-side swap gate, the DAG-shared-scan pruning guard) appears as a
hand-built bad plan the verifier must reject — review comments promoted
to invariants.
"""
import importlib.util
import os

import numpy as np
import jax.numpy as jnp
import pytest

from spark_rapids_tpu import dtypes
from spark_rapids_tpu.columnar import Column, Table
from spark_rapids_tpu.analysis import (PlanVerificationError, verify,
                                       verify_rewrite)
from spark_rapids_tpu.analysis.fuzz import (ALL_KINDS, gen_case, run_case,
                                            run_corpus)
from spark_rapids_tpu.plan import (Exchange, Filter, HashAggregate,
                                   HashJoin, Plan, PlanBuilder,
                                   PlanExecutor, PlanValidationError,
                                   Project, Scan, Union, col, lit)
from spark_rapids_tpu.plan import optimizer as opt_mod


def _tbl(**cols) -> Table:
    out, names = [], []
    for n, v in cols.items():
        a = np.asarray(v)
        dt = dtypes.FLOAT64 if a.dtype.kind == "f" else (
            dtypes.BOOL if a.dtype.kind == "b" else dtypes.INT64)
        out.append(Column(dtype=dt, length=len(a),
                          data=jnp.asarray(a.astype(dt.storage_dtype()))))
        names.append(n)
    return Table(out, names=names)


def _invariants(report):
    return {v.invariant for v in report.violations}


# ---------------------------------------------------------------------------
# error vocabulary: builder-time and execute-time share one exception type
# ---------------------------------------------------------------------------

class TestErrorVocabulary:
    def test_builder_raises_verification_error_with_invariant(self):
        b = PlanBuilder()
        with pytest.raises(PlanVerificationError) as ei:
            b.scan("t", schema=["a"]).filter(col("nope") == 1).build()
        assert isinstance(ei.value, PlanValidationError)
        v = ei.value.violations[0]
        assert v.invariant.startswith("schema")
        assert v.node.startswith("Filter#")
        assert "nope" in v.message

    def test_bind_time_same_vocabulary(self):
        b = PlanBuilder()
        plan = b.scan("t").filter(col("nope") == 1).build()
        with pytest.raises(PlanVerificationError) as ei:
            PlanExecutor().execute(plan, {"t": _tbl(a=[1, 2])})
        assert ei.value.violations[0].invariant.startswith("schema")


# ---------------------------------------------------------------------------
# typing layer
# ---------------------------------------------------------------------------

class TestTyping:
    DT = {"t": {"a": dtypes.INT64, "f": dtypes.FLOAT64}}

    def test_non_bool_predicate_rejected(self):
        plan = Plan(Filter(Scan("t", ("a", "f")), col("a") + lit(1)))
        rep = verify(plan, bound={"t": ("a", "f")}, input_dtypes=self.DT)
        assert "typing.predicate-not-bool" in _invariants(rep)

    def test_bitwise_on_float_rejected(self):
        plan = Plan(Filter(Scan("t", ("a", "f")), col("f") & col("a")))
        rep = verify(plan, bound={"t": ("a", "f")}, input_dtypes=self.DT)
        assert "typing.bitwise-on-float" in _invariants(rep)

    def test_comparison_predicate_clean(self):
        plan = Plan(Filter(Scan("t", ("a", "f")), col("f") > lit(0.5)))
        rep = verify(plan, bound={"t": ("a", "f")}, input_dtypes=self.DT)
        assert rep.ok, rep.violations

    def test_string_columns_pass_through_clean(self, monkeypatch):
        """Bare ColumnRefs zero-copy through _project and grouped
        min/count handle strings (validity / value-ordered-sort paths):
        a plan carrying a STRING column through a bare-ref Project into
        such an aggregate is VALID and must ride the gate untouched;
        only data-buffer reductions (sum/mean) flag."""
        from examples.nds import strings_column_from_list
        monkeypatch.setenv("SPARK_RAPIDS_TPU_VERIFY_PLANS", "1")
        s = strings_column_from_list([b"bb", b"aa", b"cc", b"aa"])
        k = Column(dtype=dtypes.INT64, length=4,
                   data=jnp.asarray(np.array([1, 1, 2, 2])))
        t = Table([k, s], names=["k", "s"])
        b = PlanBuilder()
        plan = (b.scan("t", schema=["k", "s"]).select(["k", "s"])
                 .aggregate(["k"], [("s", "min", "m"),
                                    ("s", "count", "c")])
                 .sort(["k"]).build())
        res = PlanExecutor().execute(plan, {"t": t})
        assert res.table.to_pydict() == {
            "k": [1, 2], "m": ["aa", "aa"], "c": [2, 2]}
        # ...but summing the chars buffer IS a definite error
        bad = (b.scan("t", schema=["k", "s"])
                .aggregate(["k"], [("s", "sum", "x")]).build())
        rep = verify(bad, bound={"t": ("k", "s")},
                     input_dtypes={"t": {"k": dtypes.INT64,
                                         "s": s.dtype}})
        assert "typing.agg-over-non-scalar" in _invariants(rep)


# ---------------------------------------------------------------------------
# scan-pruning legality (the DAG-shared-scan pushdown guard, as an invariant)
# ---------------------------------------------------------------------------

class TestScanPruning:
    def test_shared_scan_with_predicate_rejected(self):
        scan = Scan("t", ("a", "v"), predicate=col("a") > lit(1))
        u = Union((Filter(scan, col("a") > lit(1)),
                   Filter(scan, col("v") > lit(0))))
        rep = verify(Plan(u), bound={"t": ("a", "v")})
        assert "pruning.shared-scan" in _invariants(rep)

    def test_unenforced_predicate_rejected(self):
        scan = Scan("t", ("a", "v"), predicate=col("a") > lit(1))
        rep = verify(Plan(Project(scan, (("a", col("a")),))),
                     bound={"t": ("a", "v")})
        assert "pruning.unenforced-predicate" in _invariants(rep)

    def test_unretained_conjunct_rejected(self):
        # the scan prunes on a > 5 but the retained filter keeps a > 1:
        # row groups the plan still wants could be skipped
        scan = Scan("t", ("a", "v"), predicate=col("a") > lit(5))
        rep = verify(Plan(Filter(scan, col("a") > lit(1))),
                     bound={"t": ("a", "v")})
        assert "pruning.unretained-conjunct" in _invariants(rep)

    def test_lowered_conjunct_subset_clean(self):
        # exactly the scan_pruning rule's output shape: provable conjunct
        # lowered, full predicate retained above
        pred = (col("a") > lit(1)) & (col("v") > col("a"))
        scan = Scan("t", ("a", "v"), predicate=col("a") > lit(1))
        rep = verify(Plan(Filter(scan, pred)), bound={"t": ("a", "v")})
        assert rep.ok, rep.violations

    def test_gate_rejects_at_execute(self, monkeypatch):
        monkeypatch.setenv("SPARK_RAPIDS_TPU_VERIFY_PLANS", "1")
        scan = Scan("t", ("a", "v"), predicate=col("a") > lit(1))
        u = Union((Filter(scan, col("a") > lit(1)),
                   Filter(scan, col("v") > lit(0))))
        with pytest.raises(PlanVerificationError, match="shared-scan"):
            PlanExecutor().execute(Plan(u), {"t": _tbl(a=[1, 2],
                                                       v=[3, 4])})


# ---------------------------------------------------------------------------
# partitioning soundness (the PR 5 stale-claim bug as a verifier error)
# ---------------------------------------------------------------------------

BOUND = {"t": ("a", "b", "v"), "l": ("a", "v"), "r": ("b", "w")}


class TestPartitioning:
    def test_stale_partitioning_claim_rejected(self):
        """The PR 5 shape: a stacked consumer whose exchange was elided on
        a claim its input does not provide — the shard-local merge would
        emit duplicate groups. Review comment, now a verifier error."""
        scan = Scan("t", ("a", "b", "v"))
        ex = Exchange(scan, ("a",), how="hash")
        agg1 = HashAggregate(ex, ("a",), (("v", "sum", "s"),))
        agg2 = HashAggregate(agg1, ("s",), (("a", "count", "c"),))
        plan = Plan(Exchange(agg2, (), how="gather"))
        rep = verify(plan, bound=BOUND, planned=True)
        assert "partitioning.agg-not-colocated" in _invariants(rep)
        bad = [v for v in rep.violations
               if v.invariant == "partitioning.agg-not-colocated"]
        assert bad[0].node == agg2.label      # names the right operator

    def test_justified_elision_clean(self):
        # same stack, second aggregate keyed by a SUBSET of the claim:
        # the elision is justified and the verifier proves it
        scan = Scan("t", ("a", "b", "v"))
        ex = Exchange(scan, ("a",), how="hash")
        agg1 = HashAggregate(ex, ("a", "b"), (("v", "sum", "s"),))
        agg2 = HashAggregate(agg1, ("a",), (("s", "sum", "s2"),))
        plan = Plan(Exchange(agg2, (), how="gather"))
        rep = verify(plan, bound=BOUND, planned=True)
        assert rep.ok, rep.violations

    def test_elided_shuffle_join_rejected(self):
        """A shuffle join with only one side exchanged: matching keys are
        not provably co-located — the elided shuffle would drop/duplicate
        matches."""
        l = Exchange(Scan("l", ("a", "v")), ("a",), how="hash")
        r = Scan("r", ("b", "w"))
        join = HashJoin(l, r, ("a",), ("b",))
        plan = Plan(Exchange(join, (), how="gather"))
        rep = verify(plan, bound=BOUND, planned=True)
        assert "partitioning.join-not-colocated" in _invariants(rep)

    def test_planned_shuffle_join_clean(self):
        l = Exchange(Scan("l", ("a", "v")), ("a",), how="hash")
        r = Exchange(Scan("r", ("b", "w")), ("b",), how="hash")
        join = HashJoin(l, r, ("a",), ("b",))
        plan = Plan(Exchange(join, (), how="gather"))
        rep = verify(plan, bound=BOUND, planned=True)
        assert rep.ok, rep.violations

    def test_broadcast_join_clean(self):
        l = Scan("l", ("a", "v"))
        r = Exchange(Scan("r", ("b", "w")), (), how="broadcast")
        join = HashJoin(l, r, ("a",), ("b",))
        plan = Plan(Exchange(join, (), how="gather"))
        rep = verify(plan, bound=BOUND, planned=True)
        assert rep.ok, rep.violations

    def test_missing_sink_gather_rejected(self):
        l = Exchange(Scan("l", ("a", "v")), ("a",), how="hash")
        r = Exchange(Scan("r", ("b", "w")), ("b",), how="hash")
        plan = Plan(HashJoin(l, r, ("a",), ("b",)))
        rep = verify(plan, bound=BOUND, planned=True)
        assert "partitioning.unsunk-root" in _invariants(rep)

    def test_double_gather_rejected(self):
        scan = Scan("l", ("a", "v"))
        g1 = Exchange(scan, (), how="gather")
        g2 = Exchange(g1, (), how="gather")
        rep = verify(Plan(g2), bound=BOUND, planned=True)
        assert "partitioning.redundant-gather" in _invariants(rep)

    def test_exchange_planner_output_verifies(self):
        """The real exchange_planning output over an NDS-ish shape must
        pass the strict partitioning layer — verifier and planner derive
        claims from the SAME transfer function."""
        b = PlanBuilder()
        plan = (b.scan("l", schema=["a", "v"], est_rows=100_000)
                 .join(b.scan("r", schema=["b", "w"], est_rows=90_000),
                       left_on="a", right_on="b")
                 .aggregate(["a"], [("v", "sum", "s")]).build())
        opt, report = opt_mod.optimize(
            plan, {"l": ("a", "v"), "r": ("b", "w")},
            {"l": 100_000, "r": 90_000}, mesh_peers=4)
        assert report.rules["exchange_planning"] > 0
        rep = verify(opt, bound={"l": ("a", "v"), "r": ("b", "w")},
                     planned=True)
        assert rep.ok, rep.violations


# ---------------------------------------------------------------------------
# rewrite-pair checks (the fp build-side swap gate, as an invariant)
# ---------------------------------------------------------------------------

def _swap_shape(with_agg: bool):
    l = Scan("l", ("a", "v"))
    r = Scan("r", ("b", "w"))
    authored_join = HashJoin(l, r, ("a",), ("b",))
    authored_root = (HashAggregate(authored_join, ("a",),
                                   (("v", "sum", "s"),))
                     if with_agg else authored_join)
    swapped = HashJoin(r, l, ("b",), ("a",))
    restore = Project(swapped,
                      tuple((n, col(n)) for n in ("a", "v", "b", "w")))
    opt_root = (HashAggregate(restore, ("a",), (("v", "sum", "s"),))
                if with_agg else restore)
    return Plan(authored_root), Plan(opt_root)


class TestRewrite:
    def test_fp_build_side_swap_rejected(self):
        """The build_side rule's fp gate as a pair invariant: the exact
        rewrite the rule would produce, hand-built, is rejected whenever
        the inputs carry floats — fp reductions are not reorder-exact."""
        authored, optimized = _swap_shape(with_agg=True)
        rep = verify_rewrite(authored, optimized, bound=BOUND,
                             float_inputs=True)
        assert "rewrite.fp-build-side" in _invariants(rep)

    def test_integer_swap_under_aggregate_clean(self):
        authored, optimized = _swap_shape(with_agg=True)
        rep = verify_rewrite(authored, optimized, bound=BOUND,
                             float_inputs=False)
        assert rep.ok, rep.violations

    def test_order_observable_swap_rejected(self):
        authored, optimized = _swap_shape(with_agg=False)
        rep = verify_rewrite(authored, optimized, bound=BOUND,
                             float_inputs=False)
        assert "rewrite.order-unsafe-swap" in _invariants(rep)

    def test_swap_detected_despite_reversed_pair_aliasing(self):
        """A plan that authors BOTH (a)/(b) and (b)/(a) joins must not
        hide a swap of one of them: detection is multiset-based, not set
        membership."""
        s1, s2 = Scan("s1", ("a", "p")), Scan("s2", ("b", "q"))
        s3, s4 = Scan("s3", ("b", "r")), Scan("s4", ("a", "t"))
        j1 = HashJoin(s1, s2, ("a",), ("b",))            # (a)/(b)
        j2 = HashJoin(s3, s4, ("b",), ("a",))            # (b)/(a) authored
        semi = HashJoin(j1, j2, ("a",), ("a",), how="left_semi")
        authored = Plan(HashAggregate(semi, ("a",), (("p", "sum", "s"),)))
        # swapped j1 -> (b)/(a): its reversed pair is ALSO authored
        j1s = Project(HashJoin(s2, s1, ("b",), ("a",)),
                      tuple((n, col(n)) for n in ("a", "p", "b", "q")))
        semi2 = HashJoin(j1s, j2, ("a",), ("a",), how="left_semi")
        optimized = Plan(HashAggregate(semi2, ("a",),
                                       (("p", "sum", "s"),)))
        rep = verify_rewrite(authored, optimized, float_inputs=True)
        assert "rewrite.fp-build-side" in _invariants(rep)
        # and the identical un-swapped pair of plans stays clean
        rep2 = verify_rewrite(authored, authored, float_inputs=True)
        assert rep2.ok, rep2.violations

    def test_schema_drift_rejected(self):
        b = PlanBuilder()
        authored = b.scan("l", schema=["a", "v"]).build()
        optimized = (PlanBuilder().scan("l", schema=["a", "v"])
                     .select(["a"]).build())
        rep = verify_rewrite(authored, optimized,
                             bound={"l": ("a", "v")})
        assert "rewrite.schema-drift" in _invariants(rep)


# ---------------------------------------------------------------------------
# optimizer fall-back: precise diagnostic instead of a bare flag
# ---------------------------------------------------------------------------

def _patch_bad_rule(monkeypatch):
    def bad_rule(root, ctx):
        return Filter(root, col("__nope__") == lit(1)), 1
    patched = tuple((n, bad_rule) if n == "select_fusion" else (n, r)
                    for n, r in opt_mod._RULES)
    monkeypatch.setattr(opt_mod, "_RULES", patched)


class TestFallbackDiagnostics:
    @pytest.mark.parametrize("verify_rules", [False, True])
    def test_fallback_names_rule_node_invariant(self, monkeypatch,
                                                verify_rules):
        _patch_bad_rule(monkeypatch)
        b = PlanBuilder()
        plan = (b.scan("t", schema=["a", "v"]).filter(col("a") > 1)
                 .build())
        opt, report = opt_mod.optimize(plan, {"t": ("a", "v")}, {"t": 8},
                                       verify_rules=verify_rules)
        assert report.fell_back and opt is plan
        assert report.fallback is not None
        assert report.fallback["rule"] == "select_fusion"
        assert report.fallback["invariant"].startswith("schema")
        assert report.fallback["node"].startswith("Filter#")
        assert "__nope__" in report.fallback["message"]
        assert report.fallback == report.to_dict()["fallback"]
        assert "select_fusion" in report.summary()

    @pytest.mark.parametrize("verify_rules", [False, True])
    def test_attribution_uses_bound_schemas(self, monkeypatch,
                                            verify_rules):
        """A scan with NO declared schema resolves only against the bound
        tables: the per-rule check and the post-hoc attribution must
        validate against `bound` or they blame the victim rule the bad
        DAG later detonates inside, not the culprit."""
        def bad_rule(root, ctx):
            return Filter(root, col("__nope__") == lit(1)), 1
        patched = tuple((n, bad_rule) if n == "constant_folding" else
                        (n, r) for n, r in opt_mod._RULES)
        monkeypatch.setattr(opt_mod, "_RULES", patched)
        plan = PlanBuilder().scan("t").filter(col("a") > 1).build()
        opt, report = opt_mod.optimize(plan, {"t": ("a", "v")}, {"t": 8},
                                       verify_rules=verify_rules)
        assert report.fell_back and opt is plan
        assert report.fallback["rule"] == "constant_folding"
        assert "__nope__" in report.fallback["message"]

    def test_clean_optimize_has_no_fallback(self):
        b = PlanBuilder()
        plan = (b.scan("t", schema=["a", "v"]).filter(col("a") > 1)
                 .select(["a"]).build())
        _, report = opt_mod.optimize(plan, {"t": ("a", "v")}, {"t": 8},
                                     verify_rules=True)
        assert not report.fell_back and report.fallback is None

    def test_executed_result_surfaces_fallback(self, monkeypatch):
        _patch_bad_rule(monkeypatch)
        b = PlanBuilder()
        plan = b.scan("t", schema=["a", "v"]).filter(col("a") > 1).build()
        res = PlanExecutor().execute(plan, {"t": _tbl(a=[1, 2, 3],
                                                      v=[4, 5, 6])})
        assert res.optimizer["fell_back"]
        assert res.optimizer["fallback"]["rule"] == "select_fusion"
        # the authored plan ran and is still correct
        assert res.table.to_pydict()["a"] == [2, 3]


# ---------------------------------------------------------------------------
# fuzzer: determinism, coverage, parity
# ---------------------------------------------------------------------------

class TestFuzzer:
    def test_same_seed_same_plan_and_data(self):
        c1, c2 = gen_case(42), gen_case(42)
        assert c1.plan.fingerprint == c2.plan.fingerprint
        assert set(c1.tables) == set(c2.tables)
        for name in c1.tables:
            t1, t2 = c1.tables[name], c2.tables[name]
            assert list(t1.names) == list(t2.names)
            for a, b in zip(t1.columns, t2.columns):
                assert np.array_equal(np.asarray(a.data),
                                      np.asarray(b.data))

    def test_distinct_seeds_distinct_plans(self):
        fps = {gen_case(s).plan.fingerprint for s in range(12)}
        assert len(fps) > 6       # not degenerate

    def test_premerge_corpus_covers_all_kinds(self):
        kinds = set()
        for s in range(24):
            kinds.update(gen_case(s).kinds)
        assert kinds == set(ALL_KINDS)

    @pytest.mark.parametrize("start", [0, 8, 16])
    def test_corpus_verify_and_parity(self, start):
        """The premerge corpus (seeds 0..23, every node kind: the test
        above), eight seeds a case."""
        summary = run_corpus(range(start, start + 8), execute=True)
        assert summary["cases"] == summary["executed"] == 8
        assert not summary["failures"], summary["failures"]

    def test_case_properties_individually(self):
        r = run_case(gen_case(7))
        assert r.ok and r.executed and r.parity


# ---------------------------------------------------------------------------
# hazard linter
# ---------------------------------------------------------------------------

def _load_linter():
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "lint_hazards", os.path.join(root, "tools", "lint_hazards.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["lint_hazards"] = mod     # dataclass needs the module
    spec.loader.exec_module(mod)
    return mod


_HAZARD_SRC = '''
import os
from functools import partial
import jax
import numpy as np

CACHE = {}

def build(self, key):
    fn = CACHE.get(key)
    if fn is None:
        fn = jax.jit(lambda x: x * self.scale)
        CACHE[key] = fn
    return fn

@partial(jax.jit, static_argnames=("flag",))
def kern(x, flag):
    if flag:
        x = x + 1
    if x > 0:
        x = x - 1
    return float(np.asarray(x))

def knob():
    return os.environ.get("SOME_KNOB", "")

def my_fingerprint(d):
    return tuple(d.items())
'''


class TestHazardLinter:
    def test_catches_each_rule(self, tmp_path):
        lint = _load_linter()
        f = tmp_path / "hazmod.py"
        f.write_text(_HAZARD_SRC)
        findings = lint.lint_paths([str(f)], str(tmp_path))
        rules = {x.rule for x in findings}
        assert {"jit-self-capture", "tracer-branch", "host-sync-in-jit",
                "env-outside-config", "fingerprint-iteration"} <= rules
        # the static_argnames branch is specialization, not a hazard
        tracer = [x for x in findings if x.rule == "tracer-branch"]
        assert len(tracer) == 1 and tracer[0].context == "kern"

    def test_catches_bound_method_and_partial_jit(self, tmp_path):
        """The canonical PR 5 shape without a lambda: `jax.jit(bound
        method)` / `jax.jit(partial(bound method, ...))` pins the
        instance just the same and must not slip the gate."""
        lint = _load_linter()
        f = tmp_path / "boundmod.py"
        f.write_text(
            "import jax\n"
            "from functools import partial\n"
            "CACHE = {}\n"
            "class C:\n"
            "    def use(self, key, axis):\n"
            "        if key not in CACHE:\n"
            "            CACHE[key] = jax.jit(self._prim)\n"
            "            CACHE[key + 1] = jax.jit(partial(self._prim, "
            "axis))\n"
            "        return CACHE[key]\n")
        findings = lint.lint_paths([str(f)], str(tmp_path))
        hits = [x for x in findings if x.rule == "jit-self-capture"]
        assert len(hits) == 2, findings

    def test_catches_from_os_import_alias(self, tmp_path):
        lint = _load_linter()
        f = tmp_path / "aliasmod.py"
        f.write_text("from os import getenv, environ\n"
                     "def knob():\n"
                     "    return getenv('SPARK_RAPIDS_TPU_X')\n")
        findings = lint.lint_paths([str(f)], str(tmp_path))
        hits = [x for x in findings if x.rule == "env-outside-config"]
        assert len(hits) == 2, findings     # one per imported alias

    def test_allowlist_requires_justification(self, tmp_path):
        lint = _load_linter()
        good = tmp_path / "allow.txt"
        good.write_text("a.py::tracer-branch::f  # vetted because X\n")
        assert lint.load_allowlist(str(good)) == {
            ("a.py", "tracer-branch", "f"): "vetted because X"}
        bad = tmp_path / "bad.txt"
        bad.write_text("a.py::tracer-branch::f\n")
        with pytest.raises(SystemExit):
            lint.load_allowlist(str(bad))

    def test_lock_discipline_inconsistent_guard(self, tmp_path):
        """Mutating an attribute the class locks elsewhere, without the
        lock: the PR 11 thread-safety classes (StatsStore,
        KernelRegistry), machine-checked."""
        lint = _load_linter()
        f = tmp_path / "lockmod.py"
        f.write_text(
            "import threading\n"
            "class Store:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "        self._plans = {}\n"
            "        self.hits = 0\n"
            "    def record(self, k, v):\n"
            "        with self._lock:\n"
            "            self._plans[k] = v\n"
            "            self.hits += 1\n"
            "    def load(self, items):\n"
            "        for k, v in items:\n"
            "            self._plans[k] = v\n"      # BAD: no lock
            "    def _fill_locked(self, k):\n"
            "        self._plans[k] = 1\n"          # fine: convention
            "    def unrelated(self):\n"
            "        self.note = 1\n")              # never locked: fine
        findings = lint.lint_paths([str(f)], str(tmp_path))
        hits = [x for x in findings if x.rule == "lock-discipline"]
        assert len(hits) == 1 and hits[0].context == "Store.load", findings

    def test_global_mutation_rule(self, tmp_path):
        lint = _load_linter()
        f = tmp_path / "globmod.py"
        f.write_text(
            "import threading\n"
            "_g_lock = threading.Lock()\n"
            "_A = None\n"
            "_B = None\n"
            "def bad():\n"
            "    global _A\n"
            "    if _A is None:\n"
            "        _A = object()\n"               # BAD: unguarded
            "    return _A\n"
            "def good():\n"
            "    global _B\n"
            "    with _g_lock:\n"
            "        if _B is None:\n"
            "            _B = object()\n"           # fine: under the lock
            "    return _B\n")
        findings = lint.lint_paths([str(f)], str(tmp_path))
        hits = [x for x in findings if x.rule == "global-mutation"]
        assert len(hits) == 1 and hits[0].context == "bad", findings

    def test_stale_allowlist_entry_fails_the_run(self, tmp_path,
                                                 capsys):
        """A stale entry is a premerge FAILURE (exit 1), not a note."""
        lint = _load_linter()
        src = tmp_path / "clean.py"
        src.write_text("x = 1\n")
        allow = tmp_path / "allow.txt"
        allow.write_text(
            "gone.py::tracer-branch::old_fn  # vetted long ago\n")
        rc = lint.main([str(src), "--allowlist", str(allow)])
        assert rc == 1
        assert "STALE" in capsys.readouterr().out
        # an empty allowlist over a clean file: exit 0
        allow.write_text("")
        assert lint.main([str(src), "--allowlist", str(allow)]) == 0

    def test_repo_is_clean_under_allowlist(self):
        """The premerge contract, asserted in-tree: the linter over
        spark_rapids_tpu/ has no unsuppressed findings AND no stale
        allowlist entries."""
        lint = _load_linter()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        allow = lint.load_allowlist(
            os.path.join(root, "tools", "lint_hazards_allowlist.txt"))
        findings = lint.lint_paths(
            [os.path.join(root, "spark_rapids_tpu")], root)
        open_findings = [f for f in findings if f.key() not in allow]
        assert not open_findings, "\n".join(map(str, open_findings))
        stale = set(allow) - {f.key() for f in findings}
        assert not stale, f"stale allowlist entries: {sorted(stale)}"
