"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the reference's tests likewise never
need a cluster — SURVEY.md §4 "they don't need to"; multi-tenancy/multi-device
is simulated). The chip is reached only by `python3 -m chipbench.run`,
through the builder's chip tool — never by this suite; what the suite can say about the chip is
tests/test_chip_compile.py, which compiles the main path's kernels and
capped program for a described v5e.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

# Static plan verifier gate (analysis/verifier.py, docs/analysis.md): ON
# for the whole suite — every plan any test executes is symbolically
# verified pre-execution, and every optimizer rule's output re-validates.
# setdefault so a test (or developer) can still export =0 to bisect.
os.environ.setdefault("SPARK_RAPIDS_TPU_VERIFY_PLANS", "1")
# Per-fingerprint stats store (plan/stats.py, docs/adaptive.md): OFF for
# the suite. The store is process-global and keyed by STRUCTURAL
# fingerprints, so with it on, a test's cap-escalation counts and
# optimizer decisions would depend on which structurally identical plans
# earlier tests happened to run — order-dependent assertions. Adaptive
# behavior is tested deliberately in tests/test_adaptive.py (and the
# fuzzer's two-run property) through explicit `scoped_store`s, which
# outrank this default.
os.environ.setdefault("SPARK_RAPIDS_TPU_STATS", "off")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Runtime lock-order witness (runtime/lockdep.py, docs/analysis.md#
# concurrency-invariants): armed for the WHOLE suite by
# SPARK_RAPIDS_TPU_LOCKDEP=1. The module is loaded standalone and
# installed BEFORE any engine import so module-level locks (serving/
# cache's _digest_lock, plan/stats' _default_lock) are constructed
# through the patched factories; seeding sys.modules under the real
# dotted name makes every later `import spark_rapids_tpu.runtime.
# lockdep` resolve to this same instance. The env var is read directly
# (not via config.lockdep()) because importing the config module would
# import the engine package first — exactly what must not happen yet.
_LOCKDEP = None
if os.environ.get("SPARK_RAPIDS_TPU_LOCKDEP", "0").lower() \
        not in ("0", "", "off"):
    import importlib.util
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    _spec = importlib.util.spec_from_file_location(
        "spark_rapids_tpu.runtime.lockdep",
        os.path.join(_root, "spark_rapids_tpu", "runtime", "lockdep.py"))
    _LOCKDEP = importlib.util.module_from_spec(_spec)
    sys.modules[_spec.name] = _LOCKDEP
    _spec.loader.exec_module(_LOCKDEP)
    _LOCKDEP.install()

# jax may already be imported (a sitecustomize, a plugin): the env vars
# above are then too late for jax.config — set it directly as well.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# ---------------------------------------------------------------------------
# XLA memory-map pressure valve. XLA's CPU JIT mmap()s code pages for every
# compiled executable and the kernel caps a process at vm.max_map_count
# (~65530) mappings; the full suite compiles enough programs to reach
# ~60k maps, and any growth then dies MID-RUN with a segfault inside
# backend_compile — the crash lands on whichever test compiles next (the
# timezone kernels, historically), not on a culprit. Shed compiled
# programs when the count nears the cap: the persistent compilation
# cache below makes the recompiles cheap, and executor-level caches
# (fingerprint-keyed programs, caps memos) hold only PYTHON callables,
# so their own hit accounting is unaffected.
# ---------------------------------------------------------------------------
_MAPS_HIGH_WATER = 45_000


def _proc_map_count() -> int:
    try:
        with open(f"/proc/{os.getpid()}/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:          # non-Linux: no map cap to manage
        return 0


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _shed_xla_map_pressure():
    yield
    if _proc_map_count() > _MAPS_HIGH_WATER:
        jax.clear_caches()


@pytest.fixture
def lowers_nothing_again():
    """-> check(plan, inputs, other): an eager executor that has run `plan`
    over `inputs` once lowers nothing when it runs it again, nor over
    `other` (another seed's arrays of the same shapes and counts). ROADMAP
    D14's guard, one a one-chip eager cell, at the cell's rehearsal size."""
    from spark_rapids_tpu.plan import PlanExecutor
    from spark_rapids_tpu.utils import tracing

    def check(plan, inputs, other):
        ex = PlanExecutor(mode="eager")
        ex.execute(plan, inputs)
        with tracing.bracket("test.lowers_nothing_again") as b:
            res = ex.execute(plan, inputs)
        n, _ = b.lowered()
        assert (n, list(tracing._lowered.names)[-n:] if n else []) == (0, [])
        assert (res.lowerings, res.lowering_ms) == (0, 0.0)
        with tracing.bracket("test.lowers_nothing_again") as b:
            ex.execute(plan, other)
        assert b.lowered()[0] == 0, list(tracing._lowered.names)[-4:]
    return check


def pytest_sessionfinish(session, exitstatus):
    """Armed-run verdict: observed lock-order cycles or dynamic edges
    the static linter failed to predict FAIL the suite even when every
    test passed — the witness audits tools/lint_concurrency.py's
    interprocedural resolution on every armed run."""
    if _LOCKDEP is None or not _LOCKDEP.active():
        return
    rep = _LOCKDEP.certify()
    print(f"\nlockdep: {rep['observed']} observed edge class(es): "
          f"{len(rep['mapped'])} mapped to the static graph, "
          f"{len(rep['missing'])} missing from it, "
          f"{len(rep['unmapped'])} at unmodeled sites; "
          f"{len(rep['cycles'])} cycle(s)")
    for m in rep["missing"]:
        print(f"lockdep: dynamic edge NOT in static graph: {m}")
    for c in rep["cycles"]:
        print(f"lockdep: observed lock-order cycle: {c}")
    if not rep["ok"]:
        session.exitstatus = 1


# Persistent compilation cache: the suite jit-compiles hundreds of programs
# (the distributed SPMD bodies take minutes); caching them across runs cuts
# repeat suite time by an order of magnitude.
from spark_rapids_tpu.config import place_compile_cache  # noqa: E402

# JAX_COMPILATION_CACHE_DIR wins where it is set; <checkout>/.jax_cache
# otherwise (config.place_compile_cache — the one placement rule)
place_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
