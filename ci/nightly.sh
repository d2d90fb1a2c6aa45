#!/usr/bin/env bash
# Nightly build (reference: ci/nightly-build.sh adds the sanitizer tier and
# extra arches). Here: full suite, deep plan fuzz, arbiter fuzz tier,
# sanitizer tier, and the multi-chip dry run.
set -euo pipefail
cd "$(dirname "$0")/.."

# native warning gate: new -Wall/-Wextra diagnostics in load-bearing native
# code fail the nightly before anything else runs
python - <<'PY'
from spark_rapids_tpu.native.build import check_warnings
warns = check_warnings()
if warns:
    print("native warnings detected:\n" + "\n".join(warns))
    raise SystemExit(1)
print("native warning gate: clean")
PY

python -m pytest tests/ -q -m ""    # include the nightly-marked tier
# lockdep-armed serving and fleet tests (runtime/lockdep.py,
# docs/analysis.md#concurrency-invariants): every engine lock traced by
# the runtime lock-order witness; tests/conftest.py FAILS the run on any
# observed lock-order cycle or any dynamic edge missing from the static
# linter's graph (tools/lint_concurrency.py)
SPARK_RAPIDS_TPU_LOCKDEP=1 python -m pytest tests/test_serving.py \
    tests/test_serving_stress.py tests/test_fleet.py -q
# deep plan fuzz (docs/analysis.md): a seeded sweep of 200 random plans
# over all 11 operator kinds — static verification (authored + optimized,
# per-rule re-validation), no optimizer fall-backs, small-plan eager
# parity optimized-vs-unoptimized (error parity included), cold-vs-warm
# adaptive parity, and certifier soundness + monotonicity (property 5:
# observed rows/bytes inside certified bounds on every run, optimized
# root bound <= authored); any failing seed replays standalone with
# `--start <seed> --count 1 -v`
JAX_PLATFORMS=cpu python -m spark_rapids_tpu.analysis.fuzz --start 1000 --count 200 --cpu
./ci/fuzz-test.sh
./ci/sanitizer.sh
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('multichip OK')"
# Multi-PROCESS mesh proof (jax.distributed, 2 procs x 4 CPU devices) runs
# in the pytest tier above: tests/test_multiproc_mesh.py.
# The chip is not reached from here: the benchmark (`python3 -m
# chipbench.run`, the four-chip cell `q5.shuffle` too) runs through the
# builder's chip tool (README "Testing & benchmarking").
echo "nightly OK"
