#!/usr/bin/env bash
# Premerge gate (reference: ci/premerge-build.sh runs `mvn verify` with tests
# on). Linters, the fixed fuzz corpus, the full unit suite on the 8-device
# CPU mesh (native build included) and the arbiter Monte Carlo.
set -euo pipefail
cd "$(dirname "$0")/.."

python -c "import spark_rapids_tpu; print('import ok:', spark_rapids_tpu.__name__)"
# JAX-hazard linter (tools/lint_hazards.py, docs/analysis.md): AST-checks
# the known hazard patterns (self capture in jit closure caches, host
# sync on traced values, tracer branches, env reads outside config.py,
# nondeterministic iteration feeding fingerprints, inconsistent lock
# guards on shared-state classes, unguarded module-global mutation);
# vetted exceptions live in tools/lint_hazards_allowlist.txt with
# one-line justifications — STALE entries fail the run, prune them
python tools/lint_hazards.py spark_rapids_tpu
# concurrency linter (tools/lint_concurrency.py, docs/analysis.md#
# concurrency-invariants): whole-tree lock-order graph (interprocedural
# "calls F while holding L" edges, any cycle fails with a witness path),
# unbounded blocking calls reached under a lock, and FleetWorker
# isolation (worker-owned state only via the sanctioned surfaces);
# vetted exceptions + witness-proven `edge::` declarations live in
# tools/lint_concurrency_allowlist.txt — STALE entries fail the run
python tools/lint_concurrency.py
# fixed fuzz corpus (analysis/fuzz.py): 24 seeded random plans covering
# all 12 node kinds — verify + optimize (per-rule re-validation) + eager
# optimized-vs-unoptimized parity + cold-vs-warm adaptive parity +
# certifier soundness/monotonicity; the nightly runs the deep sweep
JAX_PLATFORMS=cpu python -m spark_rapids_tpu.analysis.fuzz --start 0 --count 24 --cpu
python -m pytest tests/ -x -q
python tools/monte_carlo.py --tasks 16 --parallelism 4 --gpu-mib 512 \
    --task-max-mib 384 --shuffle-threads 2 --seed 1
echo "premerge OK"
