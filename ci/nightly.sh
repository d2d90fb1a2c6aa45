#!/usr/bin/env bash
# Nightly build (reference: ci/nightly-build.sh adds the sanitizer tier and
# extra arches). Here: full suite, larger bench pass, fuzz tier, and the
# multi-chip dry run.
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
python benchmarks/run_all.py --scale 0.01 --iters 5 --cpu
# chaos soak (docs/robustness.md): NDS plans under a seeded faultinj config
# (mixed nonfatal + one fatal) — asserts result parity with the fault-free
# run, non-zero retry/degraded counts, and breaker recovery via
# reset_device(); emits retries/faults_injected/degraded JSONL fields
JAX_PLATFORMS=cpu python benchmarks/chaos_soak.py --scale 0.2 --cpu
# multi-session serving soak (docs/serving.md): 8 concurrent tenant
# sessions submit a mixed q3/q5 workload through serving.ServingScheduler
# under the same seeded chaos config (transients + one fatal) — asserts
# per-session bit-exact parity for every completion, zero failed/starved
# sessions with a bounded p99 queue wait, >=1 parity-checked result-cache
# hit, and breaker recovery after reset_device(); emits one JSONL row per
# session with the session/queue_wait_ms/cache_hit stamps
# (lint_metrics-enforced)
JAX_PLATFORMS=cpu python benchmarks/chaos_soak.py --scale 0.2 --cpu --sessions 8
# fleet soak (docs/serving.md#fleet): the same chaos storm through
# serving.FleetScheduler — 8 tenant sessions over 3 executor workers with
# one worker KILLED mid-storm while holding in-flight work. Asserts zero
# failed sessions (dead worker's queued jobs replay on survivors),
# bit-exact per-session parity for every completion, a bounded p99 queue
# wait, and >=1 parity-checked cache hit SERVED by a different worker
# than the one that COMPUTED it (consistent-hash locality + promotion);
# per-session JSONL rows carry the worker_id stamp (lint_metrics-enforced).
# The run then adds a SELF-HEALING phase (docs/serving.md#fleet-self-
# healing) on a respawning fleet: a kill, two poison-plan breaker trips
# on distinct workers, and a graceful drain, all mid-storm — asserts the
# fleet heals back to its target size with zero failed sessions, the
# poison fingerprint quarantined after the second distinct-worker trip
# (never a third), a post-kill replica cache hit from a different
# worker, and a gossip-warmed rehome (observed-bytes charge, one
# compile); the self-heal JSONL row stamps respawns + worker_id
# (lint_metrics missing-respawn-stamp rule)
JAX_PLATFORMS=cpu python benchmarks/chaos_soak.py --scale 0.2 --cpu --sessions 8 --workers 3
# lockdep-armed fleet soak (runtime/lockdep.py, docs/analysis.md#
# concurrency-invariants): the same storm — self-healing phase included,
# so the respawn/drain/gossip paths are witnessed too — with every
# engine lock traced by the runtime lock-order witness; FAILS on any
# observed lock-order cycle or any dynamic edge missing from the static
# linter's graph (tools/lint_concurrency.py), and rows stamp
# lockdep_edges/lockdep_cycles so the JSONL history shows witness
# coverage
JAX_PLATFORMS=cpu SPARK_RAPIDS_TPU_LOCKDEP=1 \
    python benchmarks/chaos_soak.py --scale 0.2 --cpu --sessions 8 --workers 3
# optimizer parity (docs/optimizer.md): the four NDS plans, capped tier,
# optimizer off vs on — asserts result parity, nonzero pruned-column
# counts on q5/q72, and a fingerprint-keyed jit-cache hit on a rebuilt
# plan; emits optimizer/rules_fired JSONL fields
JAX_PLATFORMS=cpu python benchmarks/optimizer_parity.py --scale 0.1 --cpu
# adaptive-execution gate (docs/adaptive.md): NDS q5/q72 cold then warm
# under a fresh per-fingerprint stats store — bit-exact parity (warm ==
# cold == adaptivity-off), zero cap-escalation retries on the warm run
# (observed-cap seeding across executor instances), >=1 stats-driven
# build-side rewrite fired warm (through verify_rewrite), and warm wall
# <= cold wall; every JSONL row carries adaptive/stats_hits stamps
JAX_PLATFORMS=cpu python benchmarks/adaptive_bench.py --scale 0.1 --cpu
# co-placement gate (docs/optimizer.md#placement): NDS q5/q72 eager tier,
# placement rule off vs on, cold then warm under fresh stats stores —
# bit-exact parity on == off, q5 declines its DAG-shared date dimension
# (zero placed ops), q72 places its hd/dates build sides with measured
# placement_overlap_ms > 0, and the warm-on/warm-off wall ratio is
# reported to JSONL (gated strictly only on a real device backend, where
# the host threads are different silicon — not measured on the chip; on this
# CPU runner the ratio is bounded <= 1.5x against serialization
# regressions); rows stamp placement/placement_overlap_ms alongside
# backend+session (lint_metrics missing-placement-stamp rule)
JAX_PLATFORMS=cpu python benchmarks/coplace_bench.py --scale 0.1 --cpu
# streaming-scan gate (docs/io.md): parquet-bound vs table-bound parity in
# both tiers, nonzero row groups pruned on a selective predicate (with
# measurably fewer decoded bytes), and decode/execute overlap > 0 with the
# prefetch pipeline enabled; emits io_* + backend JSONL fields
JAX_PLATFORMS=cpu python benchmarks/streaming_scan.py --scale 0.5 --cpu
# distributed parity (docs/distributed.md): NDS q5/q72 through the
# full-plan SPMD tier on a 4-device simulated mesh — exact parity vs the
# single-device eager tier, >=1 broadcast and >=1 shuffle join selected by
# exchange_planning (checked on the executed plan), one sink gather, and
# nonzero exchange-bytes; emits n_devices/mesh_axis/exchange_bytes JSONL
# fields
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/distributed_parity.py --scale 0.2 --cpu
# exchange transport (docs/distributed.md#transport): NDS q5/q72 on the
# 4-device mesh with packing + async dispatch forced on — exact parity
# packed vs pack-off vs single-device, wire <= logical on every edge with
# wire <= 0.8x logical on at least one, wire <= the certified per-edge
# bound (footprint.check_observed), nonzero exchange/compute overlap-ms,
# and JSONL rows carrying exchange_bytes_wire/_logical/_overlap_ms
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/exchange_bench.py --scale 0.2 --cpu
# kernel-registry gate (docs/kernels.md): per-kernel parity (each Pallas
# kernel FORCED against its XLA fallback — interpret mode on CPU) plus the
# NDS q5/q72 capped tier registry-on vs forced-fallback with exact parity;
# on this CPU runner it additionally asserts auto-selection picked no
# accelerator kernel, and the capped-tier speedup gate arms itself
# whenever a TPU backend is present; emits per-kernel JSONL rows with the
# `kernels` stamp
JAX_PLATFORMS=cpu python benchmarks/kernel_bench.py --scale 0.05 --cpu
# resource-certifier gate (docs/analysis.md): NDS q5/q72 eager, cold and
# warm under a fresh stats store — certified [lo,hi] row bounds hold for
# every operator (bytes too, eager tier), a 1-byte budget rejects at
# admission with the operator named, and the bound-tightness ratio
# (certified/observed, median + max) is emitted to JSONL — reported, not
# gated: bounds are sound by construction, this tracks whether they stay
# USEFUL
JAX_PLATFORMS=cpu python benchmarks/footprint_bench.py --scale 0.1 --cpu
# deep plan fuzz (docs/analysis.md): a seeded sweep of >=200 random plans
# over all 11 operator kinds — static verification (authored + optimized,
# per-rule re-validation), no optimizer fall-backs, small-plan eager
# parity optimized-vs-unoptimized (error parity included), cold-vs-warm
# adaptive parity, and certifier soundness + monotonicity (property 5:
# observed rows/bytes inside certified bounds on every run, optimized
# root bound <= authored); emits one JSONL summary row, and any failing
# seed replays standalone via
# `python -m spark_rapids_tpu.analysis.fuzz --start <seed> --count 1 -v`
JAX_PLATFORMS=cpu python benchmarks/plan_fuzz.py --seed0 1000 --count 200 --cpu
./ci/fuzz-test.sh
./ci/sanitizer.sh
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('multichip OK')"
# Multi-PROCESS mesh proof (jax.distributed, 2 procs x 4 CPU devices) runs
# in the pytest tier above: tests/test_multiproc_mesh.py.
# The chip is not reached from here: `python chip_smoke.py` runs through the
# builder's chip tool (README "Testing & benchmarking").
echo "nightly OK"
