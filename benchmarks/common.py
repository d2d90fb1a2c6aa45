"""Shared micro-benchmark harness + random data generation.

Plays the role of the reference's nvbench + benchmarks/common/generate_input.cu
(SURVEY.md §2.3): every bench file declares configs over named axes, times the
op on-device with warmup (first call compiles under jit; steady-state is what
we report, like nvbench's cold/batched split), and prints one JSON line per
config:

    {"bench": ..., "axes": {...}, "ms": ..., "rows_per_s": ...}

Run any bench file directly, or all of them via `python benchmarks/run_all.py`.
`--scale` shrinks row counts (CI smoke / CPU runs).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Callable, Dict, List, Sequence

import numpy as np

import jax
import jax.numpy as jnp


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0,
                    help="multiply all num_rows axes by this (e.g. 0.01 for smoke)")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--sessions", type=int, default=0,
                    help="multi-session serving soak width "
                         "(benchmarks/chaos_soak.py: N concurrent tenant "
                         "sessions through serving/scheduler.py; 0 keeps "
                         "the legacy single-caller soak)")
    ap.add_argument("--workers", type=int, default=0,
                    help="fleet soak width (benchmarks/chaos_soak.py: "
                         "route --sessions tenants across N executor "
                         "workers via serving/fleet.py and kill one "
                         "mid-storm; 0 keeps the single-worker soak)")
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU backend (CI correctness smoke; a "
                         "number from such a run is not a device metric)")
    args = ap.parse_args(argv)
    if args.cpu:
        # a too-late pin (backend already initialized) silently no-ops, so
        # check the outcome positively rather than catching anything
        jax.config.update("jax_platforms", "cpu")
        if jax.default_backend() != "cpu":
            print(f"WARNING: --cpu could not pin the platform (backend "
                  f"already initialized as {jax.default_backend()!r}); "
                  f"benches will run on it", file=sys.stderr)
    return args


def steady_state_ms(fn: Callable, args, iters: int) -> float:
    """Milliseconds per call of `fn(*args)`, steady-state. `fn` must
    already be compiled/warmed (call it once first). Each iteration's
    outputs are blocked before the next dispatch, on every platform (one
    output alive at a time)."""
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) * 1e3 / iters


def emit_record(bench: str, axes: Dict, ms: float, n_rows: int, *,
                impl: str = None, retries: int = None,
                faults_injected: int = None, degraded: bool = None,
                optimizer: str = None, rules_fired: Dict = None,
                io_row_groups_pruned: int = None,
                io_bytes_skipped: int = None,
                io_overlap_ms: float = None,
                mesh_axis: str = None,
                exchange_bytes: int = None,
                exchange_bytes_logical: int = None,
                exchange_bytes_wire: int = None,
                exchange_overlap_ms: float = None,
                kernels=None,
                stats_hits: int = None,
                adaptive: bool = None,
                session: str = None,
                queue_wait_ms: float = None,
                cache_hit: bool = None,
                worker_id: str = None,
                lockdep_edges: int = None,
                lockdep_cycles: int = None,
                **extra) -> Dict:
    """Build + print one bench JSONL record.

    Every record carries `backend` (jax.default_backend() at emit time):
    the bench trajectory has silently compared CPU-fallback runs against
    device runs before (ROADMAP cross-cutting note) — a headline number
    without its backend is not comparable to anything. `n_devices`
    (visible device count at emit time) is stamped the same way: a
    distributed-tier number measured over an N-way mesh is not comparable
    to a single-chip row, and the mesh width must never be inferred from
    the bench name (docs/distributed.md). `adaptive` (whether the
    per-fingerprint stats store was active at emit time) and `stats_hits`
    (the active store's cumulative consult hits) are stamped on EVERY
    row for the same reason (plan/stats.py, docs/adaptive.md): a warm,
    self-tuned number must never silently compare against a cold one.
    Both auto-fill from the active store; pass them explicitly to
    override (e.g. per-phase deltas in benchmarks/adaptive_bench.py).

    Optional distributed fields (the `*_dist` plan variants and the
    nightly distributed-parity/exchange stages record these): `mesh_axis`
    (the mesh axis name the plan was sharded over) and the exchange byte
    counters summed from the per-op metrics — `exchange_bytes` (the WIRE
    bytes the edges shipped, packed form; plan/transport.py), with
    `exchange_bytes_wire` (same number under its explicit name) and
    `exchange_bytes_logical` (unpacked payload) alongside so a JSONL
    consumer can compute the compression ratio without knowing the
    legacy field's meaning; `exchange_overlap_ms` is the async-dispatch
    transfer/compute overlap. lint_metrics enforces that a record
    stamping `exchange_bytes` stamps both named counters too — a wire
    number silently compared against a logical one is the exact
    trajectory bug the backend stamp rule exists for.

    Optional robustness fields (the chaos-soak stage records these, see
    benchmarks/chaos_soak.py / docs/robustness.md): `retries` (fault
    re-runs the plan survived), `faults_injected` (faultinj count drained
    via get_and_reset_injected), `degraded` (result produced by the CPU
    fallback tier after a breaker trip).

    Optional serving fields (the multi-session soak and any bench that
    measures through serving/scheduler.py — docs/serving.md): `session`
    (the tenant session the measured result executed FOR), `queue_wait_ms`
    (submit-to-dispatch wait the fair-share queue imposed), `cache_hit`
    (served from the plan-result cache — a cached number measured no
    execution at all and must never silently compare against a real
    one, the same rule as the backend stamp). lint_metrics enforces that
    a record stamping `queue_wait_ms` or `cache_hit` stamps `session`
    too — a serving number without its tenant is not attributable.
    `worker_id` names the fleet worker that executed (or, for a cache
    hit, COMPUTED) the result (serving/fleet.py); the multi-worker soak
    stamps it on every serving-path row, and lint_metrics enforces the
    stamp the same way it enforces `session`.

    Optional optimizer fields (the plan-tier benches and the nightly
    optimizer-parity stage record these, see docs/optimizer.md):
    `optimizer` ("on"/"off" — which variant this row measured) and
    `rules_fired` (rule -> rewrite count from PlanResult.optimizer), so
    the JSONL history shows the before/after trajectory per rule.

    Optional streaming-IO fields (benchmarks/streaming_scan.py, see
    docs/io.md): `io_row_groups_pruned` (groups skipped via footer
    min/max stats), `io_bytes_skipped` (compressed chunk bytes never
    decoded), `io_overlap_ms` (host decode that ran concurrently with
    execution — the prefetch pipeline's measured win).

    Optional lockdep fields (armed chaos-soak rows, i.e. runs with
    SPARK_RAPIDS_TPU_LOCKDEP=1 — runtime/lockdep.py,
    docs/analysis.md#concurrency-invariants): `lockdep_edges` (observed
    lock-order edge classes accumulated by the witness at emit time)
    and `lockdep_cycles` (observed cycles — any nonzero fails the
    soak). Stamped so the nightly JSONL history shows whether a soak
    row ran under the witness's overhead and how much lock-order
    coverage it exercised.

    Optional kernel-registry field (benchmarks/kernel_bench.py, the
    `*_kernels` plan variants; docs/kernels.md): `kernels` — the per-op
    kernel choices the measured run actually dispatched (a dict like
    {"hash_join": "pallas", ...} from OperatorMetrics.kernel, or the
    string "fallback" when every op ran its universal lowering).
    Trajectory numbers must never silently compare kernel backends —
    the same rule as the `backend` stamp."""
    rec = {"bench": bench, "axes": axes, "ms": round(ms, 3),
           "rows_per_s": round(n_rows / (ms * 1e-3)),
           "backend": jax.default_backend(),
           "n_devices": len(jax.devices())}
    if adaptive is None or stats_hits is None:
        from spark_rapids_tpu.plan import stats as _stats
        store = _stats.active_store()
        if adaptive is None:
            adaptive = store is not None
        if stats_hits is None:
            stats_hits = 0 if store is None else store.hits
    rec["adaptive"] = bool(adaptive)
    rec["stats_hits"] = int(stats_hits)
    if impl is not None:
        rec["impl"] = impl
    if mesh_axis is not None:
        rec["mesh_axis"] = mesh_axis
    if exchange_bytes is not None:
        rec["exchange_bytes"] = exchange_bytes
    if exchange_bytes_logical is not None:
        rec["exchange_bytes_logical"] = exchange_bytes_logical
    if exchange_bytes_wire is not None:
        rec["exchange_bytes_wire"] = exchange_bytes_wire
    if exchange_overlap_ms is not None:
        rec["exchange_overlap_ms"] = round(exchange_overlap_ms, 3)
    if session is not None:
        rec["session"] = session
    if queue_wait_ms is not None:
        rec["queue_wait_ms"] = round(queue_wait_ms, 3)
    if cache_hit is not None:
        rec["cache_hit"] = bool(cache_hit)
    if worker_id is not None:
        rec["worker_id"] = worker_id
    if lockdep_edges is not None:
        rec["lockdep_edges"] = int(lockdep_edges)
    if lockdep_cycles is not None:
        rec["lockdep_cycles"] = int(lockdep_cycles)
    if retries is not None:
        rec["retries"] = retries
    if faults_injected is not None:
        rec["faults_injected"] = faults_injected
    if degraded is not None:
        rec["degraded"] = degraded
    if optimizer is not None:
        rec["optimizer"] = optimizer
    if rules_fired is not None:
        rec["rules_fired"] = rules_fired
    if io_row_groups_pruned is not None:
        rec["io_row_groups_pruned"] = io_row_groups_pruned
    if io_bytes_skipped is not None:
        rec["io_bytes_skipped"] = io_bytes_skipped
    if io_overlap_ms is not None:
        rec["io_overlap_ms"] = round(io_overlap_ms, 3)
    if kernels is not None:
        rec["kernels"] = kernels
    rec.update(extra)
    print(json.dumps(rec), flush=True)
    return rec


def run_config(bench: str, axes: Dict, fn: Callable, args, *, n_rows: int,
               iters: int = 10, jit: bool = True,
               impl: str = None, **record_fields) -> Dict:
    """Time fn(*args) steady-state; returns + prints the result record.

    `jit=True` measures the op as deployed — one compiled XLA program
    (nvbench likewise times the kernel, not per-op dispatch). Ops whose
    output shapes are data-dependent must either take static bounds from the
    bench or pass jit=False. Timing methodology: `steady_state_ms`.

    `impl` names the measured engine/tier (e.g. "capped_jit",
    "plan_capped") and is recorded on the JSONL row, so cross-revision
    history never conflates two engines under one bench name again
    (round-5 ADVICE: the nds_q* configs silently switched engines)."""
    if jit:
        fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))    # compile + warmup
    ms = steady_state_ms(fn, args, iters)
    extra = dict(record_fields)         # caller-supplied JSONL fields
    return emit_record(bench, axes, ms, n_rows, impl=impl, **extra)


def registry_kernels(*op_names: str) -> Dict:
    """Signature-independent kernel-registry choices for the ops a bench
    dispatches through the public `ops` surface (e.g. "groupby",
    "row_conversion") — the honest `kernels` stamp for non-plan benches
    that still cross the registry. Benches that never dispatch a registry
    op stamp the string "fallback" instead (bench.py's convention:
    stamping choices the run never dispatched would misattribute); plan
    benches stamp the executed result's per-op choices via
    `nds_plans.kernels_of`. Enforced premerge by tools/lint_metrics.py."""
    from spark_rapids_tpu.ops.registry import REGISTRY
    return {op: REGISTRY.select(op, None).name for op in op_names}


# ---- datagen ----------------------------------------------------------------

def random_fixed_table(dts: Sequence, n_rows: int, seed: int = 0):
    """Random Table over fixed-width dtypes (reference create_random_table)."""
    from spark_rapids_tpu import Column, dtypes
    from spark_rapids_tpu.columnar import Table

    rng = np.random.default_rng(seed)
    cols = []
    for i, dt in enumerate(dts):
        np_dt = np.dtype(dt.storage_dtype())
        if np_dt.kind in "iu":
            info = np.iinfo(np_dt)
            arr = rng.integers(info.min, info.max, size=n_rows, dtype=np_dt,
                               endpoint=True)
        elif np_dt.kind == "f":
            arr = rng.standard_normal(n_rows).astype(np_dt) * 1e3
        elif np_dt.kind == "b":
            arr = rng.integers(0, 2, size=n_rows).astype(bool)
        else:
            raise TypeError(f"unsupported bench dtype {dt}")
        cols.append(Column(dtype=dt, length=n_rows, data=jnp.asarray(arr)))
    return Table(cols)


def strings_column_from_list(strs: List[bytes]):
    """Fast path: build a string Column from a list of byte strings via one
    concat + frombuffer, instead of per-row from_pylist."""
    from spark_rapids_tpu.columnar.column import make_string_column

    joined = b"".join(strs)
    chars = np.frombuffer(joined, dtype=np.uint8)
    lens = np.fromiter((len(s) for s in strs), dtype=np.int32, count=len(strs))
    offsets = np.zeros(len(strs) + 1, dtype=np.int32)
    np.cumsum(lens, out=offsets[1:])
    return make_string_column(jnp.asarray(chars), jnp.asarray(offsets))


def random_float_strings(n_rows: int, seed: int = 0):
    """String column holding printed random floats (reference
    cast_string_to_float.cpp:29-34: random FLOAT32 → from_floats)."""
    rng = np.random.default_rng(seed)
    vals = (rng.standard_normal(n_rows) * rng.choice(
        [1e-3, 1.0, 1e4, 1e20], size=n_rows)).astype(np.float32)
    txt = np.char.mod("%g", vals)
    return strings_column_from_list([s.encode() for s in txt.tolist()])


URI_VALID = (b"https://www.example.com/s/query?param0=0&param1=1&param2=2"
             b"&param3=3&param4=4&param5=5&param6=6&param7=7&param8=8")
URI_GARBAGE = [
    b"abcdefghijklmnopqrstuvwxyz 01234" * 8,       # spaces: invalid
    b"",                                           # empty
    "AbcéDEFGHIJKLMNOPQRSTUVWXYZ 01".encode() * 8,  # unicode + spaces: invalid
    b"9876543210,abcdefghijklmnopqrstU" * 8,       # no scheme
]


def uri_mix(n_rows: int, hit_rate: int, seed: int = 0):
    """hit_rate% valid URIs, rest drawn from the garbage pool (reference
    parse_uri.cpp bench_parse_uri hit_rate axis)."""
    rng = np.random.default_rng(seed)
    hits = rng.random(n_rows) < (hit_rate / 100.0)
    pick = rng.integers(0, len(URI_GARBAGE), size=n_rows)
    strs = [URI_VALID if h else URI_GARBAGE[p] for h, p in zip(hits, pick)]
    return strings_column_from_list(strs)
