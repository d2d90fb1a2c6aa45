"""Runtime configuration knobs (env vars).

The reference exposes runtime knobs as Java system properties and env vars
(SURVEY.md §5 "Config/flag system": `ai.rapids.cudf.spark.
rmmWatchdogPollingPeriod`, `ai.rapids.cudf.nvtx.enabled`,
`CUDA_INJECTION64_PATH`, `FAULT_INJECTOR_CONFIG_PATH`). The TPU engine's
equivalents, all read at use time (not import time) so tests can monkeypatch:

| env var | default | meaning |
|---|---|---|
| SPARK_RAPIDS_TPU_WATCHDOG_PERIOD_MS | 100 | arbiter deadlock-poll cadence |
| SPARK_RAPIDS_TPU_RETRY_LIMIT     | 500  | livelock cap before hard OOM   |
| TPU_FAULT_INJECTOR_CONFIG_PATH   | —    | fault injector config (faultinj)|
| SPARK_RAPIDS_TPU_KERNELS         | —    | kernel-registry overrides, `op=name` pairs (e.g. `fused_select=xla,topk=pallas,groupby=scan`; ops/registry.py, docs/kernels.md) |
| SPARK_RAPIDS_TPU_ROW_CONVERSION_KERNEL | auto | auto/word/concat (legacy alias for `row_conversion=` in SPARK_RAPIDS_TPU_KERNELS) |
| SPARK_RAPIDS_TPU_GROUPBY_KERNEL  | auto | auto/scan/scatter (legacy alias for `groupby=` in SPARK_RAPIDS_TPU_KERNELS) |
| SPARK_RAPIDS_TPU_BREAKER_RETRY_BUDGET | 16 | fault retries allowed per plan attempt (runtime/health) |
| SPARK_RAPIDS_TPU_BREAKER_BACKOFF_BASE_MS | 10 | first-retry backoff (doubles per attempt, jittered) |
| SPARK_RAPIDS_TPU_BREAKER_BACKOFF_MAX_MS | 1000 | backoff ceiling |
| SPARK_RAPIDS_TPU_BREAKER_STICKY_THRESHOLD | 3 | same-op failures within the window that classify as sticky |
| SPARK_RAPIDS_TPU_BREAKER_STICKY_WINDOW_S | 60 | sticky-detection window |
| SPARK_RAPIDS_TPU_BREAKER_COOLDOWN_S | 30 | open→half_open self-arm delay (0 = only reset_device) |
| SPARK_RAPIDS_TPU_BREAKER_DEGRADE | cpu  | cpu (finish tripped plans on the CPU tier) / off |
| SPARK_RAPIDS_TPU_OPTIMIZER       | on   | rule-based plan optimizer (plan/optimizer.py): on/off |
| SPARK_RAPIDS_TPU_IO_PREFETCH     | 2    | streaming-scan prefetch depth (chunks decoded ahead); 0 = decode inline |
| SPARK_RAPIDS_TPU_IO_CHUNK_ROWS   | 0    | streaming-scan morsel row bound (0 = one chunk per row group) |
| SPARK_RAPIDS_TPU_BROADCAST_ROWS  | 8192 | distributed tier: estimated build-side rows at or below which exchange_planning picks a broadcast join over a shuffle |
| SPARK_RAPIDS_TPU_BROADCAST_BYTES | 64 MiB | distributed tier: certified build-side byte bound (analysis/footprint.py) above which exchange_planning refuses a broadcast even when the row heuristic qualifies — broadcast legality as a proven byte bound |
| SPARK_RAPIDS_TPU_CERT_BUDGET_BYTES | 0 | static resource certifier (analysis/footprint.py): device byte budget the admission gate compares certified per-operator residency hi-bounds against; 0 disables admission sizing |
| SPARK_RAPIDS_TPU_CERT_ADMISSION  | reject | what an over-budget certified plan does at admission: reject (raise ResourceAdmissionError naming the operator, before any compilation) / degrade (run on the CPU tier) |
| SPARK_RAPIDS_TPU_CERT_SEED       | on   | capped tier: tighten cold-run starting capacities to the certified hi-bound and ceiling the escalation ladder at it (active only with the stats store on — stats off stays byte-identical static) |
| SPARK_RAPIDS_TPU_DIST_SLACK      | 2.0  | distributed tier: initial per-bucket slack factor for hash/range exchanges (grows geometrically on overflow) |
| SPARK_RAPIDS_TPU_EXCHANGE_PACK   | on   | exchange transport packing (plan/transport.py, docs/distributed.md#transport): ship packed columnar wire planes across hash/broadcast/gather edges; "off" restores the byte-identical legacy per-column payload |
| SPARK_RAPIDS_TPU_EXCHANGE_CODECS | auto | codec families the transport layer may choose from: auto (for,dict,rle,bitpack), none (layout-only pass-through), or a comma subset |
| SPARK_RAPIDS_TPU_EXCHANGE_ASYNC  | off  | async exchange dispatch: an Exchange's pack+transfer runs on a worker thread and overlaps downstream compute until its consumer resolves it (overlap-ms on OperatorMetrics) |
| SPARK_RAPIDS_TPU_VERIFY_PLANS    | 0    | static plan verifier gate (analysis/verifier.py): 1 verifies every plan pre-execution and every optimizer rule's output; on in tests (conftest), off in production |
| SPARK_RAPIDS_TPU_STATS           | on   | per-fingerprint operator-stats store (plan/stats.py, docs/adaptive.md): observed cardinalities drive join build sides / exchange modes, cap seeding, chunk sizing, and kernel tie-breaks; "off" restores fully static decisions |
| SPARK_RAPIDS_TPU_STATS_CAPACITY  | 256  | stats store LRU bound: per-(backend, fingerprint) plan entries retained (subtree/kernel tables scale off this) |
| SPARK_RAPIDS_TPU_STATS_PATH      | —    | optional JSONL persistence path for the stats store: records append per successful execution and load at first use, so observed stats survive the process |
| SPARK_RAPIDS_TPU_SERVING_WORKERS | 2    | serving layer (serving/scheduler.py, docs/serving.md): dispatcher worker threads — the device-side execution concurrency |
| SPARK_RAPIDS_TPU_SERVING_QUEUE_DEPTH | 64 | bounded admission queue: total plans queued across all sessions before submit blocks (or fast-rejects) |
| SPARK_RAPIDS_TPU_SERVING_QUOTA_BYTES | 256 MiB | default per-session device-memory quota the dispatcher admits certified footprints against (per-session override at open_session) |
| SPARK_RAPIDS_TPU_SERVING_DEFAULT_CHARGE_BYTES | 64 MiB | quota charge for plans the certifier could not bound (strings/unbound scans — footprint.quota_charge) |
| SPARK_RAPIDS_TPU_SERVING_STARVATION_MS | 2000 | fair-share aging bound: a queued plan waiting longer than this dispatches next regardless of lane/deficit — no session starves |
| SPARK_RAPIDS_TPU_SERVING_CACHE_ENTRIES | 64 | plan-result cache LRU bound (serving/cache.py); 0 disables the cache |
| SPARK_RAPIDS_TPU_SERVING_CACHE_BYTES | 256 MiB | plan-result cache RESIDENT-BYTES bound: cached result tables are live buffers no quota charges, so the cache evicts LRU past this and refuses any single result larger than it |
| SPARK_RAPIDS_TPU_SERVING_CACHE_TTL_S | 300 | plan-result cache entry time-to-live (seconds) |
| SPARK_RAPIDS_TPU_SERVING_OVER_QUOTA | reject | what a plan whose quota charge exceeds the session's remaining quota ceiling does: reject (typed ServingRejectedError naming session + operator, before compilation) / degrade (run on the CPU tier — the device quota does not bind there) |
| SPARK_RAPIDS_TPU_SERVING_BACKPRESSURE | block | submit() behavior at a full queue: block (wait for space) / reject (fast ServingRejectedError); per-submit override wins |
| SPARK_RAPIDS_TPU_SERVING_FEEDBACK | on | dispatch-fairness feedback loop (serving/scheduler.py): a session's WDRR credit grant scales down by its decayed cumulative wall-ms + retry cost, floored at a quarter of the configured weight; "off" restores pure weight-proportional credit |
| SPARK_RAPIDS_TPU_SERVING_FEEDBACK_HALFLIFE_S | 300 | half-life of the feedback cost decay — one bad hour fades instead of starving a tenant forever; <=0 disables decay (cost only accumulates) |
| SPARK_RAPIDS_TPU_FLEET_WORKERS | 1 | fleet serving tier (serving/fleet.py, docs/serving.md#fleet): executor workers behind the router; 1 (default) keeps the single-worker ServingScheduler path byte-identical |
| SPARK_RAPIDS_TPU_FLEET_RING_REPLICAS | 64 | consistent-hash ring virtual nodes per worker — higher spreads fingerprints more evenly at slightly more route cost |
| SPARK_RAPIDS_TPU_FLEET_SPILL_RATIO | 2.0 | load-aware spillover threshold: the routed worker sheds to the least-pressured replica when its pressure score exceeds ratio x (best score + 1); <=0 disables spillover |
| SPARK_RAPIDS_TPU_FLEET_RESPAWN | off | fleet self-healing (serving/fleet.py): when on, a killed/reaped/drained worker is replaced by a fresh one (new id, fresh isolated stack, warm-up gossip) until the fleet is back at its configured size; "off" keeps the legacy shrink-only failover |
| SPARK_RAPIDS_TPU_FLEET_RESPAWN_MAX | 16 | respawn budget: total replacement workers one fleet may spawn over its lifetime — a flapping environment must run out of budget, not respawn-storm |
| SPARK_RAPIDS_TPU_FLEET_RESPAWN_BACKOFF_MS | 100 | minimum delay between consecutive respawns, doubling per respawn in a flap streak (a quiet period of 16x the base resets the streak) |
| SPARK_RAPIDS_TPU_FLEET_QUARANTINE | reject | poison-fingerprint policy: a fingerprint whose executions tripped breakers on >=2 distinct workers is quarantined fleet-wide — "reject" fast-fails new submissions of it (typed ServingRejectedError), "degrade" pins them to the CPU tier |
| SPARK_RAPIDS_TPU_FLEET_HOT_REPLICAS | 1 | warm failover: frozen cache entries of HOT fingerprints replicate to this many secondary ring owners (0 disables replication) |
| SPARK_RAPIDS_TPU_FLEET_HOT_K | 8 | how many fingerprints (top-K by submissions seen at the router) count as HOT for replication (0 disables) |
| SPARK_RAPIDS_TPU_FLEET_SWEEP_MS | 0 | background health-sweep period: a fleet thread reaps stuck-open breakers and tops the fleet back up to size every this-many ms; 0 (default) disables the thread — kill/reap call sites still respawn inline |
| SPARK_RAPIDS_TPU_LOCKDEP         | 0    | runtime lock-order witness (runtime/lockdep.py, docs/analysis.md#concurrency-invariants): wrap engine locks, record held-set→acquired edges, raise on the first observed ordering cycle; armed by tests/conftest and the fleet chaos soak |

The SPARK_RAPIDS_TPU_BREAKER_* numeric knobs are snapshotted when a
`DeviceHealthMonitor` is constructed (one policy per monitor lifetime —
construct a new monitor/executor, or pass constructor overrides, to
re-tune); SPARK_RAPIDS_TPU_STATS_CAPACITY/_PATH likewise snapshot when a
`StatsStore` is constructed (plan/stats.reset_default_store re-reads);
everything else in the table is read at use time.

Tracing has no knob: the program's spans (utils/tracing.py: `serving.*`,
`plan.*`, `ops.*`) are recorded whenever a `jax.profiler` session runs and
are inert otherwise (the reference's `ai.rapids.cudf.nvtx.enabled` slot).
"""
from __future__ import annotations

import os


def _int_env(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _float_env(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def watchdog_period_s() -> float:
    """Deadlock-watchdog poll period (reference default: 100 ms,
    SparkResourceAdaptor.java:35-36)."""
    return _int_env("SPARK_RAPIDS_TPU_WATCHDOG_PERIOD_MS", 100) / 1000.0


def retry_limit() -> int:
    """Consecutive no-progress retries before a hard OOM (reference: 500,
    SparkResourceAdaptorJni.cpp:984-995)."""
    return _int_env("SPARK_RAPIDS_TPU_RETRY_LIMIT", 500)


def row_conversion_kernel() -> str:
    """Row-conversion kernel selection: auto (default: u32 word kernel on
    TPU, byte-concat kernel on CPU — see ops/row_conversion.py), or force
    "word" / "concat". A typo must not silently fall back to auto — an A/B
    capture would attribute numbers to the wrong kernel."""
    v = os.environ.get("SPARK_RAPIDS_TPU_ROW_CONVERSION_KERNEL", "auto")
    if v not in ("auto", "word", "concat"):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_ROW_CONVERSION_KERNEL={v!r}: expected "
            "auto, word, or concat")
    return v


def breaker_retry_budget() -> int:
    """Fault retries allowed per plan attempt, shared across every operator
    in the plan (runtime/health.py) — the no-retry-storm bound."""
    return _int_env("SPARK_RAPIDS_TPU_BREAKER_RETRY_BUDGET", 16)


def breaker_backoff_base_ms() -> float:
    """Backoff before the first retry; doubles per attempt with jitter.
    Float-valued: sub-millisecond pacing (e.g. 0.5) is valid."""
    return _float_env("SPARK_RAPIDS_TPU_BREAKER_BACKOFF_BASE_MS", 10.0)


def breaker_backoff_max_ms() -> float:
    return _float_env("SPARK_RAPIDS_TPU_BREAKER_BACKOFF_MAX_MS", 1000.0)


def breaker_sticky_threshold() -> int:
    """Failures of the SAME operator within the sticky window that escalate
    the classification from transient to sticky (breaker trip)."""
    return _int_env("SPARK_RAPIDS_TPU_BREAKER_STICKY_THRESHOLD", 3)


def breaker_sticky_window_s() -> float:
    return _float_env("SPARK_RAPIDS_TPU_BREAKER_STICKY_WINDOW_S", 60.0)


def breaker_cooldown_s() -> float:
    """Seconds an OPEN breaker waits before self-arming HALF_OPEN (the
    next admission then probes the device). Keeps quarantine from being
    permanent when the trip cause was transient (a pressure burst, a
    since-recovered device); 0 disables — only reset_device() re-arms."""
    return _float_env("SPARK_RAPIDS_TPU_BREAKER_COOLDOWN_S", 30.0)


def breaker_degrade() -> str:
    """Degradation policy when the breaker trips: "cpu" finishes the plan on
    the CPU backend tier, "off" propagates the failure (legacy behavior).
    Same strict-typo policy as the kernel selectors: a typo must not
    silently change failure-domain behavior."""
    v = os.environ.get("SPARK_RAPIDS_TPU_BREAKER_DEGRADE", "cpu")
    if v not in ("cpu", "off"):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_BREAKER_DEGRADE={v!r}: expected cpu or off")
    return v


def optimizer_enabled() -> bool:
    """Rule-based plan optimizer (plan/optimizer.py), run inside
    PlanExecutor.execute() before tier dispatch. "on" (default) or "off";
    same strict-typo policy as the kernel selectors — a typo must not
    silently change which plan shape executes."""
    v = os.environ.get("SPARK_RAPIDS_TPU_OPTIMIZER", "on")
    if v not in ("on", "off"):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_OPTIMIZER={v!r}: expected on or off")
    return v == "on"


def io_prefetch() -> int:
    """Streaming-scan prefetch depth (docs/io.md): how many decoded chunks
    a source-bound Scan's host decode thread may run ahead of execution —
    the double-buffer that overlaps host bitstream decode of chunk N+1
    with device execution of chunk N. 0 disables the thread entirely
    (decode happens inline on the executing thread)."""
    return max(0, _int_env("SPARK_RAPIDS_TPU_IO_PREFETCH", 2))


def io_chunk_rows() -> int:
    """Streaming-scan morsel row bound: decoded row groups larger than
    this split into <= this many rows per chunk, bounding the per-morsel
    working set independently of how the file was written. 0 (default)
    streams one chunk per row group. Returns 0 for "unbounded-by-rows";
    callers treat it as falsy."""
    return max(0, _int_env("SPARK_RAPIDS_TPU_IO_CHUNK_ROWS", 0))


def broadcast_rows() -> int:
    """Distributed tier (docs/distributed.md): the optimizer's
    exchange_planning rule replicates a join's build side (broadcast join,
    no shuffle of the probe side) when its estimated row count is at or
    below this — the row-count analogue of Spark's
    autoBroadcastJoinThreshold. Estimates come from bound tables or
    `est_rows` scan hints."""
    return _int_env("SPARK_RAPIDS_TPU_BROADCAST_ROWS", 8192)


def broadcast_bytes() -> int:
    """Distributed tier: the PROVEN byte bound broadcast-join legality
    requires (analysis/footprint.py, docs/analysis.md) — a build side
    whose certified hi-bound exceeds this never broadcasts, whatever the
    row estimate said (estimates are guesses; replicating a mis-estimated
    relation onto every peer is the failure mode this gate closes). Sides
    the certifier cannot bound (strings, unbound scans) fall back to the
    row heuristic alone. Default 64 MiB — roomy, the row threshold stays
    the cost heuristic; this is the legality ceiling."""
    return _int_env("SPARK_RAPIDS_TPU_BROADCAST_BYTES", 64 << 20)


def cert_budget_bytes() -> int:
    """Static-certifier admission budget (analysis/footprint.py): when
    non-zero, PlanExecutor.execute() compares every operator's certified
    residency hi-bound against this before any compilation and applies
    `cert_admission()`. 0 (default) disables admission sizing — the
    capped tier's escalation/OOM machinery remains the fallback."""
    return max(0, _int_env("SPARK_RAPIDS_TPU_CERT_BUDGET_BYTES", 0))


def cert_admission() -> str:
    """Over-budget policy for the certifier's admission gate: "reject"
    raises ResourceAdmissionError naming the offending operator (the
    serving-layer posture: fail fast, before compilation); "degrade"
    finishes the plan on the CPU tier (the device budget does not bind
    there). Same strict-typo policy as the kernel selectors."""
    v = os.environ.get("SPARK_RAPIDS_TPU_CERT_ADMISSION", "reject")
    if v not in ("reject", "degrade"):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_CERT_ADMISSION={v!r}: expected reject or "
            "degrade")
    return v


def cert_seed() -> bool:
    """Capped tier: whether cold adaptive runs tighten starting
    capacities to the certified hi-bound and ceiling the escalation
    ladder at it (analysis/footprint.py, docs/adaptive.md). Only active
    when a stats store is (SPARK_RAPIDS_TPU_STATS=on or a scoped store)
    — with stats off the capped tier stays byte-identical static. Same
    strict-typo policy as the kernel selectors."""
    v = os.environ.get("SPARK_RAPIDS_TPU_CERT_SEED", "on")
    if v not in ("on", "off"):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_CERT_SEED={v!r}: expected on or off")
    return v == "on"


def dist_slack() -> float:
    """Distributed tier: initial slack factor sizing the sample sort's
    fixed-capacity range buckets (capacity = rows/peer x slack). Skew past
    the slack raises the overflow flag and the executor retries with
    geometrically grown slack (SplitAndRetry contract,
    parallel/autoretry.py). The hash exchange needs none: it counts its
    buckets first and ships them at the fullest one's size."""
    return _float_env("SPARK_RAPIDS_TPU_DIST_SLACK", 2.0)


def exchange_pack() -> bool:
    """Exchange transport packing (plan/transport.py, docs/distributed.md
    #transport): when on, hash/broadcast/gather exchange payloads ship as
    dense packed planes (coalesced word planes, bit-packed validity,
    cheap per-column encodings) and unpack on the receiving shard;
    metrics then split logical vs wire bytes per edge. "off" restores
    the byte-identical legacy payload layout (wire == logical). Same
    strict-typo policy as the kernel selectors — a typo must not
    silently change what a bench's wire numbers measured."""
    v = os.environ.get("SPARK_RAPIDS_TPU_EXCHANGE_PACK", "on")
    if v not in ("on", "off"):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_EXCHANGE_PACK={v!r}: expected on or off")
    return v == "on"


def exchange_codecs() -> frozenset:
    """Codec families the exchange transport may choose from (selection
    per column stays by cheap inspection with strict pass-through):
    "auto" allows the full catalog (for, dict, rle, bitpack), "none"
    keeps the packed layout but no per-column encodings, a comma list
    restricts to a subset. Unknown names raise (strict-typo policy)."""
    from .plan.transport import resolve_codecs
    return resolve_codecs(
        os.environ.get("SPARK_RAPIDS_TPU_EXCHANGE_CODECS", "auto"))


def exchange_async() -> bool:
    """Async exchange dispatch (plan/distributed.py): when on, an
    Exchange node's pack+transfer runs on a worker thread and the plan
    walk continues — the transfer overlaps downstream operators' compute
    until the exchange's consumer resolves it (the PR 4 prefetch-thread
    shape applied to the exchange boundary; measured overlap-ms lands on
    the edge's OperatorMetrics). Off (default) keeps the fully
    synchronous walk — byte-identical behavior and fault attribution.
    Same strict-typo policy as the kernel selectors."""
    v = os.environ.get("SPARK_RAPIDS_TPU_EXCHANGE_ASYNC", "off")
    if v not in ("on", "off"):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_EXCHANGE_ASYNC={v!r}: expected on or off")
    return v == "on"


def verify_plans() -> bool:
    """Static plan verifier gate (analysis/verifier.py, docs/analysis.md):
    when on, PlanExecutor.execute() verifies the (optimized) plan before
    any tier runs it, and the optimizer verifies every rule's output
    instead of only net-validating the pipeline's end state. Debug-mode:
    on in the test suite (tests/conftest.py), off by default in
    production. Same strict-typo policy as the kernel selectors — a typo
    must not silently disable a soundness gate."""
    v = os.environ.get("SPARK_RAPIDS_TPU_VERIFY_PLANS", "0")
    if v not in ("0", "1", "on", "off"):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_VERIFY_PLANS={v!r}: expected 0, 1, on, "
            "or off")
    return v in ("1", "on")


def stats_enabled() -> bool:
    """Per-fingerprint operator-stats store gate (plan/stats.py,
    docs/adaptive.md): when on, every successful PlanResult records its
    observed rows/bytes/wall/caps/kernel timings and the optimizer,
    executor, and kernel registry consult them on the next execution of
    the same fingerprint. "off" restores byte-identical static decisions
    (the store is neither read nor written). Same strict-typo policy as
    the kernel selectors — a typo must not silently change whether runs
    self-tune. The test suite defaults this OFF (tests/conftest.py):
    cross-test fingerprint reuse would make cap-escalation and
    optimizer-report assertions order-dependent; tests/test_adaptive.py
    scopes explicit stores instead."""
    v = os.environ.get("SPARK_RAPIDS_TPU_STATS", "on")
    if v not in ("on", "off"):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_STATS={v!r}: expected on or off")
    return v == "on"


def stats_capacity() -> int:
    """Stats store LRU bound: plan entries per (backend, fingerprint)
    retained before the least-recently-consulted evicts; the subtree-
    cardinality and kernel-timing side tables scale off this bound
    (plan/stats.py). Snapshotted when a StatsStore is constructed."""
    return max(1, _int_env("SPARK_RAPIDS_TPU_STATS_CAPACITY", 256))


def stats_path() -> str:
    """Optional JSONL persistence path for the stats store: when set,
    each successful execution appends one record and the process-default
    store replays the file at first use — observed caps/cardinalities
    survive restarts. Empty string (default) keeps the store
    in-memory-only. Snapshotted when a StatsStore is constructed."""
    return os.environ.get("SPARK_RAPIDS_TPU_STATS_PATH", "")


def serving_workers() -> int:
    """Serving dispatcher worker threads (serving/scheduler.py,
    docs/serving.md): how many admitted plans execute concurrently.
    Small by design — workers contend for one device; the queue, not the
    worker pool, absorbs traffic."""
    return max(1, _int_env("SPARK_RAPIDS_TPU_SERVING_WORKERS", 2))


def serving_queue_depth() -> int:
    """Bounded serving queue: total queued (not yet dispatched) plans
    across every session before submit() exerts backpressure. The bound
    is the backpressure signal — an unbounded queue hides overload until
    memory does the rejecting (StreamBox-HBM's bounded-pipeline
    discipline, PAPERS.md)."""
    return max(1, _int_env("SPARK_RAPIDS_TPU_SERVING_QUEUE_DEPTH", 64))


def serving_quota_bytes() -> int:
    """Default per-session device-memory quota (serving/scheduler.py):
    the sum of a session's in-flight certified charges
    (footprint.quota_charge) may not exceed this. Per-session override
    at `open_session(quota_bytes=...)`."""
    return max(1, _int_env("SPARK_RAPIDS_TPU_SERVING_QUOTA_BYTES",
                           256 << 20))


def serving_default_charge_bytes() -> int:
    """Quota charge for a plan the certifier could not bound (strings,
    unbound scans — footprint.quota_charge): a flat configurable amount,
    so unbounded plans neither ride the quota for free nor get rejected
    outright."""
    return max(1, _int_env(
        "SPARK_RAPIDS_TPU_SERVING_DEFAULT_CHARGE_BYTES", 64 << 20))


def serving_starvation_ms() -> float:
    """Fair-share aging bound (the starvation bound): a queued plan
    waiting longer than this dispatches next, regardless of priority
    lane or deficit state — weighted fairness may skew throughput but
    must never unbound any session's queue wait."""
    return max(0.0, _float_env("SPARK_RAPIDS_TPU_SERVING_STARVATION_MS",
                               2000.0))


def serving_cache_entries() -> int:
    """Plan-result cache LRU bound (serving/cache.py): completed results
    retained per scheduler, keyed by canonical plan fingerprint +
    input-data digest. 0 disables the cache entirely."""
    return max(0, _int_env("SPARK_RAPIDS_TPU_SERVING_CACHE_ENTRIES", 64))


def serving_cache_bytes() -> int:
    """Plan-result cache resident-bytes bound (serving/cache.py): cached
    tables are live device/host buffers that NO session quota charges
    (the quota covers in-flight execution, not retention), so the cache
    itself must bound what it pins — LRU eviction past this total, and a
    single result larger than it never caches at all (a one-entry cache
    that thrashes the whole budget serves nobody)."""
    return max(1, _int_env("SPARK_RAPIDS_TPU_SERVING_CACHE_BYTES",
                           256 << 20))


def serving_cache_ttl_s() -> float:
    """Plan-result cache time-to-live: entries older than this never
    serve (and evict on the next touch). <=0 means no TTL (LRU only)."""
    return _float_env("SPARK_RAPIDS_TPU_SERVING_CACHE_TTL_S", 300.0)


def serving_over_quota() -> str:
    """Policy when a plan's quota charge exceeds its session's quota
    ceiling: "reject" raises a typed ServingRejectedError naming the
    session and the operator that set the certified peak, BEFORE any
    compilation; "degrade" runs the plan on the CPU tier, where the
    device quota does not bind.
    Same strict-typo policy as the kernel selectors."""
    v = os.environ.get("SPARK_RAPIDS_TPU_SERVING_OVER_QUOTA", "reject")
    if v not in ("reject", "degrade"):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_SERVING_OVER_QUOTA={v!r}: expected reject "
            "or degrade")
    return v


def serving_backpressure() -> str:
    """submit() behavior at a full queue: "block" waits for space (the
    synchronous-caller posture), "reject" raises ServingRejectedError
    immediately (the load-shedding posture). The per-submit `block=`
    argument overrides. Same strict-typo policy as the kernel
    selectors."""
    v = os.environ.get("SPARK_RAPIDS_TPU_SERVING_BACKPRESSURE", "block")
    if v not in ("block", "reject"):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_SERVING_BACKPRESSURE={v!r}: expected block "
            "or reject")
    return v


def serving_feedback() -> bool:
    """Dispatch-fairness feedback loop (serving/scheduler.py,
    docs/serving.md#fairness): when on, a session's WDRR credit grant
    scales down by its decayed cumulative wall-ms + retry cost — heavy
    recent consumers earn dispatch credit slower, bounded (floored at a
    quarter of the configured weight) so feedback skews but never
    starves. "off" restores pure weight-proportional credit. Same
    strict-typo policy as the kernel selectors."""
    v = os.environ.get("SPARK_RAPIDS_TPU_SERVING_FEEDBACK", "on")
    if v not in ("on", "off"):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_SERVING_FEEDBACK={v!r}: expected on or off")
    return v == "on"


def serving_feedback_halflife_s() -> float:
    """Half-life (seconds) of the feedback cost decay: a session's
    accumulated wall/retry cost halves every this-many seconds of wall
    time, so one bad hour fades instead of permanently down-weighting
    the tenant. <=0 disables decay (cost only accumulates)."""
    return _float_env("SPARK_RAPIDS_TPU_SERVING_FEEDBACK_HALFLIFE_S",
                      300.0)


def fleet_workers() -> int:
    """Fleet serving tier (serving/fleet.py, docs/serving.md#fleet):
    executor workers the router fronts, each owning its own
    PlanExecutor + health monitor + stats store + result cache. The
    default 1 keeps serving on the single-worker ServingScheduler path
    (byte-identical to a fleet-less build)."""
    return max(1, _int_env("SPARK_RAPIDS_TPU_FLEET_WORKERS", 1))


def fleet_ring_replicas() -> int:
    """Consistent-hash ring virtual nodes per fleet worker
    (serving/router.py): more replicas spread plan fingerprints more
    evenly across workers and shrink the key range that moves on
    join/leave, at slightly higher route cost."""
    return max(1, _int_env("SPARK_RAPIDS_TPU_FLEET_RING_REPLICAS", 64))


def fleet_spill_ratio() -> float:
    """Load-aware spillover threshold (serving/fleet.py): the
    consistent-hash-routed worker sheds a new session to the
    least-pressured worker when its pressure score exceeds
    ratio x (best score + 1). Higher values prefer cache locality over
    load balance; <=0 disables spillover entirely."""
    return _float_env("SPARK_RAPIDS_TPU_FLEET_SPILL_RATIO", 2.0)


def fleet_respawn() -> bool:
    """Fleet self-healing gate (serving/fleet.py, docs/serving.md#fleet):
    when on, kill_worker/reap_unhealthy/drain_worker (and the background
    sweep, when armed) spawn a fresh replacement worker — new id, fresh
    isolated executor/health/stats/cache stack, warm-up gossip from the
    survivors — until the fleet is back at its configured size. Off
    (default) keeps the legacy shrink-only failover, which several
    regression tests pin. Same strict-typo policy as the kernel
    selectors — a typo must not silently change failure-domain
    behavior."""
    v = os.environ.get("SPARK_RAPIDS_TPU_FLEET_RESPAWN", "off")
    if v not in ("on", "off"):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_FLEET_RESPAWN={v!r}: expected on or off")
    return v == "on"


def fleet_respawn_max() -> int:
    """Respawn budget: the total number of replacement workers one fleet
    may spawn over its lifetime. The bound is the respawn-storm guard —
    an environment that keeps killing replacements (a genuinely dead
    device, a poison plan the quarantine has not yet attributed) runs
    out of budget and degrades to shrink-only failover instead of
    spawning forever."""
    return max(0, _int_env("SPARK_RAPIDS_TPU_FLEET_RESPAWN_MAX", 16))


def fleet_respawn_backoff_ms() -> float:
    """Minimum delay between consecutive respawns, doubled per respawn
    while the fleet is flapping (a quiet period of 16x the base resets
    the streak). A respawn arriving inside the backoff window is
    deferred — the next kill/reap/sweep tick retries it."""
    return max(0.0, _float_env(
        "SPARK_RAPIDS_TPU_FLEET_RESPAWN_BACKOFF_MS", 100.0))


def fleet_quarantine() -> str:
    """Poison-fingerprint policy (serving/fleet.py): a fingerprint whose
    executions tripped breakers on >= 2 DISTINCT workers is quarantined
    fleet-wide — without this, auto-respawn is a crash amplifier (one
    bad plan kills every replacement in a loop). "reject" fast-fails new
    submissions of a quarantined fingerprint with a typed
    ServingRejectedError("quarantined"); "degrade" pins them to the CPU
    tier, where the device the plan keeps poisoning is not involved.
    Same strict-typo policy as SPARK_RAPIDS_TPU_SERVING_OVER_QUOTA."""
    v = os.environ.get("SPARK_RAPIDS_TPU_FLEET_QUARANTINE", "reject")
    if v not in ("reject", "degrade"):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_FLEET_QUARANTINE={v!r}: expected reject "
            "or degrade")
    return v


def fleet_hot_replicas() -> int:
    """Warm failover (serving/fleet.py): HOT fingerprints' frozen cache
    entries replicate to this many secondary ring owners beyond the
    primary, so losing the home worker loses neither the cached result
    nor (with the stats gossip) the observed sizing. 0 disables
    replication — promotion alone still shares entries reactively."""
    return max(0, _int_env("SPARK_RAPIDS_TPU_FLEET_HOT_REPLICAS", 1))


def fleet_hot_k() -> int:
    """How many fingerprints count as HOT for replication: the top-K by
    submissions observed at the router. Small by design — replication
    multiplies resident cache bytes by (1 + replicas) for exactly the
    traffic where a cold rehome would hurt most. 0 disables."""
    return max(0, _int_env("SPARK_RAPIDS_TPU_FLEET_HOT_K", 8))


def fleet_sweep_ms() -> float:
    """Background health-sweep period (serving/fleet.py): when > 0 the
    fleet runs a daemon thread that, every this-many ms, reaps workers
    whose breaker is stuck OPEN with no cooldown and tops the fleet back
    up to its configured size (respawn knob permitting) — so a worker
    that dies while no kill/reap call site is active still gets
    replaced. 0 (default) disables the thread."""
    return max(0.0, _float_env("SPARK_RAPIDS_TPU_FLEET_SWEEP_MS", 0.0))


def faultinj_config_path() -> str:
    """Fault-injector config path (TPU_FAULT_INJECTOR_CONFIG_PATH — the
    reference's FAULT_INJECTOR_CONFIG_PATH analogue). Lives here so the
    hazard linter's env-reads-outside-config rule holds for faultinj.py
    too; empty string when unset."""
    return os.environ.get("TPU_FAULT_INJECTOR_CONFIG_PATH", "")


def kernel_overrides() -> dict:
    """Kernel-registry overrides (ops/registry.py, docs/kernels.md): the ONE
    backend-dispatch knob. Comma-separated `op=kernel` pairs, e.g.
    `SPARK_RAPIDS_TPU_KERNELS=fused_select=xla,topk=pallas,groupby=scan`.
    The legacy per-op vars (SPARK_RAPIDS_TPU_GROUPBY_KERNEL,
    SPARK_RAPIDS_TPU_ROW_CONVERSION_KERNEL) fold in as aliases for the
    `groupby`/`row_conversion` entries; an explicit SPARK_RAPIDS_TPU_KERNELS
    entry wins over its alias. Format errors raise here; unknown op/kernel
    NAMES raise in the registry, which owns the catalog — both directions of
    the strict-typo policy (a typo must not silently change which kernel an
    A/B capture measured). Signature-level declines are NOT errors: a forced
    kernel that cannot run a given signature falls back cleanly."""
    out = {}
    g = groupby_kernel()
    if g != "auto":
        out["groupby"] = g
    r = row_conversion_kernel()
    if r != "auto":
        out["row_conversion"] = r
    spec = os.environ.get("SPARK_RAPIDS_TPU_KERNELS", "")
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        op, sep, name = part.partition("=")
        op, name = op.strip(), name.strip()
        if not sep or not op or not name:
            raise ValueError(
                f"SPARK_RAPIDS_TPU_KERNELS: malformed entry {part!r} "
                "(expected op=kernel, e.g. fused_select=xla)")
        out[op] = name
    return out


def groupby_kernel() -> str:
    """Groupby aggregation kernel selection: auto (default: scan design on
    TPU where scatters are ~25x a cumsum, scatter/segment design on CPU
    where the scan design measured ~2x slower — see ops/aggregate.py), or
    force "scan" / "scatter". Same strict-typo policy as
    row_conversion_kernel."""
    v = os.environ.get("SPARK_RAPIDS_TPU_GROUPBY_KERNEL", "auto")
    if v not in ("auto", "scan", "scatter"):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_GROUPBY_KERNEL={v!r}: expected auto, scan, "
            "or scatter")
    return v


def lockdep() -> bool:
    """Runtime lock-order witness gate (runtime/lockdep.py,
    docs/analysis.md#concurrency-invariants): SPARK_RAPIDS_TPU_LOCKDEP=1
    wraps every engine-constructed lock in a tracing proxy that records
    per-thread held-set -> acquired edges and raises LockOrderViolation
    on the first observed ordering cycle. Armed suite-wide by
    tests/conftest when the variable is set; off (default) means zero
    overhead. Note the knob is latched where the witness is INSTALLED
    (conftest reads it once before importing the engine, so
    module-level locks get wrapped) — flipping it mid-process does not
    re-wrap existing locks."""
    return os.environ.get("SPARK_RAPIDS_TPU_LOCKDEP", "0") not in (
        "0", "", "off")


def place_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.
    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and no
    other directory is set in code; where it is not, the cache is
    `<checkout>/.jax_cache` — a fixed path (the path is part of the cache
    key: one built from a temp name, pid or time never hits). The one
    placement rule shared by chipbench and tests/conftest."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
