"""Plan executor: walks the operator DAG and runs it on one of three tiers.

Before tier dispatch, `execute()` runs the rule-based logical optimizer
(`plan/optimizer.py`, docs/optimizer.md) over the bound plan — column
pruning, predicate/limit pushdown, constant folding, Filter+Project
fusion, join build-side selection — and executes the rewritten DAG;
`SPARK_RAPIDS_TPU_OPTIMIZER=off` or `PlanExecutor(optimize=False)`
disables it. `PlanResult.optimizer` reports what fired.

- `mode="eager"`: per-operator dispatch through the public `ops` kernels —
  every operator gets its own wall-clock, rows/bytes metrics, a
  `plan.op` span (`utils.tracing`), a plan-level faultinj interception
  point, and a bounded, backoff-paced re-run on recoverable injected
  faults (the plan-level retry that replaces per-query hand-wiring).
- `mode="capped"`: the whole DAG traces into ONE XLA program with static
  capacities (`row_cap` for joins, `key_cap` for aggregates — per-node
  overrides take precedence). A too-small cap raises the overflow flag and
  `parallel.autoretry.auto_retry_overflow` grows every cap geometrically
  and re-traces — SplitAndRetry at PLAN granularity, not per-call. The
  compiled program is cached per (plan FINGERPRINT, caps, input
  shapes+names) and the final capacities are memoized per fingerprint, so
  escalated caps are remembered for the rest of the job AND structurally
  identical plans built independently share compiled programs
  (`optimizer.plan_fingerprint`).
- distributed (eager tier only — execute() rejects a mesh with
  mode="capped" when the plan contains a distributed-lowerable operator):
  when a device `mesh` is given, the whole plan runs as SPMD over the mesh
  (plan/distributed.py, docs/distributed.md): Scans shard row-wise,
  Filter/Project stay elementwise-sharded, joins run shuffle
  (hash-exchange both sides) or broadcast (replicate the small build
  side, chosen by the optimizer's `exchange_planning` rule from row
  estimates), aggregates fuse the two-phase partial→all-to-all→final
  program behind their `Exchange` (elided entirely when the input is
  already partitioned by a subset of the group keys), Sort/TopK
  sample-sort to global order, and the result gathers to one device only
  at the sink — or at the first operator with no distributed form, the
  same graceful-boundary pattern as the streaming tier's concat. All
  static capacities escalate via `parallel.autoretry` and memoize per
  plan fingerprint.

Admission (`runtime.admission`) applies per operator automatically: the
executor calls the public `ops` surface through module attribute lookup, so
the admission wrappers — and any installed faultinj shims — intercept every
kernel the plan dispatches. Pass `session=` to scope a DeviceSession to the
execution without touching process-global state.

Failure handling is a *policy*, owned by `runtime.health` (docs/
robustness.md): transient faults (injected nonfatal asserts, substituted
return codes, RetryOOM spikes) retry with jittered exponential backoff
against a per-plan-attempt retry budget; sticky (same op keeps failing) and
fatal (`DeviceFatalError`) failures trip the circuit breaker and — with the
default `degrade="cpu"` — the remaining plan re-executes on the CPU backend
tier, salvaging completed operator outputs through host memory. `explain()`
is unchanged; `profile()`/`PlanResult` record `degraded`, `backoff_ms`, and
the breaker snapshot so a degraded run is visible after the fact. While the
breaker is open the device is quarantined (plans run fully degraded);
`health.reset_device()` arms a half-open probation and a cheap heartbeat
probe op decides whether normal execution resumes.

Results carry `profile()` — per-operator rows (live rows in the capped
tier, computed on-device and returned with the result), output buffer
bytes, wall time, retry and cap-escalation counts.

Feedback loop (plan/stats.py, docs/adaptive.md): after every successful
execution the per-op metrics, final caps, and kernel timings record into
the per-fingerprint stats store under the backend the result ran on
("cpu" for degraded results). The next execution of the same fingerprint
consumes them — observed cardinalities re-pick join build sides and
exchange modes (through `optimize(stats=...)`, every stats-driven
rewrite re-verified), the capped tier seeds its caps at the observed
high-water (no escalation ladder on warm runs), the streaming tier sizes
morsels from observed decode throughput, and the kernel registry demotes
kernels that benched slower than their fallback. `SPARK_RAPIDS_TPU_STATS
=off` restores fully static behavior.
"""
from __future__ import annotations

import contextlib
import itertools
import re
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes
from ..columnar import Column, Table
from .builder import Plan
from .metrics import OperatorMetrics, render_profile
from .nodes import (PAIRING_JOINS, Exchange, Filter, FusedSelect,
                    HashAggregate, HashJoin, Limit, PlanNode,
                    PlanValidationError, Project, Scan, Sort, TopK, Union,
                    Window,
                    nullable_sides)
from .expr import ColumnRef
from ..utils.tracing import bracket, span, text as _span_text

# The device-fault surface the executor turns into policy (runtime/health):
# injected nonfatal asserts and substituted return codes plus RetryOOM
# pressure spikes classify transient (jittered backoff + budgeted retry);
# DeviceFatalError classifies fatal and is NEVER retried on the device —
# a dead device must stop the retry loop, that is the whole point of the
# fatal tier. Sticky/fatal failures trip the breaker; with degrade="cpu"
# the remaining plan re-executes on the CPU backend tier.
def _fault_surface():
    from .. import faultinj
    from ..runtime.adaptor import CpuRetryOOM, RetryOOM
    return (faultinj.DeviceFatalError, faultinj.DeviceAssertError,
            faultinj.InjectedReturnCode, RetryOOM, CpuRetryOOM)


def _ops():
    # attribute lookups on the module keep admission + faultinj shims live
    from .. import ops
    return ops


def _sessionctx():
    from ..runtime import sessionctx
    return sessionctx


def _op_span(node: PlanNode, idx: int, tier: str = "device"):
    """The eager tiers' per-operator bracket (utils/tracing.py). `op` is
    `<toposort index>.<kind>`, the name the operator's scope carries
    inside a capped program; `tier` is where it ran: device or degraded
    (CPU tier); a join's says `how`."""
    how = {"how": node.how} if isinstance(node, HashJoin) else {}
    return bracket("plan.op", op=_scope_name(idx, node),
                   label=_span_text(node.label), tier=tier, **how)


def _say_op(sp, m: OperatorMetrics, node, childs, out) -> None:
    """On the `plan.op` span of an eager `Filter` / `FusedSelect`: how the
    rows of its input moved into `out` (`m.compact`); on those and a
    `Project`'s, how many of the columns its expressions read can hold a
    null (`nullable_inputs`: whether a validity mask is there, no read)."""
    if m.compact:
        sp.set_metadata(compact=m.compact, rows_in=childs[0].num_rows,
                        rows_out=out.num_rows)
    if isinstance(node, (Filter, Project, FusedSelect)) \
            and isinstance(childs[0], Table):
        from .optimizer import _node_exprs
        refs = frozenset().union(*(e.references()
                                   for e in _node_exprs(node)))
        sp.set_metadata(nullable_inputs=sum(
            childs[0][n].validity is not None for n in refs))


_DECIMAL_OVERFLOW = -1      # key of `_run_capped`'s counts, see there
_JOIN_UNIQUE = -2           # minus twice the join's index: key of the flag
#                             that says which tail its sort join took (an
#                             outer join: 0, it expands, and beside it its
#                             null-extended rows)
_JOIN_EXPAND = -3           # minus twice the join's index: key of the slots
#                             its expansion touched, and its frames'
_KEY_SLOTS = -1             # minus the aggregate's index: key, in a capped
#                             program's `bytes_map`, of the key planes x
#                             slots its group-by gathers (0: its keys
#                             ride). There and in no map of its own:
#                             `_jitted_capped` hands back four values, and
#                             chipbench's deviceless compile unpacks them


def _scope_name(idx: int, node: PlanNode) -> str:
    """`<toposort index>.<kind>`: an operator's name in the program's
    spans and in a capped program's scopes. The index, not the label:
    fingerprint-equal plans share one compiled program and their labels
    differ."""
    return f"{idx}.{node.kind}"


def _null_row(table: Table) -> Table:
    """One row of nulls in `table`'s schema: what a capped outer join's
    gathers read in an EMPTY null-supplying side's place (a gather from no
    rows has none); as a join side its null key matches nothing, so every
    row of the other side comes out null-extended."""
    return Table([Column.from_pylist([None], c.dtype)
                  for c in table.columns], names=list(table.names))


_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = .*metadata=\{[^}]*op_name=\"([^\"]*)\"")
_SCOPE = re.compile(r"^\d+\.[A-Za-z]+$")


def _scope_owners(hlo_text: str, nested: bool = False) -> Dict[str, str]:
    """{instruction name: its outermost `<idx>.<kind>` scope} over every
    computation of an executable's text (`_scope_name` wrote the scopes).
    `nested`: followed by the innermost scope a kernel opened below the
    operator's: `/decimal.<op>` (ops/decimal_utils.py) or, outside any
    of those, `/ops.groupby` (ops/aggregate.py: the group-by's kernel and
    the rest of its finish) or `/ops.window` (ops/window.py: the same of
    a window)."""
    owners: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m:
            parts = m.group(2).split("/")
            at = next((i for i, p in enumerate(parts)
                       if _SCOPE.match(p)), None)
            if at is not None:
                inner = [p for p in parts[at + 1:]
                         if p.startswith("decimal.")
                         or p in ("ops.groupby", "ops.window")] \
                    if nested else []
                owners[m.group(1)] = "/".join(parts[at:at + 1] + inner[-1:])
    return owners


_HLO_FRAME = re.compile(r"stack_frame_id=(\d+)")
_HLO_TABLE_ROW = re.compile(r"^(\d+) (?:\"(.*)\"|\{(.*)\})$")


def _op_sources(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """{instruction name: (primitive, "file:line function")} from an
    executable's text: the last part of the instruction's `op_name`
    (`scatter-add`, `gather`, `sort`) and the innermost Python frame that
    traced it, resolved through the text's own tables (FileNames,
    FunctionNames, FileLocations, StackFrames). A fusion reads as its
    root does. Instructions without a frame are left out."""
    tables: Dict[str, Dict[int, object]] = {}
    section = None
    sources: Dict[str, Tuple[str, str]] = {}
    for line in hlo_text.splitlines():
        text = line.strip()
        if text in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            section = tables.setdefault(text, {})
            continue
        row = _HLO_TABLE_ROW.match(text) if section is not None else None
        if row:
            section[int(row.group(1))] = row.group(2) if row.group(3) is None \
                else {k: int(v) for k, v in
                      (kv.split("=") for kv in row.group(3).split())}
            continue
        if text:
            section = None
        m = _HLO_INSTRUCTION.match(line)
        frame = _HLO_FRAME.search(line) if m else None
        if frame and "StackFrames" in tables:
            loc = tables["FileLocations"][
                tables["StackFrames"][int(frame.group(1))]["file_location_id"]]
            path = tables["FileNames"][loc["file_name_id"]]
            sources[m.group(1)] = (
                m.group(2).rsplit("/", 1)[-1],
                f"{path.rsplit('spark_rapids_tpu/', 1)[-1]}:{loc['line']} "
                f"{tables['FunctionNames'][loc['function_name_id']]}")
    return sources


def _input_key(inputs: Dict[str, Table]) -> Tuple:
    """The part of the capped program cache's key the inputs give."""
    return tuple(sorted((n, tuple(t.names), t.num_rows)
                        for n, t in inputs.items()))


# one bounded-cache definition for the whole engine (utils/lru.py): the
# executor's program/caps memos and the optimizer cache share it
from ..utils.lru import LruDict as _LruDict


def bind_scan_sources(plan: Plan, inputs: Optional[Dict]) -> Dict:
    """The ONE scan-binding prologue: a Scan carrying its own parquet
    binding needs no inputs= entry; an explicit entry (Table or source)
    for the same name wins. Shared by execute() and the serving layer's
    submit path (serving/scheduler.py) — the binding the cache digest and
    quota charge are computed from must be the binding that executes."""
    inputs = dict(inputs or {})
    for s in plan.scans:
        if s.source not in inputs and s.parquet is not None:
            inputs[s.source] = s.parquet
    return inputs


def _cpu_device():
    try:
        return jax.devices("cpu")[0]
    except Exception:
        return None


def _table_to_cpu(t: Table, dev) -> Table:
    """Salvage a table onto the CPU backend through host memory (the
    degraded tier's handoff for results computed before the breaker
    tripped). Distributed-tier sharded relations gather + compact first
    (their live rows ARE the relation). Streaming source bindings pass
    through untouched — they are host-side handles the CPU tier re-reads
    directly."""
    import dataclasses

    if hasattr(t, "to_local_table"):          # plan.distributed.ShardedRel
        t = t.to_local_table()
    if not isinstance(t, Table):
        return t

    def put(a):
        if a is None:
            return None
        try:
            if a.devices() == {dev}:
                return a            # already home: no host round-trip
        except Exception:
            pass
        return jax.device_put(np.asarray(a), dev)

    def col_cpu(c: Column) -> Column:
        return dataclasses.replace(
            c, data=put(c.data), validity=put(c.validity),
            offsets=put(c.offsets),
            children=type(c.children)(col_cpu(k) for k in c.children))

    if dev is None:
        return t
    return Table([col_cpu(c) for c in t.columns], names=list(t.names))


def _input_has_floats(t) -> bool:
    """Any floating column in a bound Table or streaming source (unknown
    dtypes count as floats — the conservative direction for every gate
    that consumes this)."""
    if isinstance(t, Table):
        return any(
            np.issubdtype(np.dtype(c.dtype.storage_dtype()), np.floating)
            for c in t.columns)
    return bool(getattr(t, "has_floats", True))


class _StreamBreaker(Exception):
    """A streaming chain hit an unrecoverable fault (breaker tripped):
    carries the original error plus the retry cost already paid, so the
    degraded re-run still reports it."""

    def __init__(self, error, retries: int, backoff_ms: float):
        super().__init__(str(error))
        self.error = error
        self.retries = retries
        self.backoff_ms = backoff_ms


class _SyncFeed:
    """Prefetch disabled (SPARK_RAPIDS_TPU_IO_PREFETCH=0): decode inline
    on the executing thread. Same surface as _ChunkPrefetcher."""

    def __init__(self, gen):
        self._gen = gen
        self.decode_intervals = []
        self.decode_ms = 0.0

    def get(self):
        t0 = time.perf_counter()
        try:
            chunk = next(self._gen)
        except StopIteration:
            return None
        t1 = time.perf_counter()
        self.decode_intervals.append((t0, t1))
        self.decode_ms += (t1 - t0) * 1e3
        return chunk

    def close(self):
        self._gen.close()


class _ChunkPrefetcher:
    """Bounded host-side prefetch thread: decodes chunk N+1 (up to `depth`
    ahead) while the consumer executes chunk N — the double-buffer that
    overlaps host bitstream decode with device execution (StreamBox-HBM's
    pipelined-chunk shape; the native decode releases the GIL, so the
    overlap is real CPU concurrency, not just queueing)."""

    _DONE = object()

    def __init__(self, gen, depth: int):
        import queue
        self._gen = gen
        self._q = queue.Queue(maxsize=max(1, depth))
        self._stop = False
        self._err = None
        self.decode_intervals = []      # (start, end) per decoded chunk
        self.decode_ms = 0.0
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="spark-rapids-tpu-io-prefetch")
        self._thread.start()

    def _run(self):
        try:
            while not self._stop:
                t0 = time.perf_counter()
                try:
                    chunk = next(self._gen)
                except StopIteration:
                    break
                t1 = time.perf_counter()
                self.decode_intervals.append((t0, t1))
                self.decode_ms += (t1 - t0) * 1e3
                self._q.put(chunk)
        except BaseException as e:       # surfaces at the consumer's get()
            self._err = e
        finally:
            self._q.put(self._DONE)

    def get(self):
        """Next decoded chunk, or None at end of stream. Re-raises a
        decode-thread error on the consumer thread."""
        item = self._q.get()
        if item is self._DONE:
            if self._err is not None:
                raise self._err
            return None
        return item

    def close(self):
        """Unblock and retire the decode thread (consumer aborted early, or
        end-of-stream cleanup): keep draining until the thread exits so a
        put() blocked on a full queue always wakes."""
        import queue
        self._stop = True
        while self._thread.is_alive():
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)
        try:
            self._gen.close()   # release the reader (mmap/file handle) now,
        except Exception:       # not at GC — the degraded tier may be about
            pass                # to re-open the same file


def _interval_overlap_ms(decode, process) -> float:
    """Total wall time decode intervals and processing intervals ran
    concurrently — the prefetch pipeline's measured win. Linear merge:
    each list is chronological and internally non-overlapping (sequential
    decode, sequential execution)."""
    total = 0.0
    i = j = 0
    while i < len(decode) and j < len(process):
        s1, e1 = decode[i]
        s2, e2 = process[j]
        total += max(0.0, min(e1, e2) - max(s1, s2))
        if e1 < e2:
            i += 1
        else:
            j += 1
    return total * 1e3


# HashAggregate ops that decompose into per-chunk partials + an exact
# merge over the partial rows (count/size merge by summing counts)
_STREAM_AGG_MERGE = {"sum": "sum", "count": "sum", "size": "sum",
                     "min": "min", "max": "max"}


class PlanResult:
    """Output of one plan execution.

    `table` is the result relation; in the capped tier it is PADDED and
    `valid` marks the live rows (`compact()` materializes just those).
    `metrics` maps node label -> OperatorMetrics; `profile()` renders them.
    """

    def __init__(self, plan: Plan, table: Table,
                 valid: Optional[jnp.ndarray],
                 metrics: Dict[str, OperatorMetrics],
                 mode: str, wall_ms: float, attempts: int = 1,
                 caps: Optional[Dict[str, int]] = None, retries: int = 0,
                 degraded: bool = False,
                 breaker: Optional[Dict] = None,
                 backoff_ms: float = 0.0,
                 jit_cache_hits: int = 0):
        self.plan = plan              # the EXECUTED plan (optimized form
        #                               when the optimizer ran; metric
        #                               labels refer to its nodes)
        self.table = table
        self.valid = valid
        self.metrics = metrics
        self.mode = mode
        self.wall_ms = wall_ms
        self.attempts = attempts      # capped-tier cap-escalation attempts
        self.caps = caps              # final (possibly grown) capacities
        self.retries = retries        # plan-level recoverable-fault re-runs
        self.degraded = degraded      # finished on the CPU tier (breaker trip)
        self.breaker = breaker        # {"state","trips","reason","error"
        #                               [,"worker_id" in a fleet]}
        self.backoff_ms = backoff_ms  # total retry backoff across the plan
        self.jit_cache_hits = jit_cache_hits  # capped-tier fingerprint-keyed
        #                               compiled-program reuses this execute
        self.optimizer = None         # OptimizeReport.to_dict() when the
        #                               optimizer ran (set by execute())
        self.cert = None              # analysis/footprint.ResourceCert for
        #                               the executed plan (set by execute();
        #                               None when the certifier declined)
        self.session = ""             # serving-session stamp (docs/serving
        #                               .md): set by execute() from the
        #                               active sessionctx scope, "" outside
        #                               the serving layer
        self.worker = ""              # fleet worker stamp (serving/fleet
        #                               .py): the executor's worker_id, ""
        #                               outside a fleet — on a cache-hit
        #                               COPY it names the worker that
        #                               COMPUTED the entry, which is how
        #                               the soak proves cross-worker
        #                               cache locality
        self.decimal_overflow_rows = 0   # rows or groups a decimal kernel
        #                               nulled by overflow (Spark's
        #                               non-ANSI rule; docs/plan.md)
        self.unique_joins = 0         # capped tier: sort joins that took the
        self.expand_joins = 0         # many-to-one tail / the expansion
        #                               (ops/join.py decides on the device)
        self.gather_slots = 0         # capped tier, over the request's inner
        self.cap_slots = 0            # joins: output slots their column
        #                               gathers touched (whole chunks over
        #                               the live rows, ops/gather.py) and
        #                               the caps they would have paid
        self.outer_joins = 0          # the request's `left_outer` joins and
        self.outer_unmatched_rows = 0  # the left rows they put out
        #                               null-extended (no match, a null key)
        self.full_joins = 0           # its `full_outer` joins, the left
        self.full_unmatched_rows = 0  # rows they put out null-extended and
        self.full_unmatched_right_rows = 0  # the right rows
        self.join_planes_gathered = 0  # eager tier, over the outer joins:
        self.join_slots_gathered = 0  # output planes (a column's data, its
        #                               validity) that went through a
        #                               frame-long `take`, and planes x
        #                               slots (ops/gather.py:
        #                               outer_join_columns)
        self.windows = 0              # the request's `Window` operators,
        self.window_rows = 0          # the rows into them and (the eager
        self.window_partitions = 0    # tier's) the partitions they held
        self.lookup_joins = 0         # eager tier: joins that took the
        self.lookup_compares = 0      # small-side path, and small rows x
        #                               large rows over them (ops/join.py)
        self.compactions = 0          # eager tier: filters that moved rows,
        self.compact_sorted_rows = 0  # and the frame rows that went through
        self.compact_position_rows = 0  # a sort / by the kept rows'
        #                               positions (ops/gather.py)
        self.group_rows = 0           # over the request's keyed aggregates:
        self.groups = 0               # rows in, groups out, and the slots
        self.group_slots = 0          # their finish ran over (the groups in
        #                               the eager tier, the key caps in the
        #                               capped)
        self.group_key_slots_gathered = 0  # and the key planes (a key's
        #                               data, its validity) x slots that
        #                               went through a `take` by the
        #                               groups' first rows (0 where every
        #                               key rode the compaction sort:
        #                               ops/aggregate.py)
        self.expand_slots = 0         # capped tier, over the joins that
        self.expand_cap_slots = 0     # expanded (the general tail, the
        #                               Pallas join): left rows the scatter
        #                               visited plus slots the expansion's
        #                               gathers touched, and the frames'
        #                               (ops/join.py:expansion_slots)
        self.dist_ops = 0             # SPMD walk (plan/distributed.py):
        self.local_ops = 0            # operators that ran over the mesh /
        #                               through the local fallback below a
        #                               sharded input (the sink's gather
        #                               apart);
        self.exchange_edges = 0       # edges that moved data and their
        self.exchange_bytes = 0       # wire bytes; capacity escalations
        self.dist_cap_escalations = 0  # of the distributed primitives
        self.lowerings = 0            # jit lowerings on the request's thread
        self.lowering_ms = 0.0        # (in-memory program cache misses) and
        #                               what they took: 0 once a plan is
        #                               warm (utils/tracing.py; the spans
        #                               `plan.execute`, `plan.attempt` and
        #                               `plan.op` say which part lowered)
        self.cached = False           # served from the serving result cache
        #                               (serving/cache.py): True ONLY on a
        #                               cache-hit COPY — its metrics are
        #                               deep copies, so profile/bench
        #                               consumers never double-attribute
        #                               the original run's wall time

    def compact(self) -> Table:
        """Live rows only (identity in the eager tier)."""
        if self.valid is None:
            return self.table
        idx = jnp.asarray(np.nonzero(np.asarray(self.valid))[0],
                          dtype=jnp.int32)
        return _ops().take_table(self.table, idx, _has_negative=False)

    def profile(self) -> List[Dict]:
        """Per-operator metric rows (post-run observability artifact)."""
        return [m.to_dict() for m in self.metrics.values()]

    def profile_text(self) -> str:
        return render_profile(list(self.metrics.values()),
                              plan_wall_ms=self.wall_ms,
                              attempts=self.attempts, caps=self.caps,
                              degraded=self.degraded, breaker=self.breaker,
                              optimizer=self.optimizer,
                              jit_cache_hits=self.jit_cache_hits,
                              cert=self.cert)


class _CappedRel:
    """A relation inside the capped trace: padded table + live-row mask;
    `unique`, on a sort join's output, the scalar that says which tail the
    join took (ops/join.py:inner_join_capped_tail); `expanded`, on an inner
    or outer join's, what its expansion touched
    (ops/join.py:expansion_slots); `unmatched`, on an outer join's, the
    left rows it put out null-extended, and `unmatched_right`, on a
    `full_outer` join's, the right rows (device scalars)."""

    __slots__ = ("table", "alive", "unique", "expanded", "unmatched",
                 "unmatched_right")

    def __init__(self, table: Table, alive: jnp.ndarray, unique=None,
                 expanded=None, unmatched=None, unmatched_right=None):
        self.table = table
        self.alive = alive
        self.unique = unique
        self.expanded = expanded
        self.unmatched = unmatched
        self.unmatched_right = unmatched_right


class PlanExecutor:
    """Executes validated Plans. One executor may run many plans; compiled
    capped programs are cached per (plan, caps)."""

    def __init__(self, mode: str = "eager",
                 caps: Optional[Dict[str, int]] = None,
                 max_cap_attempts: int = 6,
                 op_retries: int = 2,
                 mesh=None, mesh_axis: str = "data",
                 session=None,
                 block_per_op: bool = True,
                 health=None,
                 degrade: Optional[str] = None,
                 optimize: Optional[bool] = None,
                 cert_budget: Optional[int] = None,
                 worker_id: str = ""):
        if mode not in ("eager", "capped"):
            raise ValueError(f"unknown executor mode {mode!r}")
        # mesh + capped is checked PER PLAN in execute(): only a plan that
        # actually contains a distributed-lowerable operator is rejected
        # (naming it), so trivial row-wise plans still run capped
        from .. import config
        from ..runtime.health import DeviceHealthMonitor
        self.mode = mode
        self.caps = dict(caps or {})
        self.max_cap_attempts = max_cap_attempts
        self.op_retries = op_retries
        if isinstance(mesh, int) and not isinstance(mesh, bool):
            # a device count: all that a configuration file or a Spark
            # conf can say of a mesh. More than `jax.devices()` raises.
            from ..parallel.shuffle import make_mesh
            mesh = make_mesh(mesh, axis=mesh_axis)
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.session = session
        # fleet worker identity (serving/fleet.py): stamped on every
        # result and per-op metric this executor produces, "" outside a
        # fleet — failure attribution and the soak's cross-worker
        # cache-locality proof both need to know WHICH worker ran a plan
        self.worker_id = str(worker_id)
        self.block_per_op = block_per_op
        # health: the degradation policy owner (runtime/health.py). Pass a
        # shared monitor to give several executors one breaker per device.
        self.health = health if health is not None else DeviceHealthMonitor()
        self.degrade = degrade if degrade is not None else config.breaker_degrade()
        if self.degrade not in ("cpu", "off"):
            raise ValueError(f"unknown degrade policy {self.degrade!r} "
                             "(expected cpu or off)")
        # rule-based logical optimizer (plan/optimizer.py): on by default,
        # SPARK_RAPIDS_TPU_OPTIMIZER=off or optimize=False disables
        self.optimize = (config.optimizer_enabled() if optimize is None
                         else bool(optimize))
        # admission-time footprint budget (analysis/footprint.py): a plan
        # whose certified per-operator residency hi-bound exceeds this is
        # rejected (or degraded, per SPARK_RAPIDS_TPU_CERT_ADMISSION)
        # before any compilation. None defers to the
        # SPARK_RAPIDS_TPU_CERT_BUDGET_BYTES knob; 0 disables.
        self.cert_budget = cert_budget
        self._opt_cache = _LruDict(64)  # (root, bound sig) -> (plan, schemas,
        #                                 report): one rewrite per binding
        self._cert_cache = _LruDict(64)  # (root, binding sig) ->
        #                                 ResourceCert: one certify walk
        #                                 per binding, not per execute
        self._verify_cache = _LruDict(128)  # passed pre-execution-gate
        #                                 verdicts: repeat executions of a
        #                                 cached (plan, binding) rewrite
        #                                 skip re-verification (failures
        #                                 raise and are never cached)
        self._jit_cache: Dict[Tuple, Tuple[Callable, Dict]] = _LruDict(64)
        # escalated capacities survive per plan STRUCTURE (keyed by the
        # canonical fingerprint — optimizer.plan_fingerprint), so the next
        # execute() of this plan, or of an equivalent plan built
        # independently, starts from the grown caps instead of re-paying
        # the whole overflow ladder
        self._caps_memo: Dict[str, Dict[str, int]] = _LruDict(256)
        # distributed-tier capacity memo: (fingerprint, node index) ->
        # final escalated caps, same contract as _caps_memo
        self._dist_caps_memo: Dict[Tuple, Dict] = _LruDict(256)
        # request numbers for direct execute() calls (a serving worker
        # scopes its ticket's instead — runtime/sessionctx.py)
        self._requests = itertools.count()

    def _mesh_of(self, plan: Plan):
        """The mesh `plan` runs over, or None: the executor has none, or
        the plan holds an operator that keeps it on one chip whole
        (plan/optimizer.py:mesh_local_reason: a `left_outer` join; the
        optimize report names it under `<label>/mesh`)."""
        if self.mesh is None:
            return None
        from .optimizer import mesh_local_reason
        return None if mesh_local_reason(plan.nodes) else self.mesh

    def _check_capped_mesh(self, plan: Plan) -> None:
        """mode="capped" with a mesh: reject ONLY plans that contain a
        distributed-lowerable operator (the capped tier would silently run
        it on one chip), naming the offending node."""
        if self.mesh is None or self.mode == "eager":
            return
        for n in plan.nodes:
            if isinstance(n, (Exchange, HashJoin, HashAggregate, Sort,
                              TopK, Union, Window)):
                raise PlanValidationError(
                    f"{n.label}: distributed lowering (mesh=) exists only "
                    "in the eager tier; a capped executor would silently "
                    f"run this {n.kind} on one chip — drop the mesh or use "
                    "mode=\"eager\"")

    # ---- entry point ------------------------------------------------------
    def execute(self, plan: Plan,
                inputs: Optional[Dict[str, Table]] = None,
                tier: Optional[str] = None) -> PlanResult:
        """Run `plan` over `inputs`. `tier` pins the execution tier:
        None/"device" is the normal path (device with breaker-gated CPU
        degradation); "cpu" runs the WHOLE plan on the degraded CPU tier
        without touching the device — the serving layer's route for
        over-quota admission under the degrade policy and for draining a
        queue while the breaker is open (docs/serving.md)."""
        # request identity for the program's spans (utils/tracing.py):
        # the serving worker has scoped its ticket's number; a direct
        # call takes this executor's next one
        ctx = _sessionctx()
        request = ctx.current_request()
        if request < 0:
            request = next(self._requests)
        from ..ops.decimal_utils import overflow_counts
        with ctx.request_scope(request), bracket("plan.execute") as sp, \
                overflow_counts() as nulled:
            res = self._execute_request(plan, inputs, tier, nulled)
            # what the request lowered (utils/tracing.py): 0 once warm
            res.lowerings, res.lowering_ms = sp.lowered()
            sp.set_metadata(decimal_overflow_rows=res.decimal_overflow_rows,
                            group_rows=res.group_rows, groups=res.groups,
                            group_slots=res.group_slots,
                            group_key_slots_gathered=(
                                res.group_key_slots_gathered),
                            unique_joins=res.unique_joins,
                            expand_joins=res.expand_joins,
                            lookup_joins=res.lookup_joins,
                            lookup_compares=res.lookup_compares,
                            compactions=res.compactions,
                            compact_sorted_rows=res.compact_sorted_rows,
                            compact_position_rows=res.compact_position_rows,
                            outer_joins=res.outer_joins,
                            outer_unmatched_rows=res.outer_unmatched_rows,
                            full_joins=res.full_joins,
                            full_unmatched_rows=res.full_unmatched_rows,
                            full_unmatched_right_rows=(
                                res.full_unmatched_right_rows),
                            join_planes_gathered=res.join_planes_gathered,
                            join_slots_gathered=res.join_slots_gathered,
                            windows=res.windows,
                            window_rows=res.window_rows,
                            window_partitions=res.window_partitions,
                            gather_slots=res.gather_slots,
                            cap_slots=res.cap_slots,
                            expand_slots=res.expand_slots,
                            expand_cap_slots=res.expand_cap_slots)
            if self.mesh is not None:
                sp.set_metadata(exchange_edges=res.exchange_edges,
                                exchange_bytes=res.exchange_bytes,
                                dist_ops=res.dist_ops,
                                local_ops=res.local_ops,
                                dist_cap_escalations=res.dist_cap_escalations)
            return res

    def _count_groups(self, res: PlanResult) -> None:
        """`group_rows`, `groups`, `group_slots`, `group_key_slots_gathered`
        of a result, from its operators' metrics (a cached or degraded
        result keeps its own)."""
        if res.cached or res.group_slots:
            return
        for i, node in enumerate(res.plan.nodes):
            m = res.metrics.get(node.label)
            if not isinstance(node, HashAggregate) or not node.keys \
                    or m is None:
                continue
            res.group_rows += int(m.rows_in)
            res.groups += int(m.rows_out)
            res.group_slots += (self._node_cap(res.caps, "key_cap", i)
                                if res.mode == "capped" and res.caps
                                else int(m.rows_out))
            res.group_key_slots_gathered += int(m.key_slots_gathered)

    @staticmethod
    def _count_lookups(res: PlanResult) -> None:
        """`lookup_joins`, `lookup_compares` of a result, from its
        operators' metrics (a cached result keeps its own)."""
        from ..ops.join_lookup import KERNEL_LABEL
        if res.cached or res.lookup_joins:
            return
        took = [m for m in res.metrics.values()
                if m.kernel == KERNEL_LABEL]
        res.lookup_joins = len(took)
        res.lookup_compares = sum(m.lookup_compares for m in took)

    @staticmethod
    def _count_compactions(res: PlanResult) -> None:
        """`compactions` and the frame rows that went each way, from the
        operators' metrics (a cached result keeps its own)."""
        if res.cached or res.compactions:
            return
        for m in res.metrics.values():
            if m.compact in ("", "none"):
                continue
            res.compactions += 1
            if m.compact == "positions":
                res.compact_position_rows += int(m.rows_in)
            else:
                res.compact_sorted_rows += int(m.rows_in)

    @staticmethod
    def _count_outer(res: PlanResult) -> None:
        """`outer_joins`, `outer_unmatched_rows`, the `full_*` counters
        and the eager outer joins' `join_planes_gathered` /
        `join_slots_gathered` of a result, from its operators' metrics (a
        cached result keeps its own)."""
        if res.cached or res.outer_joins or res.full_joins:
            return
        for node in res.plan.nodes:
            m = res.metrics.get(node.label)
            if not isinstance(node, HashJoin) or m is None:
                continue
            if node.how == "left_outer":
                res.outer_joins += 1
                res.outer_unmatched_rows += int(m.unmatched_rows)
            elif node.how == "full_outer":
                res.full_joins += 1
                res.full_unmatched_rows += int(m.unmatched_rows)
                res.full_unmatched_right_rows += int(m.unmatched_right_rows)
            res.join_planes_gathered += int(m.planes_gathered)
            res.join_slots_gathered += int(m.slots_gathered)

    @staticmethod
    def _count_windows(res: PlanResult) -> None:
        """`windows`, `window_rows`, `window_partitions` of a result, from
        its operators' metrics (a cached result keeps its own)."""
        if res.cached or res.windows:
            return
        for node in res.plan.nodes:
            m = res.metrics.get(node.label)
            if isinstance(node, Window) and m is not None:
                res.windows += 1
                res.window_rows += int(m.rows_in)
                res.window_partitions += int(m.window_partitions)

    def _execute_request(self, plan, inputs, tier, nulled=()) -> PlanResult:
        if tier not in (None, "device", "cpu"):
            raise ValueError(f"unknown execution tier {tier!r} "
                             "(expected device or cpu)")
        with span("plan.bind"):
            self._check_capped_mesh(plan)
            inputs = bind_scan_sources(plan, inputs)
            missing = [s for s in plan.input_names if s not in inputs]
            if missing:
                raise PlanValidationError(
                    f"unbound plan input(s) {missing}")
            # full validation against the bound tables' actual schemas —
            # authored-plan errors surface against authored labels, BEFORE
            # any optimizer rewrite renames nodes (streaming sources expose
            # .names from the parquet footer, so the same contract applies)
            bound = {name: tuple(t.names) for name, t in inputs.items()}
            schemas = plan.resolve_schemas(bound)
        report = None
        authored = plan
        if self.optimize:
            with span("plan.optimize"):
                plan, schemas, report = self._optimized(plan, inputs, bound)
        from .. import config
        if config.verify_plans():
            with span("plan.verify"):
                self._verify_execution(authored, plan, report, inputs,
                                       bound)
        # the AUTHORED fingerprint keys the adaptive feedback loop
        # (plan/stats.py): cold and warm executions of one authored plan
        # share it even when a stats-driven rewrite changes the executed
        # plan's fingerprint (so warm cap seeding survives a build-side
        # flip via the global cap keys)
        source_fp = authored.fingerprint
        # static resource certifier (analysis/footprint.py): sound
        # per-operator [lo, hi] row and byte bounds over the plan about
        # to run — stamped on the result, consulted by the capped tier's
        # cold-run cap seeding, and compared against the device budget
        # BEFORE any compilation when one is configured
        cert = self._certify(plan, inputs, bound)
        res = None
        if tier == "cpu":
            # pinned to the degraded tier: same machinery as a breaker
            # trip, without consulting the device budget (it does not
            # bind on the CPU tier)
            self.health.start_plan_attempt()
            res = self._execute_degraded(
                plan, inputs, schemas, {}, {}, start=0,
                t_plan0=time.perf_counter(), mode=self.mode)
        budget = (self.cert_budget if self.cert_budget is not None
                  else config.cert_budget_bytes())
        if res is None and budget and cert is not None:
            violations = cert.over_budget(budget)
            if violations:
                from ..analysis.footprint import ResourceAdmissionError
                if config.cert_admission() == "reject":
                    raise ResourceAdmissionError(
                        violations, "admission gate: certified footprint "
                        f"exceeds the {budget} B device budget")
                # degrade: the device budget does not bind on the CPU
                # tier — run the whole plan there, same machinery as a
                # breaker trip, without touching the device
                self.health.start_plan_attempt()
                res = self._execute_degraded(
                    plan, inputs, schemas, {}, {}, start=0,
                    t_plan0=time.perf_counter(), mode=self.mode)
        if res is None:
            from ..runtime.admission import active_session
            with span("plan.run"), \
                    (active_session(self.session)
                     if self.session is not None
                     else contextlib.nullcontext()):
                res = self._execute(plan, inputs, schemas, source_fp, cert)
        with span("plan.result"):
            res.cert = cert
            if nulled:  # eager tiers: one read-back, decimal plans only
                with span("ops.host_sync", site="decimal.overflow"):
                    res.decimal_overflow_rows += int(sum(nulled))
            self._count_groups(res)
            self._count_lookups(res)
            self._count_compactions(res)
            self._count_outer(res)
            self._count_windows(res)
            # serving-session stamp (runtime/sessionctx.py,
            # docs/serving.md): results and per-op metrics carry the tenant
            # they executed for — dispatcher worker threads are multiplexed
            # across sessions, so thread identity cannot answer this after
            # the fact
            sid = _sessionctx().current_session_id()
            if sid is not None:
                res.session = sid
                for mm in res.metrics.values():
                    mm.session = sid
            if self.worker_id:
                res.worker = self.worker_id
                for mm in res.metrics.values():
                    mm.worker_id = self.worker_id
            if report is not None:
                res.optimizer = report.to_dict()
        from . import stats as stats_mod
        store = stats_mod.active_store()
        if store is not None:
            # record only what actually ran, under the backend it ran
            # ON: a degraded result finished on the CPU tier and must
            # never drive device-side decisions (docs/adaptive.md)
            with span("plan.stats"):
                store.record_result(
                    plan, res,
                    backend="cpu" if res.degraded else jax.default_backend(),
                    source_fp=source_fp)
        return res

    def _verify_execution(self, authored, plan, report, inputs, bound):
        """Debug-mode pre-execution gate (SPARK_RAPIDS_TPU_VERIFY_PLANS,
        on in tests — docs/analysis.md): the plan about to run must pass
        the static verifier. Schema propagation and (for Table bindings)
        dtype typing always check; the rewrite-pair invariants check when
        the optimizer ran; partitioning soundness checks when
        exchange_planning placed distributed boundaries. Raises
        PlanVerificationError naming the invariant and operator."""
        from ..analysis import verifier
        input_dtypes = {
            name: {cn: c.dtype for cn, c in zip(t.names, t.columns)}
            for name, t in inputs.items() if isinstance(t, Table)}
        floats = any(_input_has_floats(t) for t in inputs.values())
        mesh = self._mesh_of(plan)
        planned = (report is not None and not report.fell_back
                   and mesh is not None and self.mode == "eager"
                   and mesh.shape[self.mesh_axis] > 1)
        # verdicts memoize on everything the checks read — a repeat
        # execution of the same (plan, binding) pays nothing, the same
        # contract as the rewrite cache feeding it
        key = (authored.root, plan.root, tuple(sorted(bound.items())),
               tuple((n, tuple(repr(d) for d in cols.values()))
                     for n, cols in sorted(input_dtypes.items())),
               floats, planned,
               None if report is None else (report.fingerprint,
                                            report.fell_back))
        if self._verify_cache.get(key):
            return
        if report is None and plan is authored:
            rep = verifier.verify(plan, bound=bound,
                                  input_dtypes=input_dtypes,
                                  float_inputs=floats)
        else:
            rep = verifier.verify_rewrite(authored, plan, bound=bound,
                                          input_dtypes=input_dtypes,
                                          float_inputs=floats,
                                          planned=planned, report=report)
        rep.raise_if_failed("pre-execution gate")
        self._verify_cache[key] = True

    # a bound table of at most this many rows is a dimension whose filter
    # is counted before exchanges are planned (TPC-DS's largest dimension
    # that a query filters and broadcasts, `date_dim`, has 73,049 rows; a
    # million rows are one predicate pass of about a millisecond)
    _COUNTED_FILTER_ROWS = 1 << 20

    def _counted_filters(self, plan, inputs, store, backend
                         ) -> Dict[str, int]:
        """{"filter:<subtree fingerprint>": rows} of every Filter directly
        over a scan of a small bound table that the stats store has not
        observed yet, COUNTED (one predicate pass over at most a million
        rows, one number read back). They go to the optimizer among the
        bound row counts: what a bound dimension keeps under its filter is
        as much a fact about the binding as its length. The estimator's
        guess for a filter is half its input; 15 days of a 73,049-row
        calendar guessed at 36,524 rows plan a hash exchange of the whole
        fact side where a broadcast of 15 rows is due, and the first
        execution of a plan has no observation to correct it."""
        from .optimizer import subtree_fingerprints
        out: Dict[str, int] = {}
        fps = None
        for node in plan.nodes:
            if not (isinstance(node, Filter) and isinstance(node.child, Scan)
                    and not node.child.types):
                continue
            t = inputs.get(node.child.source)
            if not isinstance(t, Table) \
                    or not 0 < t.num_rows <= self._COUNTED_FILTER_ROWS:
                continue
            fps = fps or subtree_fingerprints(plan.root)
            fp = fps[id(node)]
            if store is not None and \
                    store.observed_rows(backend, fp) is not None:
                continue        # an execution has counted it since
            with span("ops.host_sync", site="optimize.counted_filter"):
                out["filter:" + fp] = int(
                    jnp.sum(node.predicate.truth(t)))
        return out

    def _optimized(self, plan, inputs, bound):
        """Rewrite `plan` through the rule pipeline, once per (plan,
        binding): repeat executions reuse the cached rewrite (and through
        the fingerprint-keyed program cache, the compiled XLA program)."""
        from .optimizer import optimize as run_optimizer
        # fp reductions are not reorder-exact: float columns anywhere in
        # the inputs disable the row-reordering build_side rule. The flag
        # is part of the cache KEY — a rewrite computed from integer
        # inputs must not be served to a float binding of the same
        # names/shapes (the gate would be bypassed by the cache hit)
        floats = any(_input_has_floats(t) for t in inputs.values())
        # scans bound to streaming sources: the scan_pruning rule fires
        # only for these, so the set belongs in the cache key too
        streaming = frozenset(n for n, t in inputs.items()
                              if not isinstance(t, Table))
        # the exchange_planning rule fires only for a meshed eager
        # executor, and its placements depend on the mesh width AND the
        # broadcast threshold (read at use time per config.py's
        # monkeypatch contract) — all of it belongs in the cache key
        from .. import config
        mesh_peers = (self.mesh.shape[self.mesh_axis]
                      if self.mesh is not None and self.mode == "eager"
                      else None)
        bc_rows = config.broadcast_rows() if mesh_peers else None
        bc_bytes = config.broadcast_bytes() if mesh_peers else None
        # verify mode changes which plan survives a mid-pipeline invalid
        # rewrite (per-rule fall-back), so it belongs in the cache key too
        verify_rules = config.verify_plans()
        # column dtypes feed the resource certifier's byte bounds (the
        # broadcast byte-legality proof and the certified estimator
        # tier), so the dtype signature belongs in the cache key: a
        # rewrite proven over i8 columns must not serve an i64 binding
        # of the same names/shapes
        input_dtypes = {
            name: {cn: c.dtype for cn, c in zip(t.names, t.columns)}
            for name, t in inputs.items() if isinstance(t, Table)}
        dtype_sig = tuple(
            (name, tuple((cn, repr(dt)) for cn, dt in cols.items()))
            for name, cols in sorted(input_dtypes.items()))
        # adaptive rewrites consume the stats store's observations, so
        # the store's generation joins the key: a cached rewrite must not
        # outlive the observations it ignored (each successful execution
        # records, so warm executions re-optimize — the rewrite pipeline
        # is cheap next to execution, and only paid while stats are on)
        from . import stats as stats_mod
        store = stats_mod.active_store()
        stats_gen = None if store is None else (store.uid,
                                                store.generation)
        key = (plan.root, tuple(sorted(bound.items())),
               tuple(sorted((n, t.num_rows) for n, t in inputs.items())),
               floats, streaming, mesh_peers, bc_rows, bc_bytes,
               verify_rules, dtype_sig, stats_gen)
        hit = self._opt_cache.get(key)
        if hit is None:
            bound_rows = {n: t.num_rows for n, t in inputs.items()}
            backend = jax.default_backend()
            if mesh_peers and mesh_peers > 1:
                bound_rows.update(self._counted_filters(plan, inputs, store,
                                                        backend))
            opt, report = run_optimizer(
                plan, bound, bound_rows,
                float_inputs=floats, streaming_sources=streaming,
                mesh_peers=mesh_peers, verify_rules=verify_rules,
                stats=store, backend=backend, input_dtypes=input_dtypes)
            if (store is not None and not verify_rules
                    and opt is not plan and not report.fell_back
                    and report.stats_driven()):
                # EVERY stats-driven rewrite passes the verify_rewrite
                # gate, even with SPARK_RAPIDS_TPU_VERIFY_PLANS off
                # (docs/adaptive.md): observations must never weaken the
                # static pipeline's guarantees. A violation (defensive —
                # the same rule guards protect both paths) reverts to
                # the static rewrite rather than failing the query.
                from ..analysis import verifier
                rep = verifier.verify_rewrite(
                    plan, opt, bound=bound, input_dtypes=input_dtypes,
                    float_inputs=floats, report=report,
                    # distributed plans: the partitioning-soundness
                    # layer must check the very exchange placements the
                    # observed cardinalities picked (same condition as
                    # _verify_execution's `planned`)
                    planned=bool(mesh_peers and mesh_peers > 1))
                if not rep.ok:
                    opt, report = run_optimizer(
                        plan, bound, bound_rows,
                        float_inputs=floats, streaming_sources=streaming,
                        mesh_peers=mesh_peers, verify_rules=verify_rules,
                        input_dtypes=input_dtypes)
                    report.stats_reverted = True
            hit = (opt, opt.resolve_schemas(bound), report)
            self._opt_cache[key] = hit
        return hit

    def _certify(self, plan, inputs, bound):
        """Resource-certify the plan about to run (analysis/footprint.py):
        bound input cardinalities (Tables and streaming sources both
        expose num_rows), Table column dtypes for byte widths, validity
        presence for the keyed-aggregate lo bound. Memoized per (plan,
        binding) like the rewrite cache feeding it — a hot fingerprint-
        cached plan must not re-pay the certify walk per execute.
        Defensive-None on an internal certifier error — sizing is an
        optimization layer and must never fail a query that would
        otherwise run."""
        from ..analysis import footprint
        try:
            with span("plan.certify"):
                input_dtypes, input_nullable = \
                    footprint.table_metadata(inputs)
                bound_rows = {n: t.num_rows for n, t in inputs.items()}
                mesh = self._mesh_of(plan)
                n_peers = (mesh.shape[self.mesh_axis]
                           if mesh is not None
                           and self.mode == "eager" else 1)
                key = (plan.root, tuple(sorted(bound.items())),
                       tuple(sorted(bound_rows.items())),
                       tuple((n, tuple((cn, repr(dt))
                                       for cn, dt in cols.items()))
                             for n, cols in sorted(input_dtypes.items())),
                       tuple((n, tuple(sorted(cols.items())))
                             for n, cols
                             in sorted(input_nullable.items())),
                       n_peers)
                hit = self._cert_cache.get(key)
                if hit is None:
                    hit = footprint.certify(
                        plan, bound=bound, bound_rows=bound_rows,
                        input_dtypes=input_dtypes,
                        input_nullable=input_nullable, n_peers=n_peers)
                    self._cert_cache[key] = hit
                return hit
        except Exception:
            return None

    def _execute(self, plan, inputs, schemas, source_fp=None, cert=None):
        if self.mode == "eager":
            return self._execute_eager(plan, inputs, schemas)
        return self._execute_capped(plan, inputs, schemas, source_fp,
                                    cert)

    def explain(self, plan: Plan, optimized: bool = False,
                inputs: Optional[Dict[str, Table]] = None) -> str:
        """The authored operator tree; with `optimized=True`, the authored
        AND optimizer-rewritten trees plus the per-rule rewrite summary.
        Pass `inputs` to render the EXACT rewrite execute() runs for that
        binding (bound schemas/rows + the float build_side gate); without
        them the rewrite uses declared schemas and est_rows hints only,
        so bind-time pruning/estimates may differ."""
        if not optimized:
            return plan.explain()
        if inputs is not None:
            if not self.optimize:
                # "EXACT rewrite execute() runs" — which, for a disabled
                # optimizer, is no rewrite at all
                return (plan.explain() + "\n\noptimizer: disabled for "
                        "this executor (optimize=False / "
                        "SPARK_RAPIDS_TPU_OPTIMIZER=off) — the authored "
                        "plan executes verbatim")
            bound = {name: tuple(t.names) for name, t in inputs.items()}
            plan.resolve_schemas(bound)         # validate the binding
            opt, _, report = self._optimized(plan, inputs, bound)
            # certified footprint of the EXACT plan execute() would run
            # for this binding (analysis/footprint.py)
            cert = self._certify(opt, inputs, bound)
            cert_block = [cert.render()] if cert is not None else []
            transport_block = ([self._transport_summary()]
                               if self.mesh is not None
                               and self.mode == "eager" else [])
            return "\n".join(["== authored ==", plan.explain(), "",
                              "== optimized ==", opt.explain(), "",
                              report.summary(), *cert_block,
                              *transport_block,
                              self._kernel_summary()])
        from .optimizer import explain_optimized
        return explain_optimized(plan) + "\n" + self._kernel_summary()

    def device_op_owners(self, plan: Plan,
                         inputs: Optional[Dict[str, Table]] = None,
                         nested: bool = False) -> Dict[str, str]:
        """Who owns `fusion.32`: {HLO instruction name: "<idx>.<kind>"}
        for the capped program this executor runs for `plan` over
        `inputs` at the capacities its next execution would start from
        (after a run: the ones that run ended on). A device trace prints
        bare instruction names (`jit_capped_plan/fusion.32`); every
        operator runs under the scope `<toposort index>.<kind>` of the
        EXECUTED plan (the indices `explain(optimized=True, inputs=...)`
        lists top-down from the leaves), and the compiled executable's
        text keeps that scope in each instruction's `op_name`, a fusion
        under its own. Instructions outside any operator (parameters,
        copies the compiler added) are left out. `nested=True` appends
        `/decimal.<op>` where a decimal kernel's scope (mul, add, sub,
        rescale, div, sum) lies below the operator's. On request only: this
        lowers the program again and reads the executable back through
        the compile cache; `execute` never calls it."""
        return _scope_owners(self._capped_program_text(plan, inputs), nested)

    def device_op_sources(self, plan: Plan,
                          inputs: Optional[Dict[str, Table]] = None
                          ) -> Dict[str, Tuple[str, str, str]]:
        """What `fusion.32` is, beside whose: {HLO instruction name:
        (owner, primitive, "file:line function")} for the program
        `device_op_owners` reads, from the same text (see `_op_sources`;
        owner "" outside every operator). `tools/device_ops.py` prints a
        trace's largest ops and source lines with it."""
        text = self._capped_program_text(plan, inputs)
        owners = _scope_owners(text)
        return {name: (owners.get(name, ""), prim, where)
                for name, (prim, where) in _op_sources(text).items()}

    def _capped_program_text(self, plan: Plan, inputs) -> str:
        if self.mode != "capped":
            raise PlanValidationError(
                "device_op_owners reads the capped tier's one program; "
                "the eager tier's operators are separate programs, told "
                "apart by their plan.op spans")
        inputs = bind_scan_sources(plan, inputs)
        bound = {name: tuple(t.names) for name, t in inputs.items()}
        schemas = plan.resolve_schemas(bound)
        source_fp = plan.fingerprint
        if self.optimize:
            plan, schemas, _ = self._optimized(plan, inputs, bound)
        caps, _ = self._starting_caps(plan, inputs, source_fp,
                                      self._certify(plan, inputs, bound))
        fn = self._jitted_capped(plan, schemas, caps, _input_key(inputs))[0]
        return fn.lower(dict(inputs)).compile().as_text()

    @staticmethod
    def _transport_summary() -> str:
        """One exchange-transport line for a meshed explain(optimized=True)
        (plan/transport.py, docs/distributed.md#transport): what the
        exchanges of this plan would ship under the current knobs."""
        from .. import config
        pack = config.exchange_pack()
        codecs = ",".join(sorted(config.exchange_codecs())) if pack else ""
        return ("transport: pack=" + ("on" if pack else "off")
                + f" codecs={codecs or 'none'}"
                + " async=" + ("on" if config.exchange_async() else "off")
                + " (wire vs logical bytes per edge on profile())")

    @staticmethod
    def _kernel_summary() -> str:
        """One registry line for explain(optimized=True): the signature-
        independent per-op choice on the current backend (docs/kernels.md).
        Signature-conditional kernels (the Pallas set) resolve per dispatch
        and show up on OperatorMetrics.kernel / profile_text post-run."""
        from ..ops.registry import REGISTRY
        pairs = ", ".join(f"{op}={name}"
                          for op, name in sorted(REGISTRY.summary().items()))
        return (f"kernels [{jax.default_backend()}]: {pairs} "
                "(signature-conditional kernels resolve per dispatch; see "
                "profile())")

    # ---- faultinj ---------------------------------------------------------
    @staticmethod
    def _faultinj_point(node: PlanNode):
        """Plan-level interception: rules keyed `plan.<Kind>` (or `*`) fire
        here, in addition to any op-level shims underneath."""
        from .. import faultinj
        inj = faultinj.active()
        if inj is not None:
            inj.on_compute(f"plan.{node.kind}")

    # ---- health / degradation policy --------------------------------------
    def _breaker_snapshot(self) -> Dict:
        br = self.health.breaker
        snap = {"state": br.state, "trips": br.trips,
                "reason": br.last_trip_reason, "error": br.last_trip_error}
        wid = getattr(self.health, "worker_id", "")
        if wid:
            snap["worker_id"] = wid
        return snap

    def _handle_fault(self, err, op_label: str, attempt: int,
                      metric: OperatorMetrics) -> bool:
        """One failure on the device path. Returns True when the caller
        should retry the failed unit (backoff already slept, counters
        bumped); returns False when the breaker tripped and the caller must
        degrade (or re-raise under degrade="off")."""
        from ..runtime import health as _h
        kind = self.health.record_failure(op_label, err)
        if kind == _h.TRANSIENT:
            if attempt < self.op_retries:
                slept = self.health.try_retry(attempt)
                if slept is not None:
                    metric.retries += 1
                    metric.backoff_ms += slept
                    self._maybe_rollback(err)
                    return True
                kind = _h.STICKY        # shared retry budget exhausted
            else:
                kind = _h.STICKY        # per-op retry bound exhausted
        self.health.trip(kind, err)
        return False

    def _maybe_rollback(self, err) -> None:
        """RetryOOM transients: honor the arbiter's rollback contract
        (block until memory frees) before the backoff retry, best-effort."""
        from ..runtime.adaptor import CpuRetryOOM, RetryOOM
        if not isinstance(err, (RetryOOM, CpuRetryOOM)):
            return
        sess = self.session
        if sess is None:
            from ..runtime.admission import get_active_session
            sess = get_active_session()
        if sess is None:
            return
        try:
            sess.arbiter.block_thread_until_ready()
        except Exception:
            pass

    # ---- eager tier -------------------------------------------------------
    def _execute_eager(self, plan, inputs, schemas) -> PlanResult:
        from ..runtime.admission import operand_nbytes
        t_plan0 = time.perf_counter()
        results: Dict[int, Table] = {}
        metrics: Dict[str, OperatorMetrics] = {}
        self.health.start_plan_attempt()
        if self.degrade != "off" and not self.health.admit():
            # device quarantined (breaker open / failed half-open probe):
            # run the whole plan on the CPU tier without touching it
            return self._execute_degraded(plan, inputs, schemas, results,
                                          metrics, start=0, t_plan0=t_plan0,
                                          mode="eager")
        # full-plan SPMD tier (plan/distributed.py): with a mesh, nodes
        # execute over sharded relations and gather only at the sink (or
        # the first operator with no distributed form). Streaming prefixes
        # are a single-chip pipeline shape — the distributed tier
        # materializes source-bound scans through one pruned read instead.
        dist = None
        if self._mesh_of(plan) is not None:
            from .distributed import DistContext
            dist = DistContext(self, plan, inputs)
        # streamable prefixes over source-bound scans run morsel-at-a-time
        # (decode chunk N+1 on host while chunk N executes); their interior
        # nodes never materialize a whole relation, only the chain tail does
        chains = {} if dist is not None else self._stream_chains(plan, inputs)
        chain_interior = {id(n) for ch in chains.values() for n in ch[:-1]}
        node_index = {id(n): i for i, n in enumerate(plan.nodes)}
        try:
            for i, node in enumerate(plan.nodes):
                if id(node) in chain_interior:
                    continue        # runs inside its chain, at the tail
                if id(node) in chains:
                    chain = chains[id(node)]
                    try:
                        out = self._exec_stream_chain(chain, inputs,
                                                      schemas, metrics,
                                                      node_index)
                    except _StreamBreaker as sb:
                        if self.degrade == "off":
                            raise sb.error
                        # replay the chain's remaining chunks — and the
                        # whole prefix — on the CPU tier from the scan
                        return self._execute_degraded(
                            plan, inputs, schemas, results, metrics,
                            start=node_index[id(chain[0])],
                            t_plan0=t_plan0, mode="eager",
                            carry_retries=sb.retries,
                            carry_backoff_ms=sb.backoff_ms)
                    results[id(node)] = out
                    continue
                child_tables = [results[id(c)] for c in node.children]
                m = OperatorMetrics(label=node.label, kind=node.kind,
                                    describe=node.describe())
                t0 = time.perf_counter()
                attempt = 0
                out = None
                while True:
                    try:
                        with _op_span(node, i) as osp:
                            self._faultinj_point(node)
                            if dist is not None:
                                out = dist.exec_node(node, child_tables,
                                                     inputs, schemas, m,
                                                     metrics)
                            else:
                                out = self._exec_eager_node(
                                    node, child_tables, inputs, schemas, m)
                                _say_op(osp, m, node, child_tables, out)
                            # blocked inside the span, so that the span
                            # holds the operator's device work (an async
                            # exchange in flight stays unblocked: that
                            # would forfeit the transfer/compute overlap)
                            if self.block_per_op \
                                    and not getattr(out, "pending", False):
                                with span("plan.wait", site="op"):
                                    jax.block_until_ready(
                                        [c.data for c in out.columns])
                        break
                    except _fault_surface() as err:
                        if self._handle_fault(err, node.label, attempt, m):
                            attempt += 1
                            continue
                        if self.degrade == "off":
                            raise
                        return self._execute_degraded(
                            plan, inputs, schemas, results, metrics,
                            start=i, t_plan0=t_plan0, mode="eager",
                            first_metric=m)
                if attempt:
                    # retried to success: the fault was genuinely transient,
                    # so it must not count toward a later sticky trip
                    self.health.record_success(node.label)
                if getattr(out, "pending", False):
                    # async exchange in flight (plan/distributed.PendingRel,
                    # SPARK_RAPIDS_TPU_EXCHANGE_ASYNC): blocking here would
                    # forfeit the transfer/compute overlap — wall_ms,
                    # rows_out, bytes_out, and overlap-ms stamp onto this
                    # metric row when a consumer resolves it
                    m.rows_in = sum(t.num_rows for t in child_tables)
                else:
                    # wall is compute (all attempts), NOT the backoff idle
                    # time — that is reported separately in backoff_ms,
                    # not double-counted
                    m.wall_ms = (time.perf_counter() - t0) * 1e3 \
                        - m.backoff_ms
                    m.rows_in = sum(t.num_rows for t in child_tables)
                    m.rows_out = out.num_rows
                    m.bytes_out = operand_nbytes(
                        out if isinstance(out, Table) else out.table)
                metrics[node.label] = m
                results[id(node)] = out
        except BaseException as err:
            # debuggability: a failed plan still surfaces what completed.
            # First attachment wins — a failed degraded re-run has already
            # recorded ITS metrics, which the stale device-tier dict here
            # must not clobber.
            if not hasattr(err, "plan_metrics"):
                try:
                    err.plan_metrics = dict(metrics)
                except Exception:
                    pass
            raise
        with span("plan.result"):
            root_out = results[id(plan.root)]
            if not isinstance(root_out, Table):
                # sink gather: the single host-facing collect of a
                # distributed plan (explicit when the optimizer placed
                # Exchange(gather) at the root; implicit here otherwise)
                root_out = root_out.to_local_table()
            wall = (time.perf_counter() - t_plan0) * 1e3
            res = PlanResult(plan, root_out, None, metrics,
                             "eager", wall,
                             retries=sum(mm.retries
                                         for mm in metrics.values()),
                             breaker=self._breaker_snapshot(),
                             backoff_ms=sum(mm.backoff_ms
                                            for mm in metrics.values()))
            if dist is not None:
                res.dist_ops, res.local_ops = dist.dist_ops, dist.local_ops
                res.exchange_edges = dist.exchange_edges
                res.exchange_bytes = dist.exchange_bytes
                res.dist_cap_escalations = dist.cap_escalations
        return res

    # ---- degraded CPU tier ------------------------------------------------
    def _execute_degraded(self, plan, inputs, schemas, results, metrics,
                          start: int, t_plan0: float, mode: str,
                          first_metric: Optional[OperatorMetrics] = None,
                          carry_retries: int = 0,
                          carry_backoff_ms: float = 0.0,
                          attempts: int = 1,
                          caps: Optional[Dict[str, int]] = None) -> PlanResult:
        """Finish the plan on the CPU backend tier after a breaker trip.

        Completed operator outputs are salvaged through host memory onto
        the CPU backend; the remaining nodes re-execute eagerly with ALL
        faultinj interception suppressed (`faultinj.suppressed()` — the
        CPU tier does not touch the quarantined device, so neither the op
        shims, the MemoryBudget shims, nor the poisoned-device fail-fast
        may fire here) and no plan-level injection points. If the salvage
        itself fails (device buffers already lost), the whole plan re-runs
        from the scans. Admission still applies — degraded work is
        budgeted like any other."""
        from .. import faultinj
        from ..runtime.admission import operand_nbytes
        self.health.note_degraded_plan()
        cpu = _cpu_device()
        ctx = (jax.default_device(cpu) if cpu is not None
               else contextlib.nullcontext())
        with faultinj.suppressed(), ctx:
            try:
                cpu_results = {k: _table_to_cpu(t, cpu)
                               for k, t in results.items()}
                cpu_inputs = {k: _table_to_cpu(t, cpu)
                              for k, t in inputs.items()}
            except Exception:
                # device buffers unrecoverable: restart from the bound inputs
                # (host-side numpy survives a dead device; device copies may
                # not — re-binding is the caller's contract then). The
                # retries/backoff already paid on the device path survive
                # into the carry so the result still reports them.
                carry_retries += sum(mm.retries for mm in metrics.values())
                carry_backoff_ms += sum(mm.backoff_ms
                                        for mm in metrics.values())
                if first_metric is not None:
                    carry_retries += first_metric.retries
                    carry_backoff_ms += first_metric.backoff_ms
                cpu_results, cpu_inputs = {}, inputs
                metrics = {}
                start = 0
                first_metric = None
            try:
                for i, node in enumerate(plan.nodes[start:], start):
                    childs = [cpu_results[id(c)] for c in node.children]
                    if first_metric is not None and node is plan.nodes[start]:
                        m = first_metric  # keep the failed op's retry record
                    else:
                        m = OperatorMetrics(label=node.label, kind=node.kind,
                                            describe=node.describe())
                    m.degraded = True
                    t0 = time.perf_counter()
                    with _op_span(node, i, "degraded") as osp:
                        out = self._exec_eager_node(node, childs, cpu_inputs,
                                                    schemas, m)
                        _say_op(osp, m, node, childs, out)
                        if self.block_per_op:
                            with span("plan.wait", site="degraded_op"):
                                jax.block_until_ready(
                                    [c.data for c in out.columns])
                    m.wall_ms = (time.perf_counter() - t0) * 1e3
                    m.rows_in = sum(t.num_rows for t in childs)
                    m.rows_out = out.num_rows
                    m.bytes_out = operand_nbytes(out)
                    metrics[node.label] = m
                    cpu_results[id(node)] = out
            except BaseException as err:
                # the debuggability contract holds on THIS tier too: a
                # failed degraded plan still surfaces what completed
                try:
                    err.plan_metrics = dict(metrics)
                except Exception:
                    pass
                raise
        with span("plan.result"):
            wall = (time.perf_counter() - t_plan0) * 1e3
            return PlanResult(
                plan, cpu_results[id(plan.root)], None, metrics,
                mode, wall, degraded=True,
                attempts=attempts, caps=caps,
                retries=carry_retries + sum(
                    mm.retries for mm in metrics.values()),
                breaker=self._breaker_snapshot(),
                backoff_ms=carry_backoff_ms + sum(
                    mm.backoff_ms for mm in metrics.values()))

    # ---- streaming prefix (docs/io.md) ------------------------------------
    @staticmethod
    def _stream_chains(plan, inputs) -> Dict[int, List[PlanNode]]:
        """id(tail) -> [Scan, op, ...] streamable prefixes. A chain starts
        at a Scan bound to a streaming source and extends while the node
        has exactly ONE consumer that is a row-wise Filter/Project/
        FusedSelect (no scalar aggregates — those reduce over the whole
        relation); it may terminate INTO a HashAggregate whose ops
        decompose exactly (sum/count/min/max/size over non-float inputs —
        fp partial sums are not reorder-exact — and over no decimal
        column: its sum has another type than its input). Everything else is the
        concat boundary: the tail materializes one Table and the rest of
        the plan proceeds normally."""
        from .expr import decimal_type, has_scalar_agg
        parents: Dict[int, List[PlanNode]] = {}
        for n in plan.nodes:
            for c in n.children:
                parents.setdefault(id(c), []).append(n)
        chains: Dict[int, List[PlanNode]] = {}
        for scan in plan.scans:
            src = inputs.get(scan.source)
            if src is None or isinstance(src, Table) or \
                    not getattr(src, "is_streaming_source", False):
                continue
            # name -> DType down the chain (None: not known), for the
            # terminal aggregate's one question: does it read a decimal
            types = dict(getattr(src, "column_dtypes", None) or {})
            types.update(scan.types or ())
            chain = [scan]
            node: PlanNode = scan
            while True:
                ps = parents.get(id(node), [])
                if len(ps) != 1:
                    break
                p = ps[0]
                if isinstance(p, Filter) and \
                        not has_scalar_agg(p.predicate):
                    chain.append(p)
                    node = p
                    continue
                if isinstance(p, (Project, FusedSelect)) and not (
                        isinstance(p, FusedSelect)
                        and has_scalar_agg(p.predicate)) and not any(
                            has_scalar_agg(e) for _, e in p.exprs):
                    try:
                        types = {n: (types.get(e.name)
                                     if isinstance(e, ColumnRef)
                                     else decimal_type(e, types.get))
                                 for n, e in p.exprs}
                    except TypeError:
                        break   # not lowered: the whole-table path says so
                    chain.append(p)
                    node = p
                    continue
                # a decimal aggregate widens its type (Sum: p + 10), so a
                # partial's merge is not the aggregate again: it runs whole
                # over the chain's concatenated tail
                if (isinstance(p, HashAggregate)
                        and all(o in _STREAM_AGG_MERGE
                                for _, o, _ in p.aggs)
                        and not _input_has_floats(src)
                        and not any(
                            o != "size" and types.get(c) is not None
                            and types[c].is_decimal for c, o, _ in p.aggs)):
                    chain.append(p)     # terminal: partial accumulation
                break
            if len(chain) > 1:
                chains[id(chain[-1])] = chain
        return chains

    def _stream_op(self, node, idx: int, t: Table, inputs, schemas,
                   m: OperatorMetrics, fn=None) -> Table:
        """One chain operator over one chunk, with the same per-op fault
        policy as the materialized path (backoff-paced retries; a breaker
        trip raises _StreamBreaker so the caller can degrade)."""
        attempt = 0
        while True:
            t0 = time.perf_counter()
            try:
                with _op_span(node, idx) as osp:
                    self._faultinj_point(node)
                    out = (fn(t) if fn is not None else
                           self._exec_eager_node(node, [t], inputs,
                                                 schemas, m))
                    _say_op(osp, m, node, [t], out)
                break
            except _fault_surface() as err:
                if self._handle_fault(err, node.label, attempt, m):
                    attempt += 1
                    continue
                raise _StreamBreaker(err, m.retries, m.backoff_ms)
        if attempt:
            self.health.record_success(node.label)
        m.wall_ms = (m.wall_ms or 0.0) + (time.perf_counter() - t0) * 1e3
        m.rows_in += t.num_rows
        m.rows_out += out.num_rows
        return out

    def _exec_stream_chain(self, chain, inputs, schemas,
                           metrics: Dict[str, OperatorMetrics],
                           node_index: Dict[int, int]) -> Table:
        """Run one streamable prefix morsel-at-a-time: row-group pruning at
        the scan, bounded host prefetch decoding chunk N+1 while chunk N
        executes, per-chunk Filter/Project/FusedSelect, and partial
        HashAggregate accumulation merged exactly at the end. Fills
        `metrics` for every chain node; returns the tail's Table."""
        from .. import config
        from .optimizer import pruning_conjuncts
        from ..runtime.admission import operand_nbytes
        ops = _ops()
        scan = chain[0]
        src = inputs[scan.source]
        ms = {n.label: OperatorMetrics(label=n.label, kind=n.kind,
                                       describe=n.describe())
              for n in chain}
        sm = ms[scan.label]
        columns = (list(scan.projection) if scan.projection is not None
                   else None)
        conjuncts = (pruning_conjuncts(scan.predicate)
                     if scan.predicate is not None else [])
        kept, pruned, skipped = src.select_groups(conjuncts, columns)
        sm.io_row_groups_total = src.num_row_groups
        sm.io_row_groups_pruned = pruned
        sm.io_bytes_skipped = skipped
        agg = chain[-1] if isinstance(chain[-1], HashAggregate) else None
        body = chain[1:-1] if agg is not None else chain[1:]
        chunk_rows = src.chunk_rows or config.io_chunk_rows() or None
        if chunk_rows is None:
            # adaptive morsel sizing (plan/stats.py, docs/adaptive.md):
            # with no explicit bound, size chunks from this scan's
            # OBSERVED decode throughput — the stream's exact two-phase
            # merge makes the result chunking-invariant, so this only
            # changes pacing, never bytes. Explicit knobs always win.
            from . import stats as stats_mod
            store = stats_mod.active_store()
            if store is not None:
                from .optimizer import subtree_fingerprints
                # a Scan is a leaf: hashing it alone yields the same
                # fingerprint record_result stored, without re-hashing
                # the whole plan on the streaming hot path
                scan_fp = subtree_fingerprints(scan)[id(scan)]
                chunk_rows = store.suggest_chunk_rows(
                    jax.default_backend(), scan_fp) or None
        depth = config.io_prefetch()
        gen = src.chunks(columns=columns, row_groups=kept,
                         chunk_rows=chunk_rows)
        feed = _ChunkPrefetcher(gen, depth) if depth > 0 else _SyncFeed(gen)
        parts: List[Table] = []         # tail outputs (or agg partials)
        empty_t: Optional[Table] = None
        proc_intervals = []
        try:
            while True:
                chunk = feed.get()
                if chunk is None:
                    break
                t0p = time.perf_counter()
                sm.rows_out += chunk.num_rows
                sm.bytes_out += operand_nbytes(chunk)
                t = scan.typed(chunk)
                for node in body:
                    t = self._stream_op(node, node_index[id(node)], t,
                                        inputs, schemas, ms[node.label])
                    ms[node.label].bytes_out += operand_nbytes(t)
                if agg is not None:
                    if t.num_rows == 0:
                        # fully-filtered morsel: contributes nothing, and a
                        # keyless min/max over a ZERO-ROW frame would raise
                        # where the table-bound plan (reducing over the
                        # whole non-empty relation) succeeds — skip it,
                        # keeping one empty frame for the all-empty case
                        empty_t = t
                        proc_intervals.append((t0p, time.perf_counter()))
                        continue
                    t = self._stream_op(
                        agg, node_index[id(agg)], t, inputs, schemas,
                        ms[agg.label],
                        fn=lambda tt: self._stream_partial_agg(agg, tt,
                                                               schemas))
                parts.append(t)
                if self.block_per_op:
                    with span("plan.wait", site="stream_chunk"):
                        jax.block_until_ready([c.data for c in t.columns])
                proc_intervals.append((t0p, time.perf_counter()))
        finally:
            feed.close()
        sm.io_decode_ms = feed.decode_ms
        sm.io_overlap_ms = _interval_overlap_ms(feed.decode_intervals,
                                                proc_intervals)
        sm.wall_ms = feed.decode_ms     # scan wall = host decode
        # concatenate ONLY at the first non-streamable boundary
        tail = chain[-1]
        tm = ms[tail.label]
        t0 = time.perf_counter()
        if agg is not None:
            if not parts:
                # every morsel filtered to zero rows: aggregate the empty
                # frame once — identical semantics (including any keyless
                # min/max error) to the table-bound plan over an empty
                # filtered relation
                parts = [self._stream_op(
                    agg, node_index[id(agg)], empty_t, inputs, schemas,
                    ms[agg.label],
                    fn=lambda tt: self._stream_partial_agg(agg, tt,
                                                           schemas))]
            out = self._finalize_stream_agg(agg, parts, schemas)
            tm.rows_out = out.num_rows  # partial rows were internal
        else:
            out = parts[0] if len(parts) == 1 else ops.concat_tables(parts)
        if self.block_per_op:
            with span("plan.wait", site="stream_tail"):
                jax.block_until_ready([c.data for c in out.columns])
        tm.wall_ms = (tm.wall_ms or 0.0) + (time.perf_counter() - t0) * 1e3
        tm.bytes_out = operand_nbytes(out)
        for n in chain:
            metrics[n.label] = ms[n.label]
        return out

    def _stream_partial_agg(self, node: HashAggregate, t: Table,
                            schemas) -> Table:
        """Per-chunk partial aggregation (named like the final schema, so
        the merge groups on the output columns)."""
        ops = _ops()
        if not node.keys:
            return self._global_aggregate(t, node)
        agg = ops.groupby_aggregate(t, list(node.keys),
                                    [(c, o) for c, o, _ in node.aggs])
        return Table(list(agg.columns), names=schemas[id(node)])

    def _finalize_stream_agg(self, node: HashAggregate,
                             partials: List[Table], schemas) -> Table:
        """Exact merge of per-chunk partials: counts sum, sums sum, min/max
        re-reduce — the same two-phase shape as the distributed tier, over
        chunks instead of mesh peers. The sort-based groupby kernel's
        key-ordered output makes the merged result row-identical to the
        single-pass aggregate."""
        ops = _ops()
        cat = (partials[0] if len(partials) == 1
               else ops.concat_tables(partials))
        merged_aggs = tuple((out, _STREAM_AGG_MERGE[o], out)
                            for _, o, out in node.aggs)
        if not node.keys:
            merge_node = HashAggregate(node.child, (), merged_aggs)
            return self._global_aggregate(cat, merge_node)
        agg = ops.groupby_aggregate(cat, list(node.keys),
                                    [(c, o) for c, o, _ in merged_aggs])
        return Table(list(agg.columns), names=schemas[id(node)])

    def _materialize_scan(self, node: Scan, src,
                          m: Optional[OperatorMetrics]) -> Table:
        """Source-bound Scan outside a streamable prefix (shared scans,
        join inputs, the capped tier): one admitted read, still with
        selective decode (projection columns only) and stats-driven
        row-group pruning."""
        from .optimizer import pruning_conjuncts
        columns = (list(node.projection) if node.projection is not None
                   else None)
        conjuncts = (pruning_conjuncts(node.predicate)
                     if node.predicate is not None else [])
        kept, pruned, skipped = src.select_groups(conjuncts, columns)
        t0 = time.perf_counter()
        t = src.read_all(columns=columns, row_groups=kept)
        if m is not None:
            m.io_row_groups_total = src.num_row_groups
            m.io_row_groups_pruned = pruned
            m.io_bytes_skipped = skipped
            m.io_decode_ms += (time.perf_counter() - t0) * 1e3
        return node.typed(t)

    @staticmethod
    def _kernel_choice(op: str, sig, m: Optional[OperatorMetrics] = None,
                       pin_degraded: bool = True):
        """Resolve one registry dispatch (ops/registry.py, docs/kernels.md)
        and stamp the choice on the operator's metrics. On the degraded CPU
        tier the backend is pinned to "cpu" (default_backend still reports
        the quarantined platform under jax.default_device): auto-selection
        must not hand work back to the device the breaker just isolated."""
        from ..ops.registry import REGISTRY
        backend = "cpu" if (pin_degraded and m is not None
                            and m.degraded) else None
        choice = REGISTRY.select(op, sig, backend=backend)
        if m is not None:
            m.kernel = choice.label
            if sig is not None:
                # side-channel for the stats store (plan/stats.py): the
                # op + signature this metric's wall time was measured
                # under, consumed by record_result to feed the registry
                # tie-break. A dynamic attribute, not a dataclass field —
                # profile()/to_dict() rows must not grow a non-JSON blob.
                m._kernel_sig = (op, sig)
        return choice

    def _eager_join(self, node: HashJoin, lt: Table, rt: Table,
                    m: OperatorMetrics) -> Table:
        """The eager tiers' join, in an `ops.join` span that holds its
        device work (the maps, the output columns and, where the executor
        blocks per operator, the wait for them). An inner join gathers
        its output columns through its maps, or carries them; an outer
        join's are made the way its own counts say
        (`ops.outer_join_columns`: a map that is the identity, nearly
        all -1 or a permutation is not gathered through; `m.left_out`,
        `m.right_out`)."""
        ops = _ops()
        outer_left, outer_right = nullable_sides(node.how)
        rows_left, rows_right = lt.num_rows, rt.num_rows
        lkeys = [lt[k] for k in node.left_keys]
        rkeys = [rt[k] for k in node.right_keys]
        from ..ops import join_pallas
        choice = self._kernel_choice(
            "hash_join",
            join_pallas.make_signature(lkeys, rkeys, node.how, "eager"), m)
        from ..ops.join_lookup import KERNEL_LABEL, lookup_counts
        with span("ops.join", how=node.how, rows_left=rows_left,
                  rows_right=rows_right) as sp:
            matched = unmatched = unmatched_right = 0
            carried = None
            with lookup_counts() as looked:
                if outer_right:
                    # (the join asks its counts how the right table's
                    # columns will be made, and builds the map for that)
                    parts = ops.outer_join_parts(node.how, lkeys, rkeys, rt)
                    matched, unmatched, unmatched_right = parts[4:]
                elif node.how != "inner":
                    keep = (ops.left_semi_join(lkeys, rkeys)
                            if node.how == "left_semi"
                            else ops.left_anti_join(lkeys, rkeys))
                elif not choice.fallback:
                    lm, rm = choice.fn(lkeys, rkeys)
                else:
                    # (where many rows pass a small right side the left
                    # side's columns ride the survivors' one sort; a key
                    # column's mask need not: no pair holds a null key)
                    lm, rm, carried = ops.inner_join_carrying(
                        lkeys, rkeys,
                        [c.with_validity(None) if n in node.left_keys else c
                         for n, c in zip(lt.names, lt.columns)])
            if looked:      # the small-side path answered (ops/join.py)
                m.kernel = KERNEL_LABEL
                m.lookup_compares = sum(a * b for a, b in looked)
                # its wall is no timing of a registered hash_join kernel
                m.__dict__.pop("_kernel_sig", None)
            if outer_right:
                cols, made = ops.outer_join_columns(lt, rt, parts)
                out = Table(cols, names=list(lt.names) + list(rt.names))
                for name, value in made.items():
                    setattr(m, name, value)
                sp.set_metadata(left_out=m.left_out, right_out=m.right_out)
            elif node.how == "inner":
                matched = lm.length
                if carried is not None:
                    out = self._carried_join(node, lt, rt, carried, rm)
                else:
                    out = Table(
                        list(ops.take_table(
                            lt, lm.data, _has_negative=False).columns) +
                        list(ops.take_table(
                            rt, rm.data, _has_negative=False).columns),
                        names=list(lt.names) + list(rt.names))
            else:
                out = ops.take_table(lt, keep.data, _has_negative=False)
                if node.how == "left_semi":
                    matched = keep.length
                else:
                    unmatched = keep.length
            m.unmatched_rows = unmatched if outer_right else 0
            m.unmatched_right_rows = unmatched_right
            sp.set_metadata(matched=matched, unmatched=unmatched,
                            kernel=_span_text(m.kernel))
            if outer_left:
                sp.set_metadata(unmatched_right=unmatched_right)
            if self.block_per_op:
                with span("plan.wait", site="join"):
                    jax.block_until_ready([c.data for c in out.columns])
        return out

    @staticmethod
    def _carried_join(node: HashJoin, lt: Table, rt: Table, carried,
                      rm) -> Table:
        """An inner join's output where the join moved the left side's
        columns itself (`ops.inner_join_carrying`): those, and the right
        side's columns, a key column as the left key it equals row for
        row (an inner join's pairs hold no null key, and the path takes
        keys of one type), any other gathered by the right map."""
        ops = _ops()
        left = dict(zip(lt.names, carried))
        key_of = dict(zip(node.right_keys, node.left_keys))
        right = [left[key_of[n]].with_validity(None) if n in key_of
                 else ops.take(rt[n], rm.data, _has_negative=False)
                 for n in rt.names]
        return Table(list(carried) + right,
                     names=list(lt.names) + list(rt.names))

    @staticmethod
    def _compact(t: Table, mask, m: OperatorMetrics) -> Table:
        """The rows of `t` that pass `mask`; `m.compact` says how they
        moved (ops/gather.py)."""
        from ..ops.gather import compactions
        with compactions.collect() as moved:
            out = _ops().apply_boolean_mask(t, mask)
        m.compact = moved[-1][0]
        return out

    @staticmethod
    def _proven(t: Table, predicate) -> Table:
        """`t`, the rows a filter on `predicate` kept (or, under a cap,
        left alive), without the validity masks of the columns the
        predicate proves not null (plan/expr.py:not_null_columns)."""
        from .expr import not_null_columns
        proven = not_null_columns(predicate)
        if not any(n in proven and c.validity is not None
                   for n, c in zip(t.names, t.columns)):
            return t
        return Table([c.with_validity(None) if n in proven else c
                      for n, c in zip(t.names, t.columns)], t.names,
                     ordered_by=t.ordered_by)

    def _exec_eager_node(self, node, childs: List[Table], inputs, schemas,
                         m: OperatorMetrics) -> Table:
        ops = _ops()
        if isinstance(node, Scan):
            t = inputs[node.source]
            if not isinstance(t, Table):
                # streaming source outside a streamable prefix: materialize
                # (pruned + projected) in one read
                return self._materialize_scan(node, t, m)
            if node.projection is not None:
                # pruned scan: unused columns never enter the plan
                t = t.select(list(node.projection))
            return node.typed(t)
        if isinstance(node, Filter):
            (t,) = childs
            return self._proven(
                self._compact(t, node.predicate.truth(t), m), node.predicate)
        if isinstance(node, FusedSelect):
            # fused Filter+Project: gather ONLY the projection-referenced
            # columns through the mask, then project — one pass, instead of
            # materializing the full filtered child first. The registry
            # (ops/registry.py) may hand the front half to the Pallas
            # predicate+compaction kernel; the XLA mask+gather is the
            # fallback.
            (t,) = childs
            from ..ops import select_pallas
            # one shared definition with make_signature: the supports()
            # gate must describe exactly the columns the kernel is handed
            needed = select_pallas.needed_columns(t, node.exprs)
            choice = self._kernel_choice(
                "fused_select",
                select_pallas.make_signature(t, node.predicate, node.exprs,
                                             "eager"), m)
            if not choice.fallback:
                ft = choice.fn(t, node.predicate, needed)
            else:
                ft = self._compact(t.select(needed),
                                   node.predicate.truth(t), m)
            return self._project(self._proven(ft, node.predicate), node)
        if isinstance(node, Project):
            (t,) = childs
            return self._project(t, node)
        if isinstance(node, HashJoin):
            return self._eager_join(node, *childs, m)
        if isinstance(node, HashAggregate):
            (t,) = childs
            if not node.keys:
                return self._global_aggregate(t, node)
            # dispatch happens inside groupby_aggregate (registry op
            # "groupby"); re-selecting here only stamps the choice. Backend
            # intentionally NOT pinned for the degraded tier: the scan/
            # scatter pick keys on jax.default_backend(), exactly like the
            # kernel itself
            self._kernel_choice("groupby", None, m, pin_degraded=False)
            from ..ops.aggregate import group_keys
            with group_keys.collect() as keyed:
                agg = ops.groupby_aggregate(
                    t, list(node.keys), [(c, o) for c, o, _ in node.aggs])
            m.key_slots_gathered = keyed[-1][1]
            out_names = schemas[id(node)]
            # the kernel says its groups lie in key order: so do these
            return Table(list(agg.columns), names=out_names,
                         ordered_by=out_names[:len(agg.ordered_by)])
        if isinstance(node, Window):
            (t,) = childs
            from ..ops import window as window_ops
            with window_ops.windows.collect() as did:
                out = ops.window_functions(
                    t, node.partition_by, node.order_by, node.ascending,
                    node.functions, node.frame)
            m.kernel = "xla:" + window_ops.KERNEL
            m.window_partitions, m.window_sorted, m.window_key = did[-1]
            return out
        if isinstance(node, Sort):
            (t,) = childs
            return ops.sort_table(t, key_names=list(node.keys),
                                  ascending=list(node.ascending))
        if isinstance(node, TopK):
            (t,) = childs
            from ..ops import topk_pallas
            choice = self._kernel_choice(
                "topk",
                topk_pallas.make_signature(t, node.keys, node.ascending,
                                           node.n, "eager"), m)
            if not choice.fallback:
                return choice.fn(t, list(node.keys), list(node.ascending),
                                 node.n)
            t = ops.sort_table(t, key_names=list(node.keys),
                               ascending=list(node.ascending))
            return ops.slice_table(t, 0, min(node.n, t.num_rows))
        if isinstance(node, Limit):
            (t,) = childs
            return ops.slice_table(t, 0, min(node.n, t.num_rows))
        if isinstance(node, Union):
            return ops.concat_tables(childs)
        if isinstance(node, Exchange):
            # single-chip tier: a no-op distribution marker. With a mesh,
            # the parent operator consumes it (distributed lowering).
            return childs[0]
        raise PlanValidationError(f"no eager lowering for {node.kind}")

    def _project(self, t: Table, node: Project,
                 alive: Optional[jnp.ndarray] = None) -> Table:
        # a bare reference keeps its column (type and validity); any other
        # expression is its value, Spark's result type and its validity
        # (and the order its input says it lies in, as far as the leading
        # columns of that order pass through, under their new names)
        passed = {e.name: n for n, e in reversed(node.exprs)
                  if isinstance(e, ColumnRef)}
        ordered = list(itertools.takewhile(passed.__contains__,
                                           t.ordered_by))
        return Table([t[e.name] if isinstance(e, ColumnRef)
                      else e.column(t, alive) for _, e in node.exprs],
                     names=[n for n, _ in node.exprs],
                     ordered_by=[passed[c] for c in ordered])

    def _global_aggregate(self, t: Table, node: HashAggregate,
                          alive: Optional[jnp.ndarray] = None) -> Table:
        """Keyless (one-row) aggregate; honors `alive` in the capped tier."""
        from ..ops.aggregate import _agg_value_dtype
        cols, names = [], []
        for c, op, out_name in node.aggs:
            if op == "size":
                n_live = (jnp.sum(alive.astype(jnp.int64)) if alive is not None
                          else jnp.asarray(t.num_rows, jnp.int64))
                dt = dtypes.INT64
                val = n_live
            else:
                src = t[c]
                v = src.data
                ok = src.validity
                if alive is not None:
                    ok = alive if ok is None else (ok & alive)
                if op == "count":
                    val = (jnp.sum(ok.astype(jnp.int64)) if ok is not None
                           else jnp.asarray(t.num_rows, jnp.int64))
                    dt = dtypes.INT64
                else:
                    dt = _agg_value_dtype(op, src.dtype)
                    acc = v.astype(dt.storage_dtype())
                    if op == "sum":
                        if ok is not None:
                            acc = jnp.where(ok, acc, 0)
                        val = jnp.sum(acc)
                    else:
                        from .expr import _reduce_identity
                        if ok is not None:
                            acc = jnp.where(ok, acc,
                                            _reduce_identity(op, acc.dtype))
                        val = jnp.min(acc) if op == "min" else jnp.max(acc)
            cols.append(Column(dtype=dt, length=1,
                               data=val[None].astype(dt.storage_dtype())))
            names.append(out_name)
        return Table(cols, names=names)

    # ---- capped tier ------------------------------------------------------
    def _default_caps(self, plan, inputs) -> Dict[str, int]:
        """Initial capacities: the executor's shared caps (defaulted from
        the largest input) plus one per-node entry for each node-level
        override — those ride the SAME escalation dict, so an undersized
        override grows geometrically like everything else instead of
        livelocking through identical attempts. Per-node entries key on
        the toposort INDEX (stable across fingerprint-equal plans, whose
        labels differ), so the caps memo and program cache stay shared
        when the same plan is rebuilt."""
        caps = dict(self.caps)
        max_rows = max((t.num_rows for t in inputs.values()), default=1)
        needs_row = needs_key = False
        for i, n in enumerate(plan.nodes):
            if isinstance(n, HashJoin) and n.how in PAIRING_JOINS:
                if n.row_cap is None:
                    needs_row = True
                else:
                    caps[f"row_cap:{i}"] = n.row_cap
            elif isinstance(n, HashAggregate) and n.keys:
                if n.key_cap is None:
                    needs_key = True
                else:
                    caps[f"key_cap:{i}"] = n.key_cap
        if needs_row:
            caps.setdefault("row_cap", max(max_rows, 1))
        if needs_key:
            caps.setdefault("key_cap", max(max_rows, 1))
        return caps

    @staticmethod
    def _node_cap(caps: Dict[str, int], which: str, idx: int) -> int:
        return caps.get(f"{which}:{idx}") or caps[which]

    @staticmethod
    def _cert_caps(plan, caps, cert):
        """Fold the resource certifier's sound rows-hi bounds
        (analysis/footprint.py) into the capped tier's capacities:

        - STARTING caps tighten to the certified hi where it is below the
          static start (a sound bound can never overflow, so a tighter
          start only shrinks padding and compiles a smaller program —
          per-node `row_cap:<i>`/`key_cap:<i>` entries, which outrank the
          shared keys exactly like authored overrides);
        - the escalation ladder CEILINGS at the certified hi (growing a
          capacity past a proven bound is wasted memory) — per node where
          a per-node entry exists, else on the shared key at the max hi
          over the nodes that fall through to it (an unbounded node
          poisons the shared ceiling, never the clamp safety).

        Returns (caps, ceil) for `auto_retry_overflow(ceil=...)`; the
        ceiling is advisory there — a clamped attempt that still
        overflows drops it (certifier-bug escape hatch)."""
        caps = dict(caps)
        ceil: Dict[str, int] = {}
        shared_hi: Dict[str, Optional[int]] = {"row_cap": 0, "key_cap": 0}
        for i, n in enumerate(plan.nodes):
            if isinstance(n, HashJoin) and n.how in PAIRING_JOINS:
                which = "row_cap"
            elif isinstance(n, HashAggregate) and n.keys:
                which = "key_cap"
            else:
                continue
            b = cert.by_index.get(i)
            hi = None if b is None else b.rows_hi
            key = f"{which}:{i}"
            if key in caps:
                if hi is not None:
                    if hi < caps[key]:
                        caps[key] = hi
                    ceil[key] = max(caps[key], hi)
                continue
            cur = caps.get(which)
            if hi is not None and cur is not None and hi < cur:
                caps[key] = hi
                ceil[key] = hi
            elif shared_hi[which] is not None:
                shared_hi[which] = (None if hi is None
                                    else max(shared_hi[which], hi))
        for which, g in shared_hi.items():
            if g and which in caps:
                ceil[which] = max(g, caps[which])
        return caps, ceil

    def _starting_caps(self, plan, inputs, source_fp, cert):
        """-> (caps the next capped run of `plan` over `inputs` starts
        from, the certified ceilings of its escalation ladder)."""
        # start from the input-derived defaults, floored up by any caps the
        # plan already escalated to: the memo must never UNDERSIZE a run on
        # larger inputs than it was learned on (only skip re-learning)
        caps = self._default_caps(plan, inputs)
        fp = plan.fingerprint        # canonical structural hash: equivalent
        #                              plans built independently share the
        #                              caps memo and compiled programs
        for k, v in (self._caps_memo.get(fp) or {}).items():
            caps[k] = max(caps.get(k, 0), v)
        # adaptive cap seeding (plan/stats.py, docs/adaptive.md): floor
        # the starting capacities at the observed high-water marks from
        # prior executions of this authored plan, so a repeat fingerprint
        # compiles once instead of re-climbing the escalation ladder —
        # the per-executor memo above, promoted across executor
        # instances (and processes, with persistence on). Same
        # floor-only contract: caps are STARTING capacities the overflow
        # ladder would have grown anyway, so seeding can never change
        # results, only skip retries. Keyed by the backend about to run:
        # degraded-run stats recorded under "cpu" never seed a device.
        from . import stats as stats_mod
        store = stats_mod.active_store()
        if store is not None and source_fp is not None:
            with span("plan.stats"):
                observed = store.observed_caps(jax.default_backend(),
                                               source_fp, executed_fp=fp)
            for k, v in observed.items():
                caps[k] = max(caps.get(k, 0), v)
        # certified cap bounds (analysis/footprint.py, docs/adaptive.md):
        # with adaptivity on, cold starting caps tighten to the sound
        # hi-bound and the escalation ladder ceilings at it — the warm
        # observed high-water (merged above) must always sit at or below
        # the certified bound; that inequality IS the certifier's
        # soundness check (fuzz property 5). Stats off stays
        # byte-identical static: the certifier then only stamps results.
        from .. import config
        cert_ceil: Dict[str, int] = {}
        if store is not None and cert is not None and config.cert_seed():
            caps, cert_ceil = self._cert_caps(plan, caps, cert)
        return caps, cert_ceil

    def _execute_capped(self, plan, inputs, schemas,
                        source_fp=None, cert=None) -> PlanResult:
        from ..parallel.autoretry import auto_retry_overflow
        # the capped tier traces ONE whole-plan program over concrete
        # shapes, so streaming sources materialize first — still through
        # the pruned/projected read, so the decode savings carry over
        scan_io: Dict[str, OperatorMetrics] = {}
        if any(not isinstance(t, Table) for t in inputs.values()):
            inputs = dict(inputs)
            # one Scan per source is a Plan invariant (Plan.__init__
            # rejects duplicate sources), so materializing per NAME with
            # that scan's projection/predicate loses nothing
            by_source = {n.source: n for n in plan.nodes
                         if isinstance(n, Scan)}
            for name, v in list(inputs.items()):
                if isinstance(v, Table):
                    continue
                node = by_source.get(name)
                holder = OperatorMetrics(label=name, kind="Scan")
                if node is not None:
                    inputs[name] = self._materialize_scan(node, v, holder)
                else:
                    inputs[name] = v.read_all()
                scan_io[name] = holder
        with span("plan.caps"):
            caps, cert_ceil = self._starting_caps(plan, inputs, source_fp,
                                                  cert)
        fp = plan.fingerprint
        t0 = time.perf_counter()
        attempts = 0
        cache_hits = 0
        bytes_map: Dict[int, int] = {}
        kernel_map: Dict[int, str] = {}
        last_caps = dict(caps)
        self.health.start_plan_attempt()
        if self.degrade != "off" and not self.health.admit():
            return self._execute_degraded(plan, inputs, schemas, {}, {},
                                          start=0, t_plan0=t0, mode="capped")

        tried: List[Tuple] = []     # the program cache's key, per attempt

        def run(**caps_now):
            nonlocal attempts, cache_hits
            attempts += 1
            last_caps.clear()
            last_caps.update(caps_now)
            # one pass over the program: an escalation shows as a second
            # plan.attempt under the same plan.run
            with bracket("plan.attempt", attempt=attempts) as sp:
                with span("plan.program"):
                    # shapes AND names in the key: jax retraces per input
                    # shape anyway, a per-shape entry keeps each bytes_map
                    # true to ITS trace, and the names guard
                    # fingerprint-shared undeclared scans bound to
                    # differently-named tables
                    tried.append(self._capped_key(plan, caps_now,
                                                  _input_key(inputs)))
                    # plan-level faultinj surface: fires every attempt,
                    # including cache-hit runs where the op-level shims
                    # never re-trace
                    for node in plan.nodes:
                        self._faultinj_point(node)
                    fn, bm, km, hit = self._jitted_capped(
                        plan, schemas, caps_now, _input_key(inputs))
                sp.set_metadata(hit=int(hit))
                cache_hits += hit
                # flattening the tables and enqueueing; on a miss the
                # trace and the compile too (the bracket's `lowerings`)
                with span("plan.launch", hit=int(hit)):
                    out = fn(dict(inputs))
            bytes_map.clear()
            bytes_map.update(bm)    # bm fills during the first trace
            kernel_map.clear()
            kernel_map.update(km)
            return out

        retries = 0
        backoff_total = 0.0
        plan_metric = OperatorMetrics(label="plan", kind="Plan")
        while True:
            try:
                (table, valid, counts, overflow), final_caps = \
                    auto_retry_overflow(run, caps, self.max_cap_attempts,
                                        ceil=cert_ceil)
                if retries:
                    self.health.record_success("plan")
                self._caps_memo[fp] = dict(final_caps)
                # a program that overflowed never runs for this plan
                # again (the memo floors every later start at the caps
                # that held), and an executable's code lies in HBM beside
                # the data: let it go with its cache entry
                for key in tried[:-1]:
                    if key != tried[-1]:
                        self._jit_cache.discard(key)
                break
            except _fault_surface() as err:
                # failures are plan-granular here (one XLA program), so the
                # sticky window keys on the plan attempt, not an operator
                if self._handle_fault(err, "plan", retries, plan_metric):
                    retries += 1
                    backoff_total = plan_metric.backoff_ms
                    # resume from the escalated capacities, not the
                    # originals: growth already paid for must survive
                    caps = dict(last_caps)
                    continue
                if self.degrade == "off":
                    raise
                return self._execute_degraded(
                    plan, inputs, schemas, {}, {}, start=0, t_plan0=t0,
                    mode="capped", carry_retries=plan_metric.retries,
                    carry_backoff_ms=plan_metric.backoff_ms,
                    # escalation history survives the trip: the device path
                    # DID run `attempts` times over these (grown) caps
                    attempts=attempts, caps=dict(last_caps))
        with span("plan.wait", site="capped"):
            jax.block_until_ready(valid)
        wall = (time.perf_counter() - t0) * 1e3
        # the operators' row counts: two device scalars an operator, each
        # its own transfer
        with span("plan.readback", scalars=2 * len(counts)):
            counts_np = {k: (int(a), int(b))
                         for k, (a, b) in zip(
                             counts.keys(),
                             np.asarray(list(counts.values()),
                                        dtype=np.int64))}
        # the tier's epilogue: a metrics row an operator from the counts
        # read back, and the result's counters
        with span("plan.result"):
            metrics: Dict[str, OperatorMetrics] = {}
            # cap growths only: each of the (retries+1) auto_retry runs gets a
            # free first attempt that is not an escalation
            escal = max(0, attempts - (retries + 1))
            for i, node in enumerate(plan.nodes):
                # counts/bytes key on the toposort INDEX, not the label: a
                # fingerprint-shared program was traced over an equivalent
                # plan whose node labels differ, but its toposort lines up
                # 1:1
                rows_in, rows_out = counts_np[i]
                kernel = kernel_map.get(i, "")
                if _JOIN_UNIQUE - 2 * i in counts_np:
                    kernel += ("/unique" if counts_np[_JOIN_UNIQUE - 2 * i][0]
                               else "/expand")
                uses_cap = (isinstance(node, HashJoin)
                            and node.how in PAIRING_JOINS) \
                    or (isinstance(node, HashAggregate) and node.keys)
                # retries are plan-granular in this tier (one XLA program)
                # and live on PlanResult.retries — copying them onto every
                # row would make per-op aggregation overcount N-fold
                metrics[node.label] = OperatorMetrics(
                    label=node.label, kind=node.kind, describe=node.describe(),
                    rows_in=rows_in, rows_out=rows_out,
                    bytes_out=bytes_map.get(i, 0),
                    escalations=escal if uses_cap else 0,
                    kernel=kernel,
                    key_slots_gathered=bytes_map.get(_KEY_SLOTS - i, 0))
                if isinstance(node, HashJoin) and nullable_sides(node.how)[1]:
                    metrics[node.label].unmatched_rows = int(
                        counts_np[_JOIN_UNIQUE - 2 * i][1])
                if isinstance(node, HashJoin) and node.how == "full_outer":
                    metrics[node.label].unmatched_right_rows = int(
                        counts_np[len(plan.nodes) + i][0])
                if isinstance(node, Scan) and node.source in scan_io:
                    io = scan_io[node.source]
                    mm = metrics[node.label]
                    mm.io_row_groups_total = io.io_row_groups_total
                    mm.io_row_groups_pruned = io.io_row_groups_pruned
                    mm.io_bytes_skipped = io.io_bytes_skipped
                    mm.io_decode_ms = io.io_decode_ms
            res = PlanResult(plan, table, valid, metrics, "capped", wall,
                             attempts=attempts, caps=final_caps,
                             retries=retries,
                             breaker=self._breaker_snapshot(),
                             backoff_ms=backoff_total,
                             jit_cache_hits=cache_hits)
            res.decimal_overflow_rows = counts_np[_DECIMAL_OVERFLOW][0]
            tails = [flag for k, (flag, _) in counts_np.items()
                     if k <= _JOIN_UNIQUE and k % 2 == 0]
            res.unique_joins = sum(tails)
            res.expand_joins = len(tails) - res.unique_joins
            for k, (slots, frames) in counts_np.items():
                if k <= _JOIN_EXPAND and k % 2:
                    res.expand_slots += slots
                    res.expand_cap_slots += frames
            from ..ops.gather import live_slots
            for i, node in enumerate(plan.nodes):
                if isinstance(node, HashJoin) \
                        and node.how in PAIRING_JOINS:
                    cap = self._node_cap(final_caps, "row_cap", i)
                    # (a full join's right rows without a match lie past
                    # the capped frame: concatenated, not gathered)
                    res.gather_slots += live_slots(
                        counts_np[i][1]
                        - metrics[node.label].unmatched_right_rows, cap)
                    res.cap_slots += cap
        return res

    def _capped_key(self, plan, caps, input_key) -> Tuple:
        # the canonical FINGERPRINT is the key: structurally equivalent
        # plans built independently (same kinds/exprs/schemas/DAG shape)
        # share one compiled program instead of re-tracing. The backend +
        # kernel-override knob join the key: registry selection happens at
        # trace time, so a program compiled under one kernel choice must
        # never serve another (docs/kernels.md).
        from .. import config
        from . import stats as stats_mod
        store = stats_mod.active_store()
        # the stats store's kernel tie-break resolves at trace time, so
        # its epoch (bumped only when a recorded timing changes some
        # signature's kernel ORDERING) joins the key: compiled programs
        # stay shared across runs whose picks cannot have changed, and
        # never alias across a demotion flip (docs/adaptive.md)
        kern_key = (jax.default_backend(),
                    tuple(sorted(config.kernel_overrides().items())),
                    None if store is None else (store.uid,
                                                store.kernel_epoch))
        return (plan.fingerprint, tuple(sorted(caps.items())), input_key,
                kern_key)

    def _jitted_capped(self, plan, schemas, caps, input_key):
        # -> (jitted_fn, bytes_map, kernel_map, cache_hit)
        key = self._capped_key(plan, caps, input_key)
        hit = self._jit_cache.get(key)
        if hit is not None:
            return hit[0], hit[1], hit[2], True
        bytes_map: Dict[int, int] = {}
        kernel_map: Dict[int, str] = {}

        def capped_plan(tables: Dict[str, Table]):
            # the name is the module's (`jit_capped_plan`) in a profile
            return self._run_capped(plan, schemas, caps, tables, bytes_map,
                                    kernel_map)

        jitted = jax.jit(capped_plan)
        self._jit_cache[key] = (jitted, bytes_map, kernel_map)
        return jitted, bytes_map, kernel_map, False

    def _run_capped(self, plan, schemas, caps, tables, bytes_map,
                    kernel_map):
        from ..runtime.admission import operand_nbytes
        rels: Dict[int, _CappedRel] = {}
        # counts/bytes key on the toposort index: stable across
        # fingerprint-equal plans, whose labels differ (see _jitted_capped)
        from ..ops.aggregate import group_keys
        from ..ops.decimal_utils import overflow_counts
        counts: Dict[int, Tuple] = {}
        overflow = jnp.asarray(False)
        with overflow_counts() as nulled:
            for i, node in enumerate(plan.nodes):
                childs = [rels[id(c)] for c in node.children]
                # the operator's name inside the program, which
                # device_op_owners reads back
                with jax.named_scope(_scope_name(i, node)), \
                        group_keys.collect() as keyed:
                    rel, ovf = self._exec_capped_node(
                        node, i, childs, tables, schemas, caps, kernel_map)
                    if keyed:       # a keyed aggregate: what it gathers
                        bytes_map[_KEY_SLOTS - i] = keyed[-1][1]
                    if ovf is not None:
                        overflow = overflow | ovf
                    if rel.unique is not None:
                        counts[_JOIN_UNIQUE - 2 * i] = (
                            rel.unique.astype(jnp.int64), jnp.int64(0))
                    elif rel.unmatched is not None:
                        # an outer join always expands: flag 0, and its
                        # null-extended rows beside it
                        counts[_JOIN_UNIQUE - 2 * i] = (jnp.int64(0),
                                                        rel.unmatched)
                    if rel.unmatched_right is not None:
                        # past every operator's index: a `full_outer`
                        # join's null-extended right rows
                        counts[len(plan.nodes) + i] = (rel.unmatched_right,
                                                       jnp.int64(0))
                    if rel.expanded is not None:
                        counts[_JOIN_EXPAND - 2 * i] = rel.expanded
                    bytes_map[i] = operand_nbytes(rel.table)
                    rows_in = sum((jnp.sum(c.alive.astype(jnp.int64))
                                   for c in childs), start=jnp.int64(0))
                    counts[i] = (rows_in,
                                 jnp.sum(rel.alive.astype(jnp.int64)))
                rels[id(node)] = rel
        # beside the row counts, under an index no operator has: the rows
        # and groups the program's decimal kernels nulled by overflow
        counts[_DECIMAL_OVERFLOW] = (sum(nulled, start=jnp.int64(0)),
                                     jnp.int64(0))
        root = rels[id(plan.root)]
        return root.table, root.alive, counts, overflow

    def _exec_capped_node(self, node, idx: int, childs: List[_CappedRel],
                          tables, schemas, caps, kernel_map):
        ops = _ops()

        def pick(op: str, sig):
            # registry dispatch at trace time; choices key on the toposort
            # index (like counts/bytes) so fingerprint-shared programs stamp
            # consistently
            from ..ops.registry import REGISTRY
            choice = REGISTRY.select(op, sig)
            kernel_map[idx] = choice.label
            return choice
        if isinstance(node, Scan):
            t = tables[node.source]
            if node.projection is not None:
                t = t.select(list(node.projection))
            t = node.typed(t)
            return _CappedRel(t, jnp.ones((t.num_rows,), bool)), None
        if isinstance(node, Filter):
            (c,) = childs
            # predicate as a mask AND — the jit tier's filter idiom: no
            # compaction, dead rows stay and stay dead
            mask = node.predicate.truth(c.table, c.alive)
            return _CappedRel(self._proven(c.table, node.predicate),
                              c.alive & mask), None
        if isinstance(node, FusedSelect):
            # filter-then-project over the padded frame: the predicate ANDs
            # into alive and the projection evaluates under the new mask
            # (scalar aggregates reduce over the filtered live rows). No
            # compaction happens here, so there is no Pallas form — the
            # registry consult documents the decline (tier="capped")
            (c,) = childs
            from ..ops import select_pallas
            pick("fused_select",
                 select_pallas.make_signature(c.table, node.predicate,
                                              node.exprs, "capped"))
            mask = node.predicate.truth(c.table, c.alive)
            alive = c.alive & mask
            return _CappedRel(self._project(
                self._proven(c.table, node.predicate), node, alive),
                alive), None
        if isinstance(node, Project):
            (c,) = childs
            return _CappedRel(self._project(c.table, node, c.alive),
                              c.alive), None
        if isinstance(node, HashJoin):
            l, r = childs
            # an outer join's empty side: one dead row of nulls, so that a
            # gather has a row to read
            outer_left, outer_right = nullable_sides(node.how)
            if outer_right and not r.table.num_rows:
                r = _CappedRel(_null_row(r.table), jnp.zeros((1,), bool))
            if outer_left and not l.table.num_rows:
                l = _CappedRel(_null_row(l.table), jnp.zeros((1,), bool))
            lkeys = [l.table[k] for k in node.left_keys]
            rkeys = [r.table[k] for k in node.right_keys]
            from ..ops import join_pallas
            choice = pick("hash_join",
                          join_pallas.make_signature(lkeys, rkeys, node.how,
                                                     "capped"))
            if node.how in PAIRING_JOINS:
                row_cap = self._node_cap(caps, "row_cap", idx)
                unique = rvalid = None
                if outer_right:
                    lm, rm, rvalid, valid, ovf = ops.left_join_capped(
                        lkeys, rkeys, row_cap=row_cap, lalive=l.alive,
                        ralive=r.alive)
                    # `lo - starts`, the match counts, `rorder`
                    planes = 3
                elif not choice.fallback:
                    lm, rm, valid, ovf = join_pallas.inner_join_capped_pallas(
                        lkeys, rkeys, row_cap=row_cap, lalive=l.alive,
                        ralive=r.alive)
                    planes = join_pallas.emit_planes(lkeys)
                else:
                    lm, rm, valid, ovf, unique = ops.inner_join_capped_tail(
                        lkeys, rkeys, row_cap=row_cap, lalive=l.alive,
                        ralive=r.alive)
                    planes = 3      # the spans' rows, `lo - starts`, `rorder`
                # the live rows are a prefix of the capped frame: gather
                # that prefix, whatever the cap (ops/gather.py:take_live)
                live = jnp.sum(valid.astype(jnp.int32))
                rcols = ops.take_live(r.table.columns, rm, live)
                if rvalid is not None:
                    # a left row without a match: its slot read right row 0
                    # and is null in every column of the right side
                    rcols = [c.with_validity(
                        rvalid if c.validity is None
                        else c.validity & rvalid) for c in rcols]
                cols = ops.take_live(l.table.columns, lm, live) + rcols
                from ..ops.join import expansion_slots
                expanded = expansion_slots(lm, live, l.table.num_rows, planes,
                                           packed=unique is not None)
                if unique is not None:  # the many-to-one tail expands nothing
                    expanded = tuple(jnp.where(unique, 0, x)
                                     for x in expanded)
                t = Table(cols, names=list(l.table.names) +
                          list(r.table.names))
                unmatched = None if rvalid is None else jnp.sum(
                    valid & ~rvalid, dtype=jnp.int64)
                unmatched_right = None
                if outer_left:
                    # the swapped anti pass: the right rows no left row
                    # matched follow the left join's frame as they lie,
                    # alive where unmatched, the left columns null
                    lonely = r.alive & ~ops.semi_join_mask(
                        rkeys, lkeys, lalive=r.alive, ralive=l.alive)
                    nobody = jnp.full((r.table.num_rows,), -1, jnp.int32)
                    t = ops.concat_tables([t, Table(
                        [ops.take(c, nobody, _has_negative=True)
                         for c in l.table.columns]
                        + list(r.table.columns), names=list(t.names))])
                    valid = jnp.concatenate([valid, lonely])
                    unmatched_right = jnp.sum(lonely, dtype=jnp.int64)
                return _CappedRel(t, valid, unique, expanded, unmatched,
                                  unmatched_right), ovf
            mask = ops.semi_join_mask(lkeys, rkeys, lalive=l.alive,
                                      ralive=r.alive)
            alive = (l.alive & mask if node.how == "left_semi"
                     else l.alive & ~mask)
            return _CappedRel(l.table, alive), None
        if isinstance(node, HashAggregate):
            (c,) = childs
            if not node.keys:
                t = self._global_aggregate(c.table, node, alive=c.alive)
                return _CappedRel(t, jnp.ones((1,), bool)), None
            key_cap = self._node_cap(caps, "key_cap", idx)
            aggs = [(cn, o) for cn, o, _ in node.aggs]
            # dispatch happens inside groupby_aggregate_capped, on this
            # signature: the key cap picks the sort-free kernel or a sort
            from ..ops.aggregate import groupby_signature
            pick("groupby", groupby_signature(c.table, node.keys, aggs,
                                              key_cap))
            agg, valid, ovf = ops.groupby_aggregate_capped(
                c.table, list(node.keys), aggs, key_cap=key_cap,
                alive=c.alive)
            t = Table(list(agg.columns), names=schemas[id(node)])
            return _CappedRel(t, valid), ovf
        if isinstance(node, Window):
            # dead rows sort last as a partition of their own and carry
            # into nothing (ops/window.py): the frame keeps its length
            (c,) = childs
            from ..ops import window as window_ops
            kernel_map[idx] = "xla:" + window_ops.KERNEL
            t, alive = ops.window_functions(
                c.table, node.partition_by, node.order_by, node.ascending,
                node.functions, node.frame, alive=c.alive)
            return _CappedRel(t, alive), None
        if isinstance(node, Sort):
            (c,) = childs
            t, alive = ops.sort_table_capped(
                c.table, key_names=list(node.keys),
                ascending=list(node.ascending), alive=c.alive)
            return _CappedRel(t, alive), None
        if isinstance(node, TopK):
            # fused Sort+Limit: dead rows sink in the capped sort, then the
            # first n LIVE rows survive via the inclusive prefix count. The
            # Pallas kernel instead returns the top-n live rows directly
            # (narrower frame, same live set — downstream capped operators
            # accept any row count)
            (c,) = childs
            from ..ops import topk_pallas
            choice = pick("topk",
                          topk_pallas.make_signature(c.table, node.keys,
                                                     node.ascending, node.n,
                                                     "capped"))
            if not choice.fallback:
                t, alive = topk_pallas.topk_capped(
                    c.table, list(node.keys), list(node.ascending), node.n,
                    c.alive)
                return _CappedRel(t, alive), None
            t, alive = ops.sort_table_capped(
                c.table, key_names=list(node.keys),
                ascending=list(node.ascending), alive=c.alive)
            prefix = jnp.cumsum(alive.astype(jnp.int32))
            return _CappedRel(t, alive & (prefix <= node.n)), None
        if isinstance(node, Limit):
            (c,) = childs
            # first n LIVE rows: inclusive prefix count over the mask
            prefix = jnp.cumsum(c.alive.astype(jnp.int32))
            return _CappedRel(c.table, c.alive & (prefix <= node.n)), None
        if isinstance(node, Union):
            t = ops.concat_tables([c.table for c in childs])
            alive = jnp.concatenate([c.alive for c in childs])
            return _CappedRel(t, alive), None
        if isinstance(node, Exchange):
            return childs[0], None
        raise PlanValidationError(f"no capped lowering for {node.kind}")
