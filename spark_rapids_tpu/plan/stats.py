"""Per-fingerprint operator-stats store: the engine's feedback loop.

Every successful execution already produces a rich stream — per-op
rows/bytes/wall (`OperatorMetrics`), escalated capacities
(`PlanResult.caps`), streaming-scan decode throughput, and the kernel
registry's per-dispatch choices — that used to be stamped on the result
and dropped. This module keeps it: a bounded, **backend-keyed** store of
what each plan fingerprint actually did, consulted on the next execution
of the same (or a structurally overlapping) plan by three consumers
(docs/adaptive.md):

1. **optimizer** (`plan/optimizer.py`): `_Estimator` resolves INTERIOR
   nodes' row estimates from the store's *observed* subtree
   cardinalities before falling back to the static selectivity guesses
   (at scans, a bound table's exact size always wins; observed and
   `est_rows` hints fill in only for unbound scans) — join build-side
   selection and `exchange_planning`'s shuffle-vs-broadcast choice
   become observation-driven on warm fingerprints, with the decision
   source recorded per rule firing on `OptimizeReport`;
2. **executor** (`plan/executor.py`): the capped tier seeds its initial
   capacities from the observed high-water caps, so a repeat fingerprint
   compiles once instead of re-climbing the geometric escalation ladder
   (the per-executor caps memo, promoted across executor instances and —
   with `SPARK_RAPIDS_TPU_STATS_PATH` — across processes); the eager
   streaming tier sizes its morsels from observed decode throughput;
3. **kernel registry** (`ops/registry.py`): `select()` demotes a kernel
   that has benched slower than its fallback on this (op, backend,
   signature) shape, recording the demotion on `KernelChoice`.

Adaptivity may change HOW a plan executes, never WHAT it returns: every
consumer feeds decisions the engine already guards for semantic
neutrality (build-side swaps re-verify through `verify_rewrite`, caps
are starting capacities the overflow ladder would have grown anyway,
chunking is merge-exact, kernels are parity-gated), and the fuzzer's
two-run check (`analysis/fuzz.py`) plus tests/test_adaptive.py's
cold == warm == adaptivity-off parity hold that line bit-exactly.

Backend isolation is a correctness rule, not bookkeeping: a degraded
(breaker-tripped) plan finishes on the CPU tier, and its stats record
under ``backend="cpu"`` — they must never seed device-side caps or
demote device kernels. Every table in the store is therefore keyed by
backend first, and the executor passes the backend the result actually
ran on.

Knobs (config.py): ``SPARK_RAPIDS_TPU_STATS`` (on/off — off restores
byte-identical static behavior), ``SPARK_RAPIDS_TPU_STATS_CAPACITY``
(LRU bound), ``SPARK_RAPIDS_TPU_STATS_PATH`` (optional JSONL
persistence). Tests and benches install an explicit store with
`scoped_store(...)`, which outranks the knob family.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import threading
from typing import Dict, Optional, Tuple

from ..utils.lru import LruDict

__all__ = ["StatsStore", "active_store", "default_store",
           "reset_default_store", "scoped_store"]

# morsel sizing (eager streaming tier): aim each decoded chunk at this
# much host decode wall — big enough to amortize per-chunk dispatch,
# small enough to keep the prefetch double-buffer working set bounded
_TARGET_CHUNK_MS = 25.0
_MIN_CHUNK_ROWS = 4096
# kernel tie-break hysteresis: a kernel must bench this much slower than
# its fallback (per row) before it loses the pick — noise must not flap
# the selection (and with it the capped tier's compiled-program cache)
_DEMOTE_MARGIN = 1.25
_EWMA_ALPHA = 0.5


def _ewma(old: Optional[float], new: float) -> float:
    return new if old is None else (1 - _EWMA_ALPHA) * old + _EWMA_ALPHA * new


class StatsStore:
    """Bounded feedback store. All tables key on backend first:

    - plans:    (backend, source fingerprint) -> {executed_fp, runs,
                caps{cap key: high-water}, peak_bytes (high-water
                observed live bytes — serving admission's warm charge),
                ops{toposort idx: row}}
    - subtrees: (backend, subtree fingerprint) -> {rows (high-water),
                runs} — observed output cardinality of that exact
                operator subtree, the optimizer's estimate override
    - io:       (backend, scan subtree fingerprint) -> {rows_per_ms
                (EWMA), runs} — streaming-scan decode throughput
    - kernels:  (backend, op, signature repr) -> {kernel name:
                {ms_per_krow (EWMA), runs}} — the registry tie-break

    `generation` bumps on every record (the executor's rewrite cache
    keys on it — a cached rewrite must not outlive the observations it
    ignored); `kernel_epoch` bumps only when a recorded timing flips a
    DEMOTION VERDICT for some signature (the capped tier's jit cache
    keys on it, so compiled programs stay shared across runs whose
    kernel picks cannot have changed). `hits` counts successful
    consults — the bench JSONL `stats_hits` stamp.

    Constructor: `capacity`/`path` default from the config knobs. Pass
    `path=""` to force a store in-memory-only regardless of
    SPARK_RAPIDS_TPU_STATS_PATH — every *fresh isolated* store (the
    fuzzer's per-case stores, the adaptive bench, tests) must, or an
    operator's persisted stats would silently pre-warm a run that
    documents itself as cold and pollute the persisted file with
    throwaway plans.
    """

    _uids = itertools.count()

    def __init__(self, capacity: Optional[int] = None,
                 path: Optional[str] = None):
        from .. import config
        # process-unique, never-reused identity for executor cache keys
        # (id() can be recycled after GC — a stale compiled program must
        # not alias a new store that landed on the same address)
        self.uid = next(StatsStore._uids)
        self.capacity = (config.stats_capacity() if capacity is None
                         else max(1, int(capacity)))
        self.path = (config.stats_path() or None) if path is None else \
            (path or None)
        self._plans: Dict[Tuple, Dict] = LruDict(self.capacity)
        self._subtrees: Dict[Tuple, Dict] = LruDict(self.capacity * 16)
        self._io: Dict[Tuple, Dict] = LruDict(self.capacity * 4)
        self._kernels: Dict[Tuple, Dict] = LruDict(self.capacity * 16)
        self.generation = 0
        self.kernel_epoch = 0
        self.hits = 0
        self._lock = threading.RLock()
        # persistence appends serialize separately from the table lock:
        # two sessions recording concurrently must not interleave half a
        # JSONL line each (replay tolerates torn lines, but silently
        # dropping both records is not "best-effort", it is data loss),
        # and file IO must not extend the hot lock's hold time
        self._io_lock = threading.Lock()
        if self.path:
            self._load(self.path)

    # ---- recording ---------------------------------------------------------

    def record_result(self, plan, result, *, backend: str,
                      source_fp: Optional[str] = None) -> None:
        """Record one successful execution. `plan` is the EXECUTED plan
        (the optimized form when the optimizer ran — metric labels refer
        to its nodes); `source_fp` is the authored plan's fingerprint,
        under which the plan-level entry files (cold and warm executions
        of one authored plan share it even when a stats-driven rewrite
        changes the executed fingerprint). `backend` is the backend the
        result actually ran on — the executor passes "cpu" for degraded
        results, keeping salvage runs out of device-side decisions."""
        from .optimizer import subtree_fingerprints
        source_fp = source_fp or plan.fingerprint
        sub = subtree_fingerprints(plan.root)
        # observed peak live bytes: the widest node-plus-inputs frontier
        # the walk actually materialized — the serving layer's admission
        # charge for WARM fingerprints (ISSUE 16: certified cross-product
        # bounds overcharge; what the plan DID is the better sizer)
        peak = 0
        for node in plan.nodes:
            m = result.metrics.get(node.label)
            if m is None:
                continue
            tot = int(m.bytes_out) + sum(
                int(result.metrics[c.label].bytes_out)
                for c in node.children if c.label in result.metrics)
            peak = max(peak, tot)
        event = {"backend": backend, "source_fp": source_fp,
                 "executed_fp": plan.fingerprint, "caps": {},
                 "peak_bytes": peak,
                 "ops": {}, "subtrees": {}, "io": {}, "kernels": []}
        with self._lock:
            key = (backend, source_fp)
            ps = self._plans.get(key) or {
                "executed_fp": plan.fingerprint, "runs": 0, "caps": {},
                "peak_bytes": 0, "ops": {}}
            ps["runs"] += 1
            ps["executed_fp"] = plan.fingerprint
            ps["peak_bytes"] = max(int(ps.get("peak_bytes", 0)), peak)
            if (result.caps and result.mode == "capped"
                    and not result.degraded):
                # final (possibly escalated) capacities: high-water.
                # Degraded caps are skipped — they describe the failed
                # device attempts, not a completed sizing.
                for k, v in result.caps.items():
                    ps["caps"][k] = max(int(ps["caps"].get(k, 0)), int(v))
                event["caps"] = dict(ps["caps"])
            for i, node in enumerate(plan.nodes):
                m = result.metrics.get(node.label)
                if m is None:
                    continue
                ps["ops"][i] = event["ops"][i] = {
                    "rows_out": int(m.rows_out),
                    "bytes_out": int(m.bytes_out),
                    "wall_ms": m.wall_ms,
                    "kernel": m.kernel}
                sfp = sub[id(node)]
                e = self._subtrees.get((backend, sfp)) or \
                    {"rows": 0, "runs": 0}
                e["rows"] = max(int(e["rows"]), int(m.rows_out))
                e["runs"] += 1
                self._subtrees[(backend, sfp)] = e
                event["subtrees"][sfp] = e["rows"]
                if result.degraded and not m.degraded:
                    # a partially-degraded plan: this op ran on the
                    # DEVICE before the breaker tripped. Its observed
                    # cardinality is backend-independent (recorded
                    # above), but its wall-derived kernel timing and
                    # decode rate are device measurements — filing them
                    # under "cpu" would let device numbers drive CPU
                    # tie-breaks and morsel sizing
                    continue
                if m.io_decode_ms > 0 and m.rows_out > 0:
                    rate = m.rows_out / m.io_decode_ms
                    ioe = self._io.get((backend, sfp)) or \
                        {"rows_per_ms": None, "runs": 0}
                    ioe["rows_per_ms"] = _ewma(ioe["rows_per_ms"], rate)
                    ioe["runs"] += 1
                    self._io[(backend, sfp)] = ioe
                    event["io"][sfp] = ioe["rows_per_ms"]
                ksig = getattr(m, "_kernel_sig", None)
                if ksig is not None and m.kernel and m.wall_ms:
                    op, sig = ksig
                    name = m.kernel.split(":", 1)[0]
                    per_krow = m.wall_ms / max(int(m.rows_in), 1) * 1e3
                    self._record_kernel_locked(backend, op, sig, name,
                                               per_krow)
                    event["kernels"].append(
                        [op, self._sig_key(sig), name, per_krow])
            self._plans[key] = ps
            self.generation += 1
        if self.path:
            self._append(event)

    @staticmethod
    def _sig_key(sig) -> str:
        return "" if sig is None else repr(sig)

    @staticmethod
    def _verdict_pairs(m: Dict) -> frozenset:
        """Every ordered (slower, faster) pair past the demotion margin —
        the complete set of `kernel_slower` verdicts this signature's
        timings can currently produce, whatever the fallback name."""
        return frozenset(
            (a, b) for a in m for b in m
            if a != b and m[a]["ms_per_krow"] is not None
            and m[b]["ms_per_krow"] is not None
            and m[a]["ms_per_krow"] > m[b]["ms_per_krow"] * _DEMOTE_MARGIN)

    def _record_kernel_locked(self, backend: str, op: str, sig, name: str,
                              ms_per_krow: float) -> None:
        key = (backend, op, self._sig_key(sig))
        m = self._kernels.get(key) or {}
        before = self._verdict_pairs(m)
        e = m.get(name) or {"ms_per_krow": None, "runs": 0}
        e["ms_per_krow"] = _ewma(e["ms_per_krow"], float(ms_per_krow))
        e["runs"] += 1
        m[name] = e
        if self._verdict_pairs(m) != before:
            # a demotion VERDICT a tie-break could observe flipped (an
            # EWMA drift crossing the margin counts even when the raw
            # ordering is unchanged): compiled programs keyed on the old
            # epoch must not serve new picks
            self.kernel_epoch += 1
        self._kernels[key] = m

    def record_kernel(self, backend: str, op: str, sig, name: str,
                      wall_ms: float, rows: int = 1000) -> None:
        """Public timing feed (benches, tests): `wall_ms` over `rows`
        rows normalizes to the store's ms-per-1k-rows basis."""
        with self._lock:
            self._record_kernel_locked(
                backend, op, sig, name, wall_ms / max(int(rows), 1) * 1e3)

    # ---- consults ----------------------------------------------------------

    def observed_rows(self, backend: str,
                      subtree_fp: str) -> Optional[Tuple[int, int]]:
        """(high-water rows, run count) observed for this exact operator
        subtree on this backend; None when never seen (cold start — the
        estimator falls back to bound sizes and hints)."""
        with self._lock:
            e = self._subtrees.get((backend, subtree_fp))
            if e is None:
                return None
            self.hits += 1
            return int(e["rows"]), int(e["runs"])

    def observed_caps(self, backend: str, source_fp: str,
                      executed_fp: Optional[str] = None) -> Dict[str, int]:
        """Observed high-water capacities for this authored plan. When
        the executed fingerprint differs from the recorded one (a
        stats-driven rewrite changed the plan shape since), only the
        GLOBAL cap keys carry over — per-node `row_cap:<i>` entries are
        toposort-indexed into a plan that no longer exists."""
        with self._lock:
            ps = self._plans.get((backend, source_fp))
            if ps is None or not ps["caps"]:
                return {}
            caps = dict(ps["caps"])
            if executed_fp is not None and \
                    ps.get("executed_fp") != executed_fp:
                caps = {k: v for k, v in caps.items() if ":" not in k}
            if caps:
                self.hits += 1
            return caps

    def plan_runs(self, backend: str, source_fp: str) -> int:
        with self._lock:
            ps = self._plans.get((backend, source_fp))
            return 0 if ps is None else int(ps["runs"])

    def observed_peak_bytes(self, backend: str, source_fp: str
                            ) -> Optional[Tuple[int, int]]:
        """(high-water observed live bytes, run count) for this authored
        plan on this backend — the serving layer's warm-fingerprint
        admission charge (docs/serving.md#admission). None when the plan
        was never seen here or no run produced byte counts (admission
        falls back to the certified bound, then the flat default)."""
        with self._lock:
            ps = self._plans.get((backend, source_fp))
            if ps is None or not ps.get("peak_bytes"):
                return None
            self.hits += 1
            return int(ps["peak_bytes"]), int(ps["runs"])

    def forget_plan(self, source_fp: str) -> int:
        """Drop every backend's entry for this authored plan (the fleet
        invalidation bus: a source input's digest changed, so observed
        sizes may describe data that no longer exists). Subtree/io/kernel
        tables survive — they key on structural fingerprints that remain
        valid observations of whatever data they saw. Returns the number
        of entries dropped."""
        with self._lock:
            doomed = [k for k in list(self._plans.keys())
                      if k[1] == source_fp]
            for k in doomed:
                del self._plans[k]
            if doomed:
                self.generation += 1
            return len(doomed)

    # ---- fleet gossip (serving/fleet.py, docs/serving.md#fleet) ------------

    def export_plans(self, fps=None) -> list:
        """Snapshot the plan-level observations as gossip rows —
        `{backend, source_fp, executed_fp, runs, caps, peak_bytes}` per
        (backend, fingerprint) entry, restricted to `fps` when given.
        This is the warm-failover payload: caps and high-water bytes are
        what a rehomed fingerprint needs to compile once and charge
        observed bytes immediately; per-op rows stay home (toposort-
        indexed detail no remote consumer reads). Rows are copies — the
        receiver's merge must not alias this store's tables."""
        with self._lock:
            out = []
            for (backend, source_fp), ps in self._plans.items():
                if fps is not None and source_fp not in fps:
                    continue
                out.append({"backend": backend, "source_fp": source_fp,
                            "executed_fp": ps.get("executed_fp", ""),
                            "runs": int(ps.get("runs", 0)),
                            "caps": dict(ps.get("caps", {})),
                            "peak_bytes": int(ps.get("peak_bytes", 0))})
            return out

    def merge_plans(self, rows) -> int:
        """Merge gossip rows from a peer store: high-water everything
        (caps, peak_bytes, runs), so the merge is idempotent and
        order-independent — gossiping the same snapshot twice changes
        nothing, which lets the fleet re-gossip without bookkeeping.
        Returns the number of rows that changed anything; bumps
        `generation` once if any did (cached rewrites must not outlive
        observations they ignored, same rule as record_result)."""
        changed = 0
        with self._lock:
            for row in rows:
                try:
                    key = (row["backend"], row["source_fp"])
                    ps = self._plans.get(key)
                    if ps is None:
                        ps = {"executed_fp": row.get("executed_fp", ""),
                              "runs": 0, "caps": {}, "peak_bytes": 0,
                              "ops": {}}
                    before = (ps["runs"], ps["peak_bytes"],
                              dict(ps["caps"]))
                    ps["runs"] = max(int(ps["runs"]),
                                     int(row.get("runs", 0)))
                    ps["peak_bytes"] = max(int(ps["peak_bytes"]),
                                           int(row.get("peak_bytes", 0)))
                    for k, v in (row.get("caps") or {}).items():
                        ps["caps"][k] = max(int(ps["caps"].get(k, 0)),
                                            int(v))
                    if not ps.get("executed_fp"):
                        ps["executed_fp"] = row.get("executed_fp", "")
                    if (ps["runs"], ps["peak_bytes"], ps["caps"]) \
                            != before:
                        changed += 1
                    self._plans[key] = ps
                except (KeyError, TypeError, ValueError):
                    continue    # tolerate a torn/foreign row, like _load
            if changed:
                self.generation += 1
        return changed

    def hot_fingerprints(self, k: int) -> list:
        """The top-`k` source fingerprints by total observed runs across
        backends — the store-side HOT signal replication can fall back
        on when the router's own submission counter is cold (a respawned
        worker inherits gossiped runs, not router history)."""
        if k <= 0:
            return []
        with self._lock:
            runs: Dict[str, int] = {}
            for (_backend, source_fp), ps in sorted(self._plans.items()):
                runs[source_fp] = runs.get(source_fp, 0) + \
                    int(ps.get("runs", 0))
        # ties break on the fingerprint, not dict insertion order — the
        # hot set must be identical across stores holding the same rows
        return [fp for fp, _ in sorted(runs.items(),
                                       key=lambda kv: (-kv[1], kv[0]))[:k]]

    def op_stats(self, backend: str, source_fp: str) -> Dict[int, Dict]:
        """toposort index -> {rows_out, bytes_out, wall_ms, kernel} of
        the last recorded execution of this authored plan on `backend`.
        Today's in-tree consumers are observability (tests, profile
        surfaces), not decisions."""
        with self._lock:
            ps = self._plans.get((backend, source_fp))
            return {} if ps is None else {
                int(i): dict(v) for i, v in ps["ops"].items()}

    def suggest_chunk_rows(self, backend: str, scan_fp: str) -> int:
        """Morsel row bound from observed decode throughput: about
        `_TARGET_CHUNK_MS` of host decode per chunk. 0 = no suggestion
        (cold, or throughput too low to matter); callers treat 0 the
        same as an unset SPARK_RAPIDS_TPU_IO_CHUNK_ROWS."""
        with self._lock:
            e = self._io.get((backend, scan_fp))
            if e is None or not e["rows_per_ms"]:
                return 0
            self.hits += 1
            return max(_MIN_CHUNK_ROWS,
                       int(e["rows_per_ms"] * _TARGET_CHUNK_MS))

    def kernel_slower(self, backend: str, op: str, sig, name: str,
                      fallback_name: str
                      ) -> Optional[Tuple[float, float]]:
        """(candidate, fallback) observed ms-per-1k-rows when the
        candidate has benched slower than the fallback past the
        `_DEMOTE_MARGIN` hysteresis on this exact signature; None when
        either timing is missing or the candidate holds up. The registry
        turns a non-None verdict into a decline (docs/kernels.md)."""
        if sig is None:
            return None         # shape unknown: nothing to compare
        with self._lock:
            m = self._kernels.get((backend, op, self._sig_key(sig)))
            if not m or name not in m or fallback_name not in m:
                return None
            a = m[name]["ms_per_krow"]
            b = m[fallback_name]["ms_per_krow"]
            if a is None or b is None or a <= b * _DEMOTE_MARGIN:
                return None
            self.hits += 1
            return float(a), float(b)

    # ---- persistence (JSONL) -----------------------------------------------

    def _append(self, event: Dict) -> None:
        try:
            with self._io_lock, open(self.path, "a", encoding="utf-8") as f:
                f.write(json.dumps(event) + "\n")
        except OSError:
            pass                # persistence is best-effort observability

    def _load(self, path: str) -> None:
        try:
            with open(path, "r", encoding="utf-8") as f:
                lines = f.readlines()
        except OSError:
            return
        # construction is single-threaded today, but the tables this
        # fills are the lock-protected shared state — the lint_hazards
        # lock-discipline rule (tools/lint_hazards.py) holds every
        # mutation site to the same standard, replay included
        with self._lock:
            self._load_locked(lines)

    def _load_locked(self, lines) -> None:
        for line in lines:
            try:
                ev = json.loads(line)
                backend = ev["backend"]
                key = (backend, ev["source_fp"])
                ps = self._plans.get(key) or {
                    "executed_fp": ev["executed_fp"], "runs": 0,
                    "caps": {}, "peak_bytes": 0, "ops": {}}
                ps["runs"] += 1
                ps["executed_fp"] = ev["executed_fp"]
                ps["peak_bytes"] = max(int(ps.get("peak_bytes", 0)),
                                       int(ev.get("peak_bytes") or 0))
                for k, v in (ev.get("caps") or {}).items():
                    ps["caps"][k] = max(int(ps["caps"].get(k, 0)), int(v))
                for i, v in (ev.get("ops") or {}).items():
                    ps["ops"][int(i)] = dict(v)
                self._plans[key] = ps
                for sfp, rows in (ev.get("subtrees") or {}).items():
                    e = self._subtrees.get((backend, sfp)) or \
                        {"rows": 0, "runs": 0}
                    e["rows"] = max(int(e["rows"]), int(rows))
                    e["runs"] += 1
                    self._subtrees[(backend, sfp)] = e
                for sfp, rate in (ev.get("io") or {}).items():
                    ioe = self._io.get((backend, sfp)) or \
                        {"rows_per_ms": None, "runs": 0}
                    ioe["rows_per_ms"] = _ewma(ioe["rows_per_ms"],
                                               float(rate))
                    ioe["runs"] += 1
                    self._io[(backend, sfp)] = ioe
                for op, sig_key, name, per_krow in (ev.get("kernels")
                                                    or []):
                    m = self._kernels.get((backend, op, sig_key)) or {}
                    e = m.get(name) or {"ms_per_krow": None, "runs": 0}
                    e["ms_per_krow"] = _ewma(e["ms_per_krow"],
                                             float(per_krow))
                    e["runs"] += 1
                    m[name] = e
                    self._kernels[(backend, op, sig_key)] = m
                self.generation += 1
            except (KeyError, TypeError, ValueError):
                continue        # tolerate a torn/foreign line


# ---- process wiring ---------------------------------------------------------

_default_store: Optional[StatsStore] = None
# guards the singleton hand-off: without it two threads racing first use
# would construct two stores and BOTH replay the persistence file —
# double-counted EWMAs and a torn generation counter (the
# unguarded-module-global-mutation lint rule now machine-checks this)
_default_lock = threading.Lock()
# explicit-scope stack: tests/benches push a store (or None, to force
# adaptivity OFF regardless of the knob) — the top outranks the knob.
# THREAD-LOCAL, like runtime/admission's active_session: concurrent
# executors must not see (or pop) each other's scopes — one session's
# isolated test store leaking into another thread's production
# executions would defeat the isolation the scope exists for.
_scope = threading.local()


def _scope_stack() -> list:
    stack = getattr(_scope, "stack", None)
    if stack is None:
        stack = _scope.stack = []
    return stack


def default_store() -> StatsStore:
    """The process singleton (capacity/path snapshot from config at first
    construction; `reset_default_store` re-reads)."""
    global _default_store
    with _default_lock:
        if _default_store is None:
            _default_store = StatsStore()
        return _default_store


def reset_default_store() -> None:
    global _default_store
    with _default_lock:
        _default_store = None


def active_store() -> Optional[StatsStore]:
    """The store consumers consult/record through, or None when
    adaptivity is off: the innermost `scoped_store` of THIS thread wins
    (even a scoped None — an explicit off), then
    `SPARK_RAPIDS_TPU_STATS` gates the process default."""
    stack = _scope_stack()
    if stack:
        return stack[-1]
    from .. import config
    if not config.stats_enabled():
        return None
    return default_store()


@contextlib.contextmanager
def scoped_store(store: Optional[StatsStore]):
    """Install `store` as the active store for the dynamic extent on the
    CURRENT thread (None forces adaptivity off). Used by tests, the
    fuzzer's two-run parity check, and the nightly adaptive gate to
    isolate observations."""
    stack = _scope_stack()
    stack.append(store)
    try:
        yield store
    finally:
        stack.pop()
