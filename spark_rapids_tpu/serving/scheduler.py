"""Multi-tenant serving layer: fair-share session scheduler with quota
admission, backpressure, and overload-graceful degradation
(docs/serving.md).

This is the paper's SparkResourceAdaptor story — many concurrent tasks
share one device without deadlock or starvation (PAPER.md §0) — promoted
to whole-plan traffic: the front door the `runtime/` arbitration
machinery (admission, retry budgets, breaker, spill) never had. N tenant
sessions submit plans; a bounded queue + a small dispatcher worker pool
execute them through ONE shared `PlanExecutor`, so the compiled-program
caches, the health monitor, and the stats store are genuinely shared
across tenants while every per-tenant bound stays per-tenant:

- **fair share** — weighted deficit round-robin over the sessions of
  each priority lane (interactive > normal > batch), one deficit credit
  per dispatched plan scaled by the session weight; an AGING bound
  (`SPARK_RAPIDS_TPU_SERVING_STARVATION_MS`) dispatches any plan that
  has waited too long regardless of lane or deficit, so weighted
  fairness can skew throughput but never unbound a session's queue wait.
  With `SPARK_RAPIDS_TPU_SERVING_FEEDBACK` on, each session's credit
  grant scales down by its decayed cumulative wall-ms + retry cost (the
  ROADMAP dispatch-fairness feedback loop) — half-life
  `_FEEDBACK_HALFLIFE_S`, floored at a quarter of the configured weight
  so one bad hour skews dispatch but can never starve a tenant;
- **quota admission** — every submission is charged against its
  session's device-memory quota: the OBSERVED high-water live bytes
  when the stats store has seen this fingerprint on this backend (what
  the plan DID — capped by the certified bound when both exist), else
  `footprint.quota_charge(cert, default)`: the PR 12 certifier's sound
  `peak_bytes_hi` when the plan is bounded, a flat configurable default
  when it is not. The winning source ("observed"/"certified"/"default")
  is stamped on the ticket (`charge_source`) and the soak's JSONL. A
  charge that can NEVER fit the session quota rejects (typed, naming
  session + the operator that set the certified peak, before any
  compilation) or pins the plan to the CPU tier; a
  charge that fits but is currently crowded out just waits — the
  dispatcher skips the session until its in-flight charges drain;
- **backpressure** — the queue is bounded; a full queue blocks submit()
  (or fast-rejects, caller-selectable) instead of hiding overload until
  memory does the rejecting (StreamBox-HBM's bounded-pipeline
  discipline, PAPERS.md);
- **per-session retry budgets** — every job executes inside
  `sessionctx.session_scope`, so the health monitor's retry budgets and
  sticky windows key on the TENANT (runtime/health.py): one pathological
  session exhausts its own budget, never a neighbour's;
- **breaker-aware dispatch** — an open breaker never stalls the queue:
  the executor's admission gate routes each dispatched plan to the
  degraded CPU tier (parity-exact) until the half-open probe closes the
  breaker, at which point device dispatch resumes on the very next job;
- **result cache** — completed results key by canonical fingerprint +
  input-data digest (serving/cache.py, LRU + TTL); hits serve deep-
  copied results stamped `cached=True` without consuming queue, quota,
  or a worker.

Concurrency note: this layer is the first real multi-plan concurrency
the engine sees — one session's streaming-scan prefetch thread decoding
chunks while another session's plan executes on the device is the PR 4
overlap promoted across tenants.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Deque, Dict, List, Optional

from . import cache as cache_mod

__all__ = ["ServingScheduler", "ServingSession", "Ticket",
           "ServingRejectedError", "PRIORITIES"]

# priority lanes, served strictly in order (aging outranks lanes)
PRIORITIES = {"interactive": 0, "normal": 1, "batch": 2}


class ServingRejectedError(RuntimeError):
    """Typed fast-reject from the serving layer. `reason` is machine-
    checkable ("queue_full" | "over_quota" | "closed" | "deadline" |
    "quarantined" — the last from the fleet's poison-fingerprint gate,
    serving/fleet.py); `session` and `operator` (the label that set the
    certified peak, over-quota only) make the diagnostic attributable
    without parsing the message."""

    def __init__(self, reason: str, detail: str, *,
                 session: Optional[str] = None, operator: str = ""):
        at = f" [session={session}]" if session else ""
        op = f" [operator={operator}]" if operator else ""
        super().__init__(f"{reason}{at}{op}: {detail}")
        self.reason = reason
        self.session = session
        self.operator = operator


class Ticket:
    """One submitted plan's handle: `result()` blocks for the outcome
    (re-raising the execution error, if any); `queue_wait_ms` and
    `cached` are the serving-side observability stamps."""

    def __init__(self, session_id: str, request: int = -1):
        self.session = session_id
        self.request = request    # monotonic per scheduler: the number
        #                           this submission's spans carry
        #                           (utils/tracing.py)
        self.queue_wait_ms: float = 0.0
        self.cached = False
        self.charge_source = ""   # "observed" | "certified" | "default"
        self.worker = ""          # fleet worker id ("" single-worker)
        self._event = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None
        # completion callbacks (serving/fleet.py condition-notify
        # wakeup): own lock, never held while running a callback or
        # while any other lock is held — no lock-order edges
        self._cb_lock = threading.Lock()
        self._callbacks: List = []

    def done(self) -> bool:
        return self._event.is_set()

    def add_done_callback(self, fn) -> None:
        """Run `fn(self)` when the ticket completes — immediately if it
        already has. Callbacks run on the completing thread (or this
        one), outside every scheduler lock; exceptions are swallowed
        (a waiter's notification hook must never fail the job)."""
        with self._cb_lock:
            if not self._event.is_set():
                self._callbacks.append(fn)
                return
        try:
            fn(self)
        except Exception:
            pass

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"serving ticket [session={self.session}] not complete "
                f"after {timeout}s")
        if self._error is not None:
            raise self._error
        return self._result

    def _complete(self, result=None, error: Optional[BaseException] = None):
        self._result = result
        self._error = error
        # set the event UNDER the callback lock: a concurrent
        # add_done_callback either appends before the set (drained
        # below) or observes it set and self-invokes — never neither
        with self._cb_lock:
            self._event.set()
            cbs, self._callbacks = self._callbacks, []
        for fn in cbs:
            try:
                fn(self)
            except Exception:
                pass


class _SessionState:
    """Dispatcher-side per-session bookkeeping (all fields guarded by the
    scheduler lock)."""

    def __init__(self, sid: str, weight: float, priority: str,
                 quota_bytes: int):
        self.id = sid
        self.weight = weight
        self.priority = priority
        self.lane = PRIORITIES[priority]
        self.quota_bytes = quota_bytes
        self.deficit = 0.0
        self.in_flight_bytes = 0
        # dispatch-fairness feedback (ISSUE 16): decayed cumulative cost
        # (wall-ms + retry penalty) this session has charged the device;
        # scales the WDRR credit grant down, bounded so one bad hour
        # can never starve a tenant forever
        self.cost_score = 0.0
        self.cost_at = 0.0        # clock of the last decay application
        self.queue: Deque["_Job"] = collections.deque()
        # accounting for metrics()/the soak's per-session assertions
        self.submitted = 0
        self.completed = 0
        self.failed = 0
        self.rejected = 0
        self.degraded = 0
        self.retries = 0
        self.cache_hits = 0
        self.deadline_rejects = 0            # expired-in-queue completions
        self.wait_ms: List[float] = []       # per-dispatch queue waits
        self.aged_dispatches = 0             # starvation-bound promotions
        self.active_jobs = 0                 # dispatched, not yet completed
        self.closed = False

    def wait_stats(self) -> Dict[str, float]:
        if not self.wait_ms:
            return {"max": 0.0, "p99": 0.0, "mean": 0.0}
        s = sorted(self.wait_ms)
        return {"max": s[-1],
                "p99": s[min(len(s) - 1, int(0.99 * len(s)))],
                "mean": sum(s) / len(s)}


class _Job:
    __slots__ = ("plan", "inputs", "state", "ticket", "charge",
                 "charge_source", "op_label", "tier", "cache_key",
                 "enqueued_at", "deadline")

    def __init__(self, plan, inputs, state: _SessionState, ticket: Ticket,
                 charge: int, charge_source: str, op_label: str, tier: str,
                 cache_key, enqueued_at: float,
                 deadline: Optional[float] = None):
        self.plan = plan
        self.inputs = inputs
        self.state = state
        self.ticket = ticket
        self.charge = charge
        self.charge_source = charge_source
        self.op_label = op_label
        self.tier = tier                  # "device" | "cpu" (quota-degraded)
        self.cache_key = cache_key
        self.enqueued_at = enqueued_at
        self.deadline = deadline          # submit-side deadline (clock units)


class ServingSession:
    """One tenant's handle onto the scheduler: `submit()` enqueues and
    returns a Ticket, `run()` is the submit+wait convenience. Closing a
    session only bars NEW submissions — queued work drains normally."""

    def __init__(self, scheduler: "ServingScheduler", state: _SessionState):
        self._scheduler = scheduler
        self._state = state
        self.id = state.id

    def submit(self, plan, inputs: Optional[Dict] = None, *,
               block: Optional[bool] = None,
               timeout: Optional[float] = None,
               pin_cpu: bool = False) -> Ticket:
        return self._scheduler._submit(self._state, plan, inputs,
                                       block=block, timeout=timeout,
                                       pin_cpu=pin_cpu)

    def run(self, plan, inputs: Optional[Dict] = None, *,
            block: Optional[bool] = None,
            timeout: Optional[float] = None,
            pin_cpu: bool = False):
        """submit + wait under ONE deadline: whatever the blocked submit
        consumed of `timeout` is not granted to the result wait again."""
        t0 = time.monotonic()
        ticket = self.submit(plan, inputs, block=block, timeout=timeout,
                             pin_cpu=pin_cpu)
        remaining = (None if timeout is None
                     else max(0.0, timeout - (time.monotonic() - t0)))
        return ticket.result(remaining)

    def close(self) -> None:
        self._scheduler._close_session(self._state)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class ServingScheduler:
    """The serving front door: N sessions, one device, bounded queue,
    fair-share dispatch (see the module docstring for the contract).

    Pass an existing `PlanExecutor` to share its health monitor and
    program caches with non-serving callers; by default the scheduler
    owns an eager-tier executor. All knob parameters default from the
    `SPARK_RAPIDS_TPU_SERVING_*` family (config.py), read once at
    construction (one policy per scheduler lifetime, the health-monitor
    convention)."""

    _ids = itertools.count(1)

    def __init__(self, executor=None, *,
                 workers: Optional[int] = None,
                 queue_depth: Optional[int] = None,
                 starvation_ms: Optional[float] = None,
                 cache_entries: Optional[int] = None,
                 cache_ttl_s: Optional[float] = None,
                 quota_bytes: Optional[int] = None,
                 default_charge_bytes: Optional[int] = None,
                 over_quota: Optional[str] = None,
                 backpressure: Optional[str] = None,
                 feedback: Optional[bool] = None,
                 feedback_halflife_s: Optional[float] = None,
                 stats_store=None,
                 clock=time.monotonic):
        from .. import config
        from ..plan.executor import PlanExecutor
        self.executor = executor if executor is not None \
            else PlanExecutor(mode="eager")
        # an explicit per-scheduler stats store (fleet workers isolate
        # theirs); None keeps the process-default active_store() wiring
        self.stats_store = stats_store
        self.feedback = (config.serving_feedback() if feedback is None
                         else bool(feedback))
        self.feedback_halflife_s = (
            config.serving_feedback_halflife_s()
            if feedback_halflife_s is None else float(feedback_halflife_s))
        self.workers = (config.serving_workers() if workers is None
                        else max(1, int(workers)))
        self.queue_depth = (config.serving_queue_depth()
                            if queue_depth is None
                            else max(1, int(queue_depth)))
        self.starvation_ms = (config.serving_starvation_ms()
                              if starvation_ms is None
                              else float(starvation_ms))
        self.default_quota_bytes = (config.serving_quota_bytes()
                                    if quota_bytes is None
                                    else int(quota_bytes))
        self.default_charge_bytes = (config.serving_default_charge_bytes()
                                     if default_charge_bytes is None
                                     else int(default_charge_bytes))
        self.over_quota = (config.serving_over_quota()
                           if over_quota is None else over_quota)
        if self.over_quota not in ("reject", "degrade"):
            raise ValueError(f"unknown over_quota policy "
                             f"{self.over_quota!r} (expected reject or "
                             "degrade)")
        bp = (config.serving_backpressure() if backpressure is None
              else backpressure)
        if bp not in ("block", "reject"):
            raise ValueError(f"unknown backpressure policy {bp!r} "
                             "(expected block or reject)")
        self.block_default = bp == "block"
        self.cache = cache_mod.ResultCache(entries=cache_entries,
                                           ttl_s=cache_ttl_s, clock=clock)
        self._clock = clock
        self._requests = itertools.count()    # Ticket.request numbers
        self._lock = threading.Lock()
        self._lock_cond = threading.Condition(self._lock)
        self._sessions: Dict[str, _SessionState] = {}
        self._rr: Dict[int, int] = {}     # per-lane round-robin cursor
        self._queued = 0
        self._queued_hiwater = 0
        self._active = 0                  # jobs dispatched, not yet done
        self._closed = False
        self._threads = [
            threading.Thread(target=self._worker, daemon=True,
                             name=f"srt-serving-{i}")
            for i in range(self.workers)]
        for t in self._threads:
            t.start()

    # ---- sessions ----------------------------------------------------------

    def open_session(self, session_id: Optional[str] = None, *,
                     weight: float = 1.0, priority: str = "normal",
                     quota_bytes: Optional[int] = None) -> ServingSession:
        if priority not in PRIORITIES:
            raise ValueError(f"unknown priority {priority!r} (expected "
                             f"one of {sorted(PRIORITIES)})")
        if weight <= 0:
            raise ValueError(f"session weight must be > 0, got {weight}")
        with self._lock:
            if self._closed:
                raise ServingRejectedError(
                    "closed", "scheduler is shut down")
            sid = session_id or f"s{next(self._ids)}"
            old = self._sessions.get(sid)
            if old is not None and not old.closed:
                raise ValueError(f"session id {sid!r} already open")
            if old is not None and old.queue:
                # reopening would orphan the old state's queued jobs: the
                # dispatcher discovers work only through self._sessions,
                # so replacing the entry now would strand those tickets
                # forever while _queued still counts them
                raise ValueError(f"session id {sid!r} is closed but still "
                                 f"draining {len(old.queue)} queued "
                                 "plan(s); reopen after they complete")
            state = _SessionState(
                sid, float(weight), priority,
                self.default_quota_bytes if quota_bytes is None
                else int(quota_bytes))
            self._sessions[sid] = state
        return ServingSession(self, state)

    def _close_session(self, state: _SessionState) -> None:
        with self._lock:
            state.closed = True
            self._maybe_reap_locked(state)

    def _maybe_reap_locked(self, state: _SessionState) -> None:
        """Drop a closed, fully-drained session from the map: a
        long-running scheduler serving short-lived tenants must not
        accumulate one _SessionState (deque + counters + wait samples)
        per tenant ever opened — _pick_locked iterates the map under the
        dispatch lock on every pick, so leaked sessions are latency, not
        just memory. Waits for queued AND dispatched work (a CPU-pinned
        job carries zero in-flight charge, so bytes alone cannot prove
        quiescence). Reaped ids disappear from metrics(); callers wanting
        a tenant's final numbers read them before close()."""
        if state.closed and not state.queue and \
                state.active_jobs == 0 and \
                self._sessions.get(state.id) is state:
            del self._sessions[state.id]

    # ---- submission --------------------------------------------------------

    def _bind(self, plan, inputs: Optional[Dict]) -> Dict:
        """The executor's OWN scan-binding prologue (one definition —
        plan/executor.bind_scan_sources), applied here so the cache
        digest and quota charge see exactly the binding execute() will."""
        from ..plan.executor import bind_scan_sources
        return bind_scan_sources(plan, inputs)

    def _certify(self, plan, inputs: Dict):
        """Certify the AUTHORED plan through the executor's memoized walk
        — quota must resolve BEFORE any optimization/compilation, so the
        charge is deliberately the authored plan's bound (the optimizer
        may only keep or tighten it — certifier monotonicity, docs/
        analysis.md); repeat submissions of the same (plan, binding)
        share the memo, execute()'s own cert of the REWRITTEN plan is a
        separate (also memoized) walk. Defensive None on any error:
        sizing must never fail a submission the executor would accept
        (missing inputs etc. surface at execution, against
        executor-owned diagnostics)."""
        try:
            bound = {name: tuple(t.names) for name, t in inputs.items()}
            return self.executor._certify(plan, inputs, bound)
        except Exception:
            return None

    def _observed_charge(self, plan) -> Optional[int]:
        """High-water OBSERVED live bytes for this authored plan on the
        current backend (plan/stats.py), or None when cold / stats off.
        Defensive None on any error — sizing must never fail a submit."""
        from ..plan import stats as stats_mod
        store = (self.stats_store if self.stats_store is not None
                 else stats_mod.active_store())
        if store is None:
            return None
        try:
            import jax
            obs = store.observed_peak_bytes(jax.default_backend(),
                                            plan.fingerprint)
        except Exception:
            return None
        return None if obs is None else int(obs[0])

    def _submit(self, state: _SessionState, plan, inputs: Optional[Dict],
                *, block: Optional[bool], timeout: Optional[float],
                pin_cpu: bool = False) -> Ticket:
        """One submission, under the spans that say where its host time
        goes (utils/tracing.py): `serving.submit` > `serving.digest`
        (input digest + cache consult), `serving.admit` (certify, charge,
        over-quota resolution), `serving.enqueue` (the bounded queue)."""
        from ..runtime.sessionctx import request_scope
        from ..utils.tracing import span
        request = next(self._requests)
        with request_scope(request), span("serving.submit"):
            if self._closed or state.closed:
                # early unlocked read: a submit racing close() is still
                # caught by the locked re-check at enqueue below; this
                # just keeps cache hits from serving through a closed
                # front door
                raise ServingRejectedError(
                    "closed", "session or scheduler is shut down",
                    session=state.id)
            if block is None:
                block = self.block_default
            inputs = self._bind(plan, inputs)
            ticket = Ticket(state.id, request)
            with span("serving.digest") as sp:
                folded0 = cache_mod.bytes_digested()
                key = cache_mod.cache_key(plan, inputs) \
                    if self.cache.entries > 0 else None
                hit = self.cache.get(key)
                # host_bytes: buffer bytes copied to the host to be
                # hashed. The fold runs where the buffer lives and 16
                # bytes a buffer come back, so there are none
                sp.set_metadata(bytes=cache_mod.bytes_digested() - folded0,
                                host_bytes=0, hit=int(hit is not None))
            if hit is not None:
                # a hit consumes nothing: no queue slot, no quota, no
                # worker
                hit.session = state.id
                for m in hit.metrics.values():
                    m.session = state.id
                ticket.cached = True
                with self._lock:
                    state.submitted += 1
                    state.completed += 1
                    state.cache_hits += 1
                ticket._complete(result=hit)
                return ticket
            with span("serving.admit"):
                charge, source, op_label, tier = self._admit(
                    state, plan, inputs, pin_cpu)
            ticket.charge_source = source
            deadline = None if timeout is None else self._clock() + timeout
            job = _Job(plan, inputs, state, ticket, charge, source,
                       op_label, tier, key, self._clock(),
                       deadline=deadline)
            with span("serving.enqueue"):
                self._enqueue(job, block, timeout)
        return ticket

    def _admit(self, state: _SessionState, plan, inputs: Dict,
               pin_cpu: bool):
        """Size the submission against the session quota BEFORE any
        compilation -> (charge, charge source, operator label, tier);
        raises the typed over-quota rejection."""
        from ..analysis.footprint import quota_charge
        cert = self._certify(plan, inputs)
        charge, source, op_label = quota_charge(cert,
                                                self.default_charge_bytes)
        observed = self._observed_charge(plan)
        if observed:
            # warm fingerprint: what the plan DID is the better sizer
            # than the sound-but-loose certified cross-product bound —
            # but never charge above a certified ceiling (both bound the
            # same execution, the tighter one wins)
            charge = min(observed, charge) if source == "certified" \
                else observed
            source = "observed"
        tier = "device"
        if pin_cpu:
            # fleet quarantine degrade (serving/fleet.py): the device
            # never sees this plan, so the device quota does not bind —
            # the same contract as the over_quota degrade below
            tier, charge = "cpu", 0
        elif charge > state.quota_bytes:
            # can NEVER fit this session's quota: resolve now, before any
            # compilation — reject with an attributable diagnostic, or
            # pin to the CPU tier where the device quota does not bind
            if self.over_quota == "reject":
                with self._lock:
                    state.submitted += 1
                    state.rejected += 1
                raise ServingRejectedError(
                    "over_quota",
                    f"plan charges {charge} B ({source}) against a "
                    f"{state.quota_bytes} B session quota",
                    session=state.id, operator=op_label)
            tier, charge = "cpu", 0
        return charge, source, op_label, tier

    def _enqueue(self, job: _Job, block: bool,
                 timeout: Optional[float]) -> None:
        """Append `job` to its session's queue, blocking (or fast-
        rejecting, per the backpressure policy) while the bounded queue
        is full; the queue wait counts from the append."""
        state, deadline = job.state, job.deadline
        with self._lock_cond:
            if self._closed or state.closed:
                raise ServingRejectedError(
                    "closed", "session or scheduler is shut down",
                    session=state.id)
            while self._queued >= self.queue_depth:
                if not block:
                    state.submitted += 1
                    state.rejected += 1
                    raise ServingRejectedError(
                        "queue_full",
                        f"{self._queued} plans queued (depth "
                        f"{self.queue_depth}); backpressure policy is "
                        "fast-reject", session=state.id)
                remaining = (None if deadline is None
                             else deadline - self._clock())
                if remaining is not None and remaining <= 0:
                    state.submitted += 1
                    state.rejected += 1
                    raise ServingRejectedError(
                        "queue_full",
                        f"queue stayed full past the {timeout}s submit "
                        "timeout", session=state.id)
                self._lock_cond.wait(timeout=0.05 if remaining is None
                                else min(0.05, remaining))
                if self._closed or state.closed:
                    raise ServingRejectedError(
                        "closed", "session or scheduler shut down while "
                        "submit was blocked", session=state.id)
            job.enqueued_at = self._clock()
            state.queue.append(job)
            state.submitted += 1
            self._queued += 1
            self._queued_hiwater = max(self._queued_hiwater, self._queued)
            self._lock_cond.notify_all()

    # ---- dispatch ----------------------------------------------------------

    def _eligible(self, state: _SessionState) -> bool:
        """Head-of-line job can dispatch now: CPU-pinned jobs always (no
        device charge), device jobs when the session's in-flight charges
        leave room under its quota."""
        if not state.queue:
            return False
        job = state.queue[0]
        return job.tier == "cpu" or \
            state.in_flight_bytes + job.charge <= state.quota_bytes

    # cost normalizer: one second of accumulated wall halves a session's
    # effective weight; each retry charges like 100 ms of wall
    _FEEDBACK_NORM_MS = 1000.0
    _FEEDBACK_RETRY_MS = 100.0
    # the decayed penalty never cuts a session below a quarter of its
    # configured weight — feedback skews dispatch, it cannot starve
    _FEEDBACK_FLOOR = 0.25

    def _effective_weight_locked(self, s: _SessionState,
                                 now: float) -> float:
        """WDRR credit grant with the dispatch-fairness feedback loop
        (docs/serving.md#fairness): sessions that have recently burned
        disproportionate wall-ms / retries earn credit slower. The cost
        score decays with a configurable half-life (one bad hour fades)
        and the grant is floored at `_FEEDBACK_FLOOR x weight` (bounded
        skew, never starvation). Feedback off => exactly `s.weight`."""
        if not self.feedback:
            return s.weight
        if s.cost_score > 0.0 and self.feedback_halflife_s > 0:
            dt = now - s.cost_at
            if dt > 0:
                s.cost_score *= 0.5 ** (dt / self.feedback_halflife_s)
        s.cost_at = now
        scaled = s.weight / (1.0 + s.cost_score / self._FEEDBACK_NORM_MS)
        return max(scaled, self._FEEDBACK_FLOOR * s.weight)

    def _pick_locked(self) -> Optional[_Job]:
        """Next job to dispatch (scheduler lock held).

        1. Starvation aging: the oldest eligible head waiting past
           `starvation_ms` wins outright — bounded queue wait for every
           session, whatever the lanes/weights say.
        2. Priority lanes in order; weighted deficit round-robin within a
           lane: each pass over the lane's eligible sessions grants
           `weight` credit (scaled down by the feedback cost score when
           SPARK_RAPIDS_TPU_SERVING_FEEDBACK is on), a dispatch costs 1
           credit — over time a weight-2 session dispatches twice per
           weight-1 session's once.
        """
        eligible = [s for s in self._sessions.values() if self._eligible(s)]
        if not eligible:
            return None
        now = self._clock()
        if self.starvation_ms > 0:
            starved = [s for s in eligible
                       if (now - s.queue[0].enqueued_at) * 1e3
                       >= self.starvation_ms]
            if starved:
                s = min(starved, key=lambda s: s.queue[0].enqueued_at)
                s.aged_dispatches += 1
                return self._take_locked(s)
        lanes: Dict[int, List[_SessionState]] = {}
        for s in eligible:
            lanes.setdefault(s.lane, []).append(s)
        for lane in sorted(lanes):
            members = sorted(lanes[lane], key=lambda s: s.id)
            cursor = self._rr.get(lane, 0)
            # rotate so round-robin order persists across picks
            members = members[cursor % len(members):] + \
                members[:cursor % len(members)]
            for _ in range(64):     # bounded credit rounds (weights >= eps)
                for i, s in enumerate(members):
                    if s.deficit >= 1.0:
                        s.deficit -= 1.0
                        self._rr[lane] = (cursor + i + 1) % len(members)
                        return self._take_locked(s)
                for s in members:
                    s.deficit = min(
                        s.deficit + self._effective_weight_locked(s, now),
                        64.0)
        return None

    def _take_locked(self, state: _SessionState) -> _Job:
        job = state.queue.popleft()
        self._queued -= 1
        if job.tier != "cpu":
            state.in_flight_bytes += job.charge
        state.active_jobs += 1
        self._active += 1
        self._lock_cond.notify_all()
        return job

    def _worker(self) -> None:
        while True:
            with self._lock_cond:
                job = None
                while job is None:
                    if self._closed and self._queued == 0:
                        return
                    job = self._pick_locked()
                    if job is None:
                        # timed wait, not pure signal-driven: aging
                        # promotions and quota releases become pickable
                        # with time, and a missed notify must never
                        # strand a queued job
                        self._lock_cond.wait(timeout=0.05)
            self._run_job(job)

    def _run_job(self, job: _Job) -> None:
        from ..runtime import sessionctx
        from ..utils.tracing import span
        wait_ms = (self._clock() - job.enqueued_at) * 1e3
        job.ticket.queue_wait_ms = wait_ms
        # the worker's side of the request: the number published here is
        # what joins plan.* and ops.* spans to the submitter's serving.*
        with sessionctx.request_scope(job.ticket.request), \
                span("serving.dispatch", queue_wait_ms=round(wait_ms, 3)):
            self._run_dispatched(job, wait_ms)

    def _run_dispatched(self, job: _Job, wait_ms: float) -> None:
        from ..utils.tracing import span
        result = error = None
        served_hit = False
        # EVERYTHING between dispatch and the ticket's completion must
        # leave the worker alive and the ticket completed: an unguarded
        # raise here (cache copy under memory pressure, say) would kill the
        # dispatcher thread, leak _active/in_flight accounting (close()
        # then never drains), and strand the submitter's result() forever
        try:
            result, served_hit = self._consult_and_execute(job, wait_ms)
        except BaseException as e:
            error = e
        with span("serving.complete"):
            if error is None and not served_hit \
                    and job.cache_key is not None and not result.degraded:
                # device-tier results only: a degraded result is a
                # transient-condition artifact (breaker open, quota pin)
                # whose degraded=True stamp would keep reporting CPU-tier
                # completions to healthy-device traffic for the whole TTL.
                # The cache is an optimization — failing to store must not
                # fail the job.
                try:
                    self.cache.put(job.cache_key, result)
                except Exception:
                    pass
                except BaseException as e:
                    error = e
            self._complete_job(job, wait_ms, result, error, served_hit)

    def _consult_and_execute(self, job: _Job, wait_ms: float):
        """-> (result, whether the dispatch-time cache consult served it).
        `serving.consult` is the worker's part before `plan.execute`."""
        import contextlib
        from ..runtime import sessionctx
        from ..utils.tracing import span
        state = job.state
        with contextlib.ExitStack() as scopes:
            with span("serving.consult"):
                # deadline enforcement at dispatch: a job whose submit-side
                # deadline expired while QUEUED completes with the typed
                # rejection before certification or compilation — nobody
                # is waiting for the result, and executing it anyway would
                # charge quota and burn a dispatcher slot for dead traffic.
                # queue_wait_ms is already stamped: the wait that killed
                # the job is exactly the number worth reporting.
                if job.deadline is not None \
                        and self._clock() >= job.deadline:
                    raise ServingRejectedError(
                        "deadline",
                        f"submit-side deadline expired after "
                        f"{wait_ms:.0f} ms queued", session=state.id)
                # dispatch-time cache consult: a repeat plan that QUEUED
                # behind its twin (both submitted before either completed
                # — the common shape of a burst of identical traffic)
                # still serves the first completion's result instead of
                # re-executing
                # count_miss=False: submit() already counted this key's
                # miss once — the dispatch-time re-consult is burst dedup,
                # not new traffic, and must not halve the reported hit rate
                hit = self.cache.get(job.cache_key, count_miss=False)
                if hit is not None:
                    hit.session = state.id
                    for m in hit.metrics.values():
                        m.session = state.id
                    job.ticket.cached = True
                    return hit, True
                from ..plan import stats as stats_mod
                scopes.enter_context(sessionctx.session_scope(state.id))
                if self.stats_store is not None:
                    scopes.enter_context(
                        stats_mod.scoped_store(self.stats_store))
                # attribution scope: a breaker trip fired by THIS
                # execution is stamped with this plan's fingerprint in
                # the health monitor's trip log, which is what lets the
                # fleet's poison-plan quarantine (serving/fleet.py)
                # attribute trips to fingerprints instead of guessing
                scopes.enter_context(self.executor.health.attribution(
                    job.plan.fingerprint))
            return self.executor.execute(
                job.plan, job.inputs,
                tier="cpu" if job.tier == "cpu" else None), False

    def _complete_job(self, job: _Job, wait_ms: float, result, error,
                      served_hit: bool) -> None:
        """The accounting of a finished job, the quota's release and the
        ticket's completion."""
        state = job.state
        with self._lock:
            if job.tier != "cpu":
                state.in_flight_bytes -= job.charge
            state.active_jobs -= 1
            self._active -= 1
            state.wait_ms.append(wait_ms)
            if len(state.wait_ms) > 10_000:
                del state.wait_ms[:5_000]     # bounded sample memory
            if error is None and result is not None:
                state.completed += 1
                if served_hit:
                    state.cache_hits += 1
                else:
                    state.retries += result.retries
                    if result.degraded or job.tier == "cpu":
                        state.degraded += 1
                    if self.feedback:
                        if state.cost_score == 0.0:
                            # anchor the decay clock: an untouched
                            # cost_at of 0 would decay the first
                            # accrual away instantly
                            state.cost_at = self._clock()
                        state.cost_score += float(result.wall_ms) + \
                            self._FEEDBACK_RETRY_MS * result.retries
            elif (isinstance(error, ServingRejectedError)
                  and error.reason == "deadline"):
                # expired-in-queue is an admission outcome, not an
                # execution failure: count it with the rejects so
                # `failed` keeps meaning "execution broke"
                state.rejected += 1
                state.deadline_rejects += 1
            else:
                state.failed += 1
            self._maybe_reap_locked(state)
            self._lock_cond.notify_all()
        job.ticket._complete(result=result, error=error)

    # ---- lifecycle / observability -----------------------------------------

    def close(self, drain: bool = True,
              timeout: Optional[float] = None) -> None:
        """Shut down: `drain=True` (default) serves everything already
        queued, then stops; `drain=False` fails queued jobs with a typed
        `ServingRejectedError("closed")` immediately. Either way no new
        submission is accepted from the moment of the call."""
        deadline = None if timeout is None else self._clock() + timeout
        doomed: List[_Job] = []
        with self._lock_cond:
            self._closed = True
            if not drain:
                for state in self._sessions.values():
                    while state.queue:
                        job = state.queue.popleft()
                        self._queued -= 1
                        doomed.append(job)
            self._lock_cond.notify_all()
        # complete OUTSIDE the scheduler lock: _complete runs done-
        # callbacks (fleet ticket wakeups), and callbacks under the
        # scheduler lock would hand arbitrary code a lock-order edge
        for job in doomed:
            job.ticket._complete(error=ServingRejectedError(
                "closed", "scheduler shut down before dispatch",
                session=job.state.id))
        with self._lock_cond:
            while self._queued > 0 or self._active > 0:
                remaining = (None if deadline is None
                             else deadline - self._clock())
                if remaining is not None and remaining <= 0:
                    break
                self._lock_cond.wait(timeout=0.05 if remaining is None
                                else min(0.05, remaining))
        for t in self._threads:
            t.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def metrics(self) -> Dict:
        """Snapshot: per-session accounting + queue/cache aggregates (the
        soak's assertion surface, docs/serving.md#observability)."""
        with self._lock:
            now = self._clock()
            sessions = {
                s.id: {"weight": s.weight, "priority": s.priority,
                       "quota_bytes": s.quota_bytes,
                       "in_flight_bytes": s.in_flight_bytes,
                       "queued": len(s.queue), "submitted": s.submitted,
                       "completed": s.completed, "failed": s.failed,
                       "rejected": s.rejected, "degraded": s.degraded,
                       "deadline_rejects": s.deadline_rejects,
                       "retries": s.retries, "cache_hits": s.cache_hits,
                       "aged_dispatches": s.aged_dispatches,
                       "cost_score": round(s.cost_score, 3),
                       "effective_weight": round(
                           self._effective_weight_locked(s, now), 4),
                       "queue_wait_ms": s.wait_stats()}
                for s in self._sessions.values()}
            queued, hiwater = self._queued, self._queued_hiwater
        return {"sessions": sessions,
                "queued": queued,
                "queue_hiwater": hiwater,
                "queue_depth": self.queue_depth,
                "workers": self.workers,
                "cache": self.cache.stats(),
                "breaker": self.executor.health.breaker.state}

    def pressure(self) -> Dict:
        """Cheap load signal for the fleet router (serving/fleet.py):
        queued + active work, total in-flight certified charge, and the
        breaker state — enough to rank workers for spillover without
        touching per-session detail."""
        with self._lock:
            queued, active = self._queued, self._active
            inflight = sum(s.in_flight_bytes
                           for s in self._sessions.values())
        return {"queued": queued, "active": active,
                "in_flight_bytes": inflight,
                "queue_depth": self.queue_depth,
                "workers": self.workers,
                "breaker": self.executor.health.breaker.state}
