"""Device health monitor + circuit breaker for the plan/op surface.

The fault injector exists to prove one thing: the framework STOPS retrying
on a dead device (faultinj/README.md:6-16 and `spark_rapids_tpu.faultinj`'s
fatal tier). This module is the production half of that story — it turns
raw failures from the executor into a *policy*:

- **transient** — an injected nonfatal assert, a substituted return code,
  or a `RetryOOM` pressure spike. Worth retrying, but only with jittered
  exponential backoff and only while the plan attempt's shared retry
  *budget* lasts (no retry storms).
- **sticky** — the same operator keeps failing inside a time window, or
  the retry budget / per-op retry bound is exhausted. The device may be
  fine but this workload on it is not; stop hammering it.
- **fatal** — `DeviceFatalError`: the device is poisoned until
  `reset_device()`. Never retried (the whole point of the fatal tier).

Sticky and fatal failures **trip the circuit breaker**:

    closed ── sticky/fatal ──▶ open ── reset_device() ─────▶ half_open
      ▲                         ▲ │      or cooldown_s elapsed   │
      └───── probe succeeds ────┼─┴───────── probe fails ────────┘

While the breaker is open the device is quarantined — the plan executor
routes work to the degraded CPU tier instead (plan/executor.py). The
breaker arms HALF_OPEN either when the operator intervenes
(`reset_device()`, the executor-restart analogue) or on its own once
`cooldown_s` has elapsed since the trip (quarantine is never permanent: a
passed pressure burst or recovered device is re-discovered automatically);
the next admission then runs a cheap heartbeat probe op through the same
faultinj-intercepted surface — success closes the breaker, failure
re-opens it and restarts the cooldown.

Health metrics drain with get-and-reset semantics like the arbiter's
(`ResourceArbiter.get_and_reset_num_retry_throw`): `get_and_reset_metrics()`
returns the counters accumulated since the previous call and zeroes them.

Multi-tenant keying (runtime/sessionctx.py, docs/serving.md): the
SESSION the work belongs to — the explicit id installed by
`sessionctx.session_scope` (the serving dispatcher wraps every job in
one), falling back to thread identity when unscoped — keys the failure
state. Thread keying alone aliased tenants the moment the serving layer
multiplexed sessions over worker threads: one pathological tenant's
failures would drain the budget — or arm the sticky window — of whoever
landed on that thread next. Sticky windows key per (session, op);
retry budgets per (session, thread), so one tenant's concurrent plans
on different workers stay independently bounded per plan attempt. The
breaker itself stays DEVICE-scoped: a fatal fault poisons the device
for every session, whoever triggered it.

Co-processing precedent: treating the CPU as a second execution tier is
how coupled CPU-GPU systems keep serving under device loss ("Revisiting
Co-Processing for Hash Joins on the Coupled CPU-GPU Architecture",
"Accelerating Presto with GPUs" — PAPERS.md).

Knobs (read at monitor construction, `SPARK_RAPIDS_TPU_BREAKER_*` —
config.py): retry budget, backoff base/max, sticky threshold/window,
degrade policy.
"""
from __future__ import annotations

import collections
import random
import threading
import time
from typing import Callable, Deque, Dict, List, Optional

# breaker states
CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"

# failure classifications
TRANSIENT = "transient"
STICKY = "sticky"
FATAL = "fatal"


def device_probe() -> bool:
    """Cheap heartbeat: one tiny device computation, routed through the
    faultinj interception surface (key "health.probe", also matched by `*`
    rules) so a poisoned device fails the probe exactly like a real op."""
    from .. import faultinj
    inj = faultinj.active()
    if inj is not None:
        inj.on_compute("health.probe")
    import jax
    import jax.numpy as jnp
    x = jnp.arange(8, dtype=jnp.int32)
    return int(jax.block_until_ready(jnp.sum(x))) == 28


class CircuitBreaker:
    """closed → open → half_open state machine over one device.

    `trip()` opens it (quarantine); `half_open()` is the reset_device
    lifecycle hook arming a probation period immediately; an OPEN breaker
    also self-arms HALF_OPEN once `cooldown_s` has elapsed since the trip,
    so a quarantine is never permanent — a device that recovered (or a
    pressure burst that passed) is re-discovered by the next admission
    without operator intervention. `probe()` runs the heartbeat and closes
    (success) or re-opens (failure, restarting the cooldown clock).

    `admit()` is the gate: closed admits, open refuses (until cooldown),
    half_open probes. `DeviceHealthMonitor.admit()` is the same gate with
    probe metrics counted — the state transitions live only here."""

    def __init__(self, probe: Optional[Callable[[], bool]] = None,
                 cooldown_s: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        from .. import config
        self._probe = probe or device_probe
        self.cooldown_s = (config.breaker_cooldown_s()
                           if cooldown_s is None else cooldown_s)
        self._clock = clock
        self._lock = threading.Lock()
        self._state = CLOSED
        self._opened_at = 0.0
        self.trips = 0
        self.last_trip_reason: Optional[str] = None
        self.last_trip_error: Optional[str] = None

    @property
    def state(self) -> str:
        return self._state

    def trip(self, reason: str, detail: Optional[str] = None) -> None:
        with self._lock:
            self._state = OPEN
            self._opened_at = self._clock()
            self.trips += 1
            self.last_trip_reason = reason
            self.last_trip_error = detail

    def half_open(self) -> None:
        with self._lock:
            if self._state == OPEN:
                self._state = HALF_OPEN

    def maybe_cooldown(self) -> None:
        """Arm HALF_OPEN when an OPEN breaker's cooldown has elapsed
        (cooldown_s <= 0 disables: quarantine until reset_device())."""
        with self._lock:
            if (self._state == OPEN and self.cooldown_s > 0
                    and self._clock() - self._opened_at >= self.cooldown_s):
                self._state = HALF_OPEN

    def probe(self) -> bool:
        try:
            ok = bool(self._probe())
        except Exception:
            ok = False
        with self._lock:
            if ok:
                self._state = CLOSED
            else:
                self._state = OPEN
                self._opened_at = self._clock()   # restart the cooldown
        return ok

    def admit(self, probe: Optional[Callable[[], bool]] = None) -> bool:
        """ONE admission gate: closed admits, open refuses (until the
        cooldown arms half_open), half_open probes. `probe` overrides the
        probe call so callers can route it through counted wrappers
        (DeviceHealthMonitor.admit) without duplicating this dispatch."""
        self.maybe_cooldown()
        if self._state == CLOSED:
            return True
        if self._state == HALF_OPEN:
            return (probe or self.probe)()
        return False


class DeviceHealthMonitor:
    """Classifies device failures and owns the breaker + retry policy.

    One monitor guards one device (a PlanExecutor creates its own by
    default). Injectable `sleep`/`clock`/`rng`/`probe` keep tests fast and
    deterministic."""

    def __init__(self, *,
                 retry_budget: Optional[int] = None,
                 backoff_base_ms: Optional[float] = None,
                 backoff_max_ms: Optional[float] = None,
                 sticky_threshold: Optional[int] = None,
                 sticky_window_s: Optional[float] = None,
                 cooldown_s: Optional[float] = None,
                 probe: Optional[Callable[[], bool]] = None,
                 sleep: Callable[[float], None] = time.sleep,
                 clock: Callable[[], float] = time.monotonic,
                 rng: Optional[random.Random] = None,
                 worker_id: str = ""):
        from .. import config
        # fleet worker identity (serving/fleet.py): one monitor guards
        # one worker's device, so breaker snapshots carry WHOSE breaker
        # tripped — "" outside a fleet
        self.worker_id = str(worker_id)
        self.retry_budget = (config.breaker_retry_budget()
                             if retry_budget is None else retry_budget)
        self.backoff_base_ms = (config.breaker_backoff_base_ms()
                                if backoff_base_ms is None else backoff_base_ms)
        self.backoff_max_ms = (config.breaker_backoff_max_ms()
                               if backoff_max_ms is None else backoff_max_ms)
        self.sticky_threshold = (config.breaker_sticky_threshold()
                                 if sticky_threshold is None else sticky_threshold)
        self.sticky_window_s = (config.breaker_sticky_window_s()
                                if sticky_window_s is None else sticky_window_s)
        self.breaker = CircuitBreaker(probe=probe, cooldown_s=cooldown_s,
                                      clock=clock)
        self._sleep = sleep
        self._clock = clock
        self._rng = rng or random.Random()
        self._lock = threading.Lock()
        # retry budget is per plan attempt, keyed by (session, thread)
        # (sessionctx.session_key x executing thread): the session
        # component stops two tenants multiplexed over one serving worker
        # thread from sharing one bound, while the thread component keeps
        # ONE tenant's concurrent plans on different workers independently
        # bounded — a same-tenant neighbour's start_plan_attempt() must
        # not refill (or its retries starve) this plan's budget mid-plan.
        # Bounded: dead sessions' residue must not grow the monitor
        # forever. The bound errs on the soft side — an evicted live
        # entry refills on the next try_retry — so it sits far above any
        # plausible in-flight count: keys are created only by
        # start_plan_attempt/try_retry, one per concurrently executing
        # plan per thread, and 8192 distinct keys would have to churn
        # through DURING one plan's backoff sleep to soften its bound.
        from ..utils.lru import LruDict
        self._budgets: Dict[tuple, int] = LruDict(8192)
        self._failures: Dict[tuple, Deque[float]] = {}
        self._reset_hooks: List[Callable[[], None]] = []
        self._metrics: Dict[str, float] = collections.defaultdict(float)
        # trip attribution (serving/fleet.py poison quarantine): the
        # fingerprint of the plan executing on THIS thread when a trip
        # lands — thread-local, because the serving dispatcher runs
        # several tenants' plans concurrently through one monitor.
        # Bounded log, drained by the fleet with get-and-reset semantics
        # like the metrics counters.
        self._attr = threading.local()
        self._trip_log: Deque[tuple] = collections.deque(maxlen=64)

    # ---- classification ----------------------------------------------------

    def record_failure(self, op: str, exc: BaseException) -> str:
        """Record one failure of `op` and classify it. Fatal faults classify
        immediately; otherwise stickiness is N failures of the SAME op
        UNDER THE SAME SESSION within the window (old entries age out) —
        tenant A's flaky operator must not arm a sticky trip against
        tenant B's first failure of the same op."""
        from .. import faultinj
        from . import sessionctx
        now = self._clock()
        with self._lock:
            if isinstance(exc, faultinj.DeviceFatalError):
                self._metrics["fatal_faults"] += 1
                return FATAL
            dq = self._failures.setdefault((sessionctx.session_key(), op),
                                           collections.deque())
            dq.append(now)
            while dq and now - dq[0] > self.sticky_window_s:
                dq.popleft()
            if len(self._failures) > 4096:
                # dead-session residue: windows whose every entry has aged
                # out carry no sticky evidence — drop them instead of
                # growing per (session, op) forever
                self._failures = {
                    k: d for k, d in self._failures.items()
                    if d and now - d[-1] <= self.sticky_window_s}
            if len(dq) >= self.sticky_threshold:
                self._metrics["sticky_faults"] += 1
                return STICKY
            self._metrics["transient_faults"] += 1
            return TRANSIENT

    def record_success(self, op: str) -> None:
        """A unit that eventually SUCCEEDED proves its faults were not
        sticky: clear the op's failure window (for the session that ran
        it) so occasional absorbed transients (one per job, say) never
        accumulate across executions into a quarantine of a device that
        recovers every time. Sticky therefore means: repeated failures
        with no intervening success."""
        from . import sessionctx
        with self._lock:
            dq = self._failures.get((sessionctx.session_key(), op))
            if dq:
                dq.clear()

    # ---- retry budget + backoff --------------------------------------------

    def _budget_key(self) -> tuple:
        from . import sessionctx
        return (sessionctx.session_key(), threading.get_ident())

    def start_plan_attempt(self) -> None:
        """Refill this plan attempt's retry budget (keyed by session x
        thread — see __init__: tenants never alias across a shared
        worker thread, and one tenant's concurrent plans never refill or
        starve each other's bound mid-plan)."""
        with self._lock:
            self._budgets[self._budget_key()] = self.retry_budget

    def try_retry(self, attempt: int) -> Optional[float]:
        """Consume one unit of the plan attempt's retry budget and sleep a
        jittered exponential backoff for retry number `attempt` (0-based).
        Returns the milliseconds slept, or None when the budget is
        exhausted (the caller must escalate, not retry)."""
        key = self._budget_key()
        with self._lock:
            budget = self._budgets.get(key)
            if budget is None:
                budget = self.retry_budget
            if budget <= 0:
                self._metrics["budget_exhausted"] += 1
                return None
            self._budgets[key] = budget - 1
        delay_ms = min(self.backoff_max_ms,
                       self.backoff_base_ms * (2 ** attempt))
        delay_ms *= self._rng.uniform(0.5, 1.0)   # jitter: decorrelate peers
        self._sleep(delay_ms / 1e3)
        with self._lock:
            self._metrics["retries"] += 1
            self._metrics["backoff_ms"] += delay_ms
        return delay_ms

    # ---- breaker lifecycle -------------------------------------------------

    def attribution(self, fingerprint: str):
        """Context manager installing `fingerprint` as the CURRENT
        THREAD's trip attribution: a breaker trip landing inside the
        scope logs (fingerprint, reason) for the fleet's poison-plan
        quarantine (serving/fleet.py — a fingerprint that trips breakers
        on >= 2 distinct workers is the crash amplifier auto-respawn
        must not keep feeding). The serving dispatcher wraps every
        execution in one; unattributed trips log fingerprint ""."""
        import contextlib

        @contextlib.contextmanager
        def _scope():
            prev = getattr(self._attr, "fp", "")
            self._attr.fp = str(fingerprint)
            try:
                yield
            finally:
                self._attr.fp = prev
        return _scope()

    def drain_trips(self) -> List[tuple]:
        """Drain the attributed-trip log — `[(fingerprint, reason),
        ...]` since the last drain (get-and-reset, like the metrics
        counters). The fleet absorbs these on every submit and before
        every worker removal, so a dying worker's attributions are
        collected before its stack is torn down."""
        with self._lock:
            out = list(self._trip_log)
            self._trip_log.clear()
        return out

    def trip(self, reason: str, exc: Optional[BaseException] = None) -> None:
        # the underlying error rides the snapshot: a degraded nightly run
        # must say WHICH failure tripped it, not just the classification
        detail = None if exc is None else f"{type(exc).__name__}: {exc}"[:300]
        self.breaker.trip(reason, detail=detail)
        with self._lock:
            self._metrics["trips"] += 1
            self._metrics[f"{reason}_trips"] += 1
            self._trip_log.append(
                (getattr(self._attr, "fp", ""), reason))

    def probe(self) -> bool:
        ok = self.breaker.probe()
        with self._lock:
            self._metrics["probes"] += 1
            if not ok:
                self._metrics["probe_failures"] += 1
            else:
                # recovery (probed closed) restarts every stickiness window,
                # exactly like reset_device(): pre-trip failures must not
                # instantly re-trip the just-recovered device
                self._failures.clear()
        return ok

    def admit(self) -> bool:
        """The executor's device-admission gate: the breaker's single
        dispatch with the half-open probe routed through the counted
        `probe()` wrapper."""
        return self.breaker.admit(probe=self.probe)

    def note_degraded_plan(self) -> None:
        with self._lock:
            self._metrics["degraded_plans"] += 1

    def add_reset_hook(self, fn: Callable[[], None]) -> None:
        """Register a callable run by reset_device() (e.g. re-initializing a
        client) — the quarantine-exit lifecycle hook."""
        self._reset_hooks.append(fn)

    def reset_device(self) -> None:
        """Executor-restart analogue: clear the injector's poisoned-device
        state, run the registered lifecycle hooks, and arm the breaker
        HALF_OPEN so the next admission probes before trusting the device."""
        from .. import faultinj
        inj = faultinj.active()
        if inj is not None:
            inj.reset_device()
        for fn in self._reset_hooks:
            fn()
        with self._lock:
            # pre-recovery failures must not re-trip the breaker: the reset
            # starts a fresh stickiness window for every operator
            self._failures.clear()
        self.breaker.half_open()

    # ---- metrics -----------------------------------------------------------

    def get_and_reset_metrics(self) -> Dict[str, float]:
        """Drain the health counters (arbiter-style get-and-reset)."""
        with self._lock:
            snap = dict(self._metrics)
            self._metrics.clear()
        return snap
