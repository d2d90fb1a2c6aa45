"""Fleet serving tier: a router fronting N executor workers with
failover (docs/serving.md#fleet).

The reference deployment is one coordinator over many per-device JNI
executors (PAPER.md), and "Accelerating Presto with GPUs" converges on
the same two-level split for GPU SQL serving. PR 15's
`ServingScheduler` solved many-tenants-one-device; this module scales
it out: `FleetScheduler` owns N `FleetWorker`s — each a full
single-worker serving stack (its own `PlanExecutor` + device, its own
`DeviceHealthMonitor`/breaker, its own `StatsStore`, its own
`ResultCache`) — and routes every submission by three rules, in
precedence order:

1. **session affinity** — a session with work still in flight on its
   pinned worker stays there: retry budgets and sticky-failure windows
   key on (session, worker) and a mid-plan re-home would reset them;
2. **consistent hashing on the canonical plan fingerprint**
   (serving/router.py) — the same plan lands on the same worker run
   after run, so that worker's result cache / stats store / compiled
   programs stay warm for it, and the mapping survives worker
   join/leave with only ~1/n of the keyspace moving;
3. **load-aware spillover** — when the routed worker's pressure score
   (queued + active work, breaker state; `ServingScheduler.pressure()`)
   exceeds `SPARK_RAPIDS_TPU_FLEET_SPILL_RATIO` x the least-loaded
   worker's, the submission sheds to that worker instead of queueing
   unboundedly behind a hot spot — locality is a preference, overload
   is a fact.

**Failover.** `kill_worker()` (deliberate kill, the chaos soak's move)
and `reap_unhealthy()` (breaker stuck OPEN with no cooldown) mark a
worker dead, remove it from the ring, fail its queued jobs, and REPLAY
every incomplete tracked submission on a surviving worker. Execution is
deterministic and side-effect-free, so a replay returns the bit-exact
result the dead worker would have — the soak asserts per-session parity
against solo execution. `FleetTicket.result()` also self-heals: a
ticket that surfaces the dead worker's typed `closed` rejection
re-routes itself instead of failing the tenant.

**Cache promotion.** Affinity and spillover divert computations off
their ring home, so the home worker's cache can lack results the fleet
already paid for. On a routed submission the router checks the routed
worker's cache; on a would-miss it adopts a peer's frozen entry
(`ResultCache.peek_frozen`/`adopt` — a dict slot, not a table copy).
The served copy keeps the COMPUTING worker's stamp while the fleet
ticket names the SERVING worker — when they differ, consistent-hash
locality (not luck) produced the hit.

**Invalidation bus.** Worker caches are per-worker, so a source input
whose digest changes on resubmit would keep serving stale results from
OTHER workers' caches (the submitting worker naturally misses — its key
includes the digest). The fleet tracks the last digest seen per plan
fingerprint; on change it publishes an invalidation to every worker:
`ResultCache.invalidate_fingerprint` (old-digest entries only — the
new-digest entry stays sound) and `StatsStore.forget_plan` (observed
sizes describe data that no longer exists). The bus only runs with >1
live worker: one worker's digest-keyed cache is already coherent by
itself, and single-worker behavior must stay byte-identical to the
plain scheduler.

**Self-healing** (docs/serving.md#fleet-self-healing). Failover alone
shrinks the fleet: every kill/reap permanently loses a worker's
capacity. With `SPARK_RAPIDS_TPU_FLEET_RESPAWN=on` the fleet heals
itself back to its target size:

- **auto-respawn** — after a kill, reap, or drain the fleet spawns a
  replacement worker with a fresh isolated stack and a NEW monotonic id
  (ids are never reused: quarantine counts trips per worker
  *incarnation*, and a name-recycling respawn would alias the dead
  worker's history onto the newborn), gated by a lifetime budget
  (`_RESPAWN_MAX`) and an exponential backoff (`_RESPAWN_BACKOFF_MS`)
  so a crash-looping root cause cannot churn workers forever;
- **poison-plan quarantine** — breaker trips are attributed to the
  fingerprint that fired them (`DeviceHealthMonitor.attribution`); a
  fingerprint that tripped breakers on >= 2 DISTINCT workers is
  quarantined fleet-wide — rejected with a typed error or pinned to the
  CPU tier per `_FLEET_QUARANTINE`. This check runs BEFORE respawn
  logic on purpose: respawning workers under a poison plan without
  quarantining it is a crash amplifier (each newborn dies the same way);
- **graceful drain** — `drain_worker()` stops new routing immediately,
  lets in-flight work finish under a deadline, then removes the worker
  and replays only the stragglers (`failover_reason == "drained"`);
- **warm failover** — HOT fingerprints (>= 2 observed runs AND top-K by
  run count) replicate their frozen cache entries to the next
  `_FLEET_HOT_REPLICAS` distinct ring successors, and the stats stores
  gossip observed caps / high-water bytes to every survivor on worker
  death and to every newborn on respawn — so a failover rehome serves
  the replica (or compiles ONCE, `attempts == 1`) and charges observed
  bytes immediately instead of re-learning the plan from scratch.

A background sweep (`_FLEET_SWEEP_MS > 0`) runs reap + respawn
periodically so healing does not wait for the next submission.

With `SPARK_RAPIDS_TPU_FLEET_WORKERS=1` (the default) the fleet is one
worker and every routing rule degenerates to "that worker" — serving
behavior is the single-worker `ServingScheduler` path, regression-held
byte-identical by tests/test_fleet.py.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Set

from . import cache as cache_mod
from .router import HashRing
from .scheduler import (PRIORITIES, ServingRejectedError, ServingScheduler,
                        Ticket)

__all__ = ["FleetScheduler", "FleetSession", "FleetTicket", "FleetWorker"]

# pressure-score penalty for a non-closed breaker: a worker whose device
# is quarantined can still serve (CPU-degraded), but routing NEW work at
# it when healthy replicas exist is self-harm
_BREAKER_PENALTY = 1000.0


class FleetWorker:
    """One executor worker: a full single-worker serving stack under a
    worker id. Every layer is worker-scoped on purpose — a breaker trip,
    a poisoned stats entry, or a cache eviction storm on one worker must
    never bleed into its replicas (failure isolation is the point of
    having replicas)."""

    def __init__(self, worker_id: str, *, scheduler_kwargs=None):
        from ..plan.executor import PlanExecutor
        from ..plan.stats import StatsStore
        from ..runtime.health import DeviceHealthMonitor
        self.id = worker_id
        self.health = DeviceHealthMonitor(worker_id=worker_id)
        self.executor = PlanExecutor(mode="eager", health=self.health,
                                     worker_id=worker_id)
        # path="": a worker's observations are its own — N workers
        # replaying one persisted JSONL would each double-count it
        self.stats = StatsStore(path="")
        self.scheduler = ServingScheduler(self.executor,
                                          stats_store=self.stats,
                                          **(scheduler_kwargs or {}))
        self.alive = True
        # draining: still alive (finishing in-flight work) but no NEW
        # routing — the half-state graceful drain needs that kill lacks
        self.draining = False

    # The gossip surface: every cross-worker stats reach goes through
    # these wrappers so the isolation linter (tools/lint_concurrency.py)
    # can sanction the worker's OWN surface instead of allowlisting raw
    # `w.stats.*` reaches all over fleet.py.

    def drain_trips(self):
        """Get-and-reset the health monitor's attributed trip log —
        (fingerprint, reason) pairs the quarantine logic consumes."""
        return self.health.drain_trips()

    def gossip_export(self, fps=None):
        """This worker's observed plan rows (caps, high-water bytes,
        run counts) for merging into peers on death/drain/respawn."""
        return self.stats.export_plans(fps)

    def gossip_merge(self, rows) -> int:
        """High-water merge of peer observations into this worker's
        stats store; idempotent, returns the number of rows changed."""
        return self.stats.merge_plans(rows)

    def pressure_score(self) -> float:
        """Scalar load rank for the router: queued + active work, plus a
        large penalty when the breaker is not closed. Cheap by contract
        — this runs on every routed submission."""
        p = self.scheduler.pressure()
        score = float(p["queued"] + p["active"])
        if p["breaker"] != "closed":
            score += _BREAKER_PENALTY
        return score


class FleetTicket:
    """A submission's fleet-level handle. Wraps the current worker-level
    `Ticket` and re-routes itself through `FleetScheduler._replay` when
    the worker serving it dies — the tenant sees one ticket with one
    result, whatever happened underneath. `worker` names the worker that
    SERVED the result; `result().worker` (stamped by the executor) names
    the one that COMPUTED it, which differs exactly when a consistent-
    hash cache hit served another worker's computation."""

    def __init__(self, fleet: "FleetScheduler", sid: str, plan, inputs):
        self._fleet = fleet
        self.session = sid
        self.plan = plan
        self.inputs = inputs
        self.worker = ""                # serving worker id
        self.replays = 0
        # why this ticket ever left its first worker: "" (never did),
        # "killed" / "reaped" / "drained" (proactive fleet failover) or
        # "self_heal" (result() discovered the death itself)
        self.failover_reason = ""
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._inner: Optional[Ticket] = None
        self._inner_worker = ""
        self._failed: Optional[BaseException] = None
        self._replaying = False

    def _bind(self, inner: Ticket, worker_id: str) -> None:
        with self._lock:
            self._inner = inner
            self._inner_worker = worker_id
            self.worker = worker_id
            inner.worker = worker_id
            self._cond.notify_all()
        # register OUTSIDE the ticket lock: an already-completed inner
        # invokes the callback inline, and _wake re-takes the lock
        inner.add_done_callback(self._wake)

    def _wake(self, _inner) -> None:
        """Done-callback from the CURRENT (or a superseded) inner
        ticket: wake result() waiters. Spurious wakeups from a stale
        inner are harmless — the waiter re-checks under the lock."""
        with self._lock:
            self._cond.notify_all()

    def _current(self):
        with self._lock:
            return self._inner, self._inner_worker

    def _fail(self, err: BaseException) -> None:
        """Terminal failure, under the ticket lock — `done()`/`result()`
        read `_failed` under the same lock, so a lock-free write here
        (the pre-lockdep bug) could be reordered past a concurrent
        `done()` that already answered False and will never re-poll."""
        with self._lock:
            self._failed = err
            self._cond.notify_all()

    def done(self) -> bool:
        with self._lock:
            if self._failed is not None:
                return True
            inner = self._inner
        return inner is not None and inner.done()

    @property
    def queue_wait_ms(self) -> float:
        inner, _ = self._current()
        return 0.0 if inner is None else inner.queue_wait_ms

    @property
    def cached(self) -> bool:
        inner, _ = self._current()
        return False if inner is None else inner.cached

    @property
    def charge_source(self) -> str:
        inner, _ = self._current()
        return "" if inner is None else inner.charge_source

    def result(self, timeout: Optional[float] = None):
        """Block for the outcome, transparently surviving worker death:
        a typed `closed` rejection from a worker the fleet knows is dead
        replays on a survivor instead of raising.

        Waits on a condition the inner ticket's done-callback notifies
        (`_wake`, re-armed on every re-bind) — completion wakes the
        waiter immediately instead of on the next slot of a fixed poll
        loop. The bounded wait slice below is insurance against a
        missed edge, not the wakeup mechanism."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        while True:
            with self._lock:
                if self._failed is not None:
                    raise self._failed
                inner, wid = self._inner, self._inner_worker
                if inner is None or not inner.done():
                    remaining = (None if deadline is None
                                 else deadline - time.monotonic())
                    if remaining is not None and remaining <= 0:
                        raise TimeoutError(
                            f"fleet ticket [session={self.session}] not "
                            f"complete after {timeout}s")
                    self._cond.wait(1.0 if remaining is None
                                    else min(1.0, remaining))
                    continue
            # harvest OUTSIDE the ticket lock: result(0) cannot block
            # (inner.done() above), and the self-heal path below takes
            # fleet-level locks the ticket lock must never sit under
            try:
                return inner.result(0)
            except TimeoutError:
                continue        # raced with a re-bind: re-check
            except ServingRejectedError as e:
                if e.reason == "closed" and \
                        not self._fleet._worker_alive(wid):
                    with self._lock:
                        if not self.failover_reason:
                            self.failover_reason = "self_heal"
                    self._fleet._replay(self)
                    continue
                raise


class _SessRec:
    """Fleet-side per-session record (guarded by the fleet lock):
    open-session parameters (replayed onto every worker the session
    touches), the affinity pin, and the in-flight tickets failover must
    replay."""

    def __init__(self, sid: str, weight: float, priority: str,
                 quota_bytes: Optional[int]):
        self.id = sid
        self.weight = weight
        self.priority = priority
        self.quota_bytes = quota_bytes
        self.affinity: Optional[str] = None
        self.handles: Dict[str, object] = {}   # worker id -> ServingSession
        self.tickets: Set[FleetTicket] = set()
        self.closed = False


class FleetSession:
    """One tenant's handle onto the fleet — same surface as
    `ServingSession` (submit/run/close, context manager), with the
    routing hidden behind it."""

    def __init__(self, fleet: "FleetScheduler", rec: _SessRec):
        self._fleet = fleet
        self._rec = rec
        self.id = rec.id

    def submit(self, plan, inputs: Optional[Dict] = None, *,
               block: Optional[bool] = None,
               timeout: Optional[float] = None) -> FleetTicket:
        return self._fleet._submit(self._rec, plan, inputs,
                                   block=block, timeout=timeout)

    def run(self, plan, inputs: Optional[Dict] = None, *,
            block: Optional[bool] = None,
            timeout: Optional[float] = None):
        t0 = time.monotonic()
        ticket = self.submit(plan, inputs, block=block, timeout=timeout)
        remaining = (None if timeout is None
                     else max(0.0, timeout - (time.monotonic() - t0)))
        return ticket.result(remaining)

    def close(self) -> None:
        self._fleet._close_session(self._rec)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class FleetScheduler:
    """The router tier: N workers, one front door.

    `open_session()` mirrors `ServingScheduler.open_session` and returns
    a `FleetSession`; every knob parameter not listed here passes
    through to each worker's `ServingScheduler` via
    `scheduler_kwargs`."""

    def __init__(self, workers: Optional[int] = None, *,
                 ring_replicas: Optional[int] = None,
                 spill_ratio: Optional[float] = None,
                 respawn: Optional[bool] = None,
                 respawn_max: Optional[int] = None,
                 respawn_backoff_ms: Optional[float] = None,
                 quarantine: Optional[str] = None,
                 hot_replicas: Optional[int] = None,
                 hot_k: Optional[int] = None,
                 sweep_ms: Optional[float] = None,
                 scheduler_kwargs: Optional[Dict] = None):
        from .. import config
        n = (config.fleet_workers() if workers is None
             else max(1, int(workers)))
        self.spill_ratio = (config.fleet_spill_ratio() if spill_ratio
                            is None else float(spill_ratio))
        # self-healing knobs (docs/serving.md#fleet-self-healing)
        self.respawn = (config.fleet_respawn() if respawn is None
                        else bool(respawn))
        self.respawn_max = (config.fleet_respawn_max() if respawn_max
                            is None else max(0, int(respawn_max)))
        self.respawn_backoff_ms = (
            config.fleet_respawn_backoff_ms() if respawn_backoff_ms
            is None else max(0.0, float(respawn_backoff_ms)))
        self.quarantine_policy = (config.fleet_quarantine()
                                  if quarantine is None else quarantine)
        if self.quarantine_policy not in ("reject", "degrade"):
            raise ValueError(
                f"quarantine policy must be 'reject' or 'degrade', "
                f"got {self.quarantine_policy!r}")
        self.hot_replicas = (config.fleet_hot_replicas() if hot_replicas
                             is None else max(0, int(hot_replicas)))
        self.hot_k = (config.fleet_hot_k() if hot_k is None
                      else max(0, int(hot_k)))
        self.sweep_ms = (config.fleet_sweep_ms() if sweep_ms is None
                         else max(0.0, float(sweep_ms)))
        self._lock = threading.Lock()
        self._workers: Dict[str, FleetWorker] = {}
        self._ring = HashRing(replicas=ring_replicas)
        self._sessions: Dict[str, _SessRec] = {}
        self._closed = False
        # invalidation bus state: last input digest seen per fingerprint
        from ..utils.lru import LruDict
        self._digests: Dict[str, str] = LruDict(4096)
        # self-healing state: the size auto-respawn heals back to, the
        # monotonic worker-id counter (ids are NEVER reused — quarantine
        # counts trips per distinct worker incarnation), the poison map
        # (fingerprint -> worker ids whose breakers it tripped), the
        # quarantine set, the respawn rate-limit clock, and the router-
        # side run counter hot replication ranks fingerprints by
        self.target_workers = n
        self._widx = n
        self._poison: Dict[str, Set[str]] = LruDict(512)
        self._quarantined: Dict[str, str] = LruDict(256)
        self._respawn_last = 0.0
        self._respawn_streak = 0
        self._fp_runs: Dict[str, int] = LruDict(4096)
        # observability counters
        self.routes_affinity = 0
        self.routes_ring = 0
        self.routes_spill = 0
        self.failovers = 0
        self.replayed_jobs = 0
        self.bus_publishes = 0
        self.cache_promotions = 0
        self.killed = 0
        self.reaped = 0
        self.drained = 0
        self.respawned = 0
        self.respawn_deferred = 0
        self.replications = 0
        self.gossips = 0
        self.quarantine_hits = 0
        for i in range(n):
            self._add_worker_locked(f"w{i}",
                                    scheduler_kwargs=scheduler_kwargs)
        self._scheduler_kwargs = dict(scheduler_kwargs or {})
        # background health sweep: reap stuck-OPEN breakers and top the
        # fleet back up without waiting for the next submission to
        # trigger healing (0 = off; tests drive healing synchronously)
        self._sweep_stop = threading.Event()
        self._sweep_thread: Optional[threading.Thread] = None
        if self.sweep_ms > 0:
            self._sweep_thread = threading.Thread(
                target=self._sweep_loop, name="fleet-sweep", daemon=True)
            self._sweep_thread.start()

    # ---- membership --------------------------------------------------------

    def _add_worker_locked(self, wid: str, *, scheduler_kwargs=None):
        w = FleetWorker(wid, scheduler_kwargs=scheduler_kwargs)
        self._workers[wid] = w
        self._ring.add(wid)
        return w

    def _next_wid_locked(self) -> str:
        """Monotonic, never-reused worker id. Reusing a dead worker's
        name would alias its incarnation in the poison map — a respawn
        that 'inherits' the trips of the corpse it replaced would
        quarantine fingerprints off one worker's evidence."""
        wid = f"w{self._widx}"
        self._widx += 1
        return wid

    def add_worker(self) -> str:
        """Scale out by one worker (join): only ~1/n of the fingerprint
        keyspace re-homes onto it. Raises the self-healing target size
        — the fleet now heals back to the larger fleet."""
        with self._lock:
            if self._closed:
                raise ServingRejectedError("closed", "fleet is shut down")
            wid = self._next_wid_locked()
            self._add_worker_locked(
                wid, scheduler_kwargs=self._scheduler_kwargs)
            self.target_workers += 1
        return wid

    def _worker_alive(self, wid: str) -> bool:
        with self._lock:
            w = self._workers.get(wid)
            return w is not None and w.alive

    def _live_workers_locked(self) -> List[FleetWorker]:
        return [w for w in self._workers.values() if w.alive]

    def _routable_locked(self) -> List[FleetWorker]:
        """Workers new submissions may land on: alive and not draining
        (a draining worker still finishes its in-flight work — it is
        live for gossip and the invalidation bus, dead for routing)."""
        return [w for w in self._workers.values()
                if w.alive and not w.draining]

    def kill_worker(self, wid: str, *, _cause: str = "killed") -> int:
        """Deliberate worker death (the chaos soak's kill-mid-storm):
        remove from the ring, fail its queue, replay every incomplete
        tracked submission on a survivor. Returns the number of
        in-flight jobs failed over — a job that manages to FINISH on
        the dying worker during the drain keeps that result and is not
        re-submitted (`metrics()["replayed_jobs"]` counts actual
        re-submissions). In-execution jobs whose tickets were already
        re-bound discard the late result (first-completion-wins is
        safe: execution is deterministic, both completions are the
        same bytes).

        Before the worker disappears the fleet (1) absorbs its
        attributed breaker trips into the poison map — the incarnation
        dies, its evidence does not — and (2) gossips its stats-store
        observations to every survivor, so rehomed fingerprints charge
        observed bytes (and skip compile churn) wherever they land.
        With respawn enabled a replacement is spawned afterward."""
        with self._lock:
            w = self._workers.get(wid)
            if w is None or not w.alive:
                return 0
            routable = self._routable_locked()
            if w in routable and len(routable) <= 1:
                raise ValueError(
                    f"cannot kill {wid}: it is the last live worker")
            self._absorb_trips_locked(w)
            rows = w.gossip_export()
            if rows:
                for peer in self._live_workers_locked():
                    if peer is not w:
                        peer.gossip_merge(rows)
                        self.gossips += 1
            w.alive = False
            self._ring.remove(wid)
            self.failovers += 1
            if _cause == "reaped":
                self.reaped += 1
            else:
                self.killed += 1
            orphans: List[FleetTicket] = []
            for rec in self._sessions.values():
                if rec.affinity == wid:
                    rec.affinity = None
                rec.handles.pop(wid, None)
                for t in list(rec.tickets):
                    if t.done():
                        rec.tickets.discard(t)
                    elif t._current()[1] == wid:
                        t.failover_reason = t.failover_reason or _cause
                        orphans.append(t)
        # close OUTSIDE the fleet lock: drain=False completes queued
        # tickets with the typed "closed" rejection (self-heal path) and
        # waits on active jobs — holding the lock here would stall every
        # route until the dead worker's in-flight work unwinds
        w.scheduler.close(drain=False, timeout=30.0)
        for t in orphans:
            self._replay(t)
        self._maybe_respawn()
        return len(orphans)

    def reap_unhealthy(self) -> List[str]:
        """Kill workers whose breaker is stuck OPEN with no cooldown to
        self-arm (cooldown_s <= 0): that worker will refuse device work
        until operator intervention, so its sessions fail over now. A
        breaker WITH a cooldown is left alone — it will half-open and
        probe by itself, and the CPU-degraded tier keeps serving
        meanwhile. Never kills the last live worker. Reaps count under
        `metrics()["reaped"]` (not `killed`), and with respawn enabled
        each reap spawns a replacement."""
        doomed = []
        with self._lock:
            for w in self._live_workers_locked():
                br = w.health.breaker
                if w.alive and br.state == "open" and br.cooldown_s <= 0:
                    doomed.append(w.id)
        out = []
        for wid in doomed:
            try:
                self.kill_worker(wid, _cause="reaped")
                out.append(wid)
            except ValueError:
                break               # last live worker: keep serving
        return out

    def drain_worker(self, wid: str,
                     timeout: Optional[float] = None) -> int:
        """Graceful decommission: stop routing NEW work at `wid`
        immediately (ring removal + affinity unpin), let its in-flight
        and queued work FINISH under `timeout`, then remove it and
        replay only the stragglers the deadline cut off
        (`failover_reason == "drained"`). The polite sibling of
        `kill_worker` — a planned node rotation should not throw away
        work the worker was mid-way through. Returns the number of
        stragglers replayed; with respawn enabled a replacement is
        spawned afterward."""
        with self._lock:
            w = self._workers.get(wid)
            if w is None or not w.alive or w.draining:
                return 0
            routable = self._routable_locked()
            if w in routable and len(routable) <= 1:
                raise ValueError(
                    f"cannot drain {wid}: it is the last live worker")
            w.draining = True
            self._ring.remove(wid)
            self._absorb_trips_locked(w)
            rows = w.gossip_export()
            if rows:
                for peer in self._routable_locked():
                    peer.gossip_merge(rows)
                    self.gossips += 1
            for rec in self._sessions.values():
                if rec.affinity == wid:
                    rec.affinity = None
        # drain OUTSIDE the fleet lock: this BLOCKS until the worker's
        # queue and active jobs finish (or the deadline) — the whole
        # point of drain over kill, and exactly why the lock can't be
        # held (every route would stall behind the drain)
        w.scheduler.close(drain=True, timeout=timeout)
        stragglers: List[FleetTicket] = []
        with self._lock:
            w.alive = False
            self.failovers += 1
            self.drained += 1
            for rec in self._sessions.values():
                rec.handles.pop(wid, None)
                for t in list(rec.tickets):
                    if t.done():
                        rec.tickets.discard(t)
                    elif t._current()[1] == wid:
                        t.failover_reason = t.failover_reason or "drained"
                        stragglers.append(t)
        for t in stragglers:
            self._replay(t)
        self._maybe_respawn()
        return len(stragglers)

    # ---- self-healing ------------------------------------------------------

    def _absorb_trips_locked(self, w: FleetWorker) -> None:
        """Drain `w`'s attributed breaker-trip log into the poison map
        and quarantine any fingerprint that has now tripped breakers on
        >= 2 DISTINCT worker incarnations. One worker tripping could be
        that worker's hardware; the same fingerprint wrecking two
        isolated stacks is the plan's fault — and with auto-respawn on,
        NOT quarantining it turns the healer into a crash amplifier
        (every replacement worker dies the same death)."""
        for fp, reason in w.drain_trips():
            if not fp:
                continue            # trip outside any attribution scope
            trippers = self._poison.get(fp)
            if trippers is None:
                trippers = set()
            trippers.add(w.id)
            self._poison[fp] = trippers     # (re)insert refreshes LRU
            if len(trippers) >= 2 and fp not in self._quarantined:
                self._quarantined[fp] = reason or "breaker"

    def quarantined(self) -> Dict[str, str]:
        """Snapshot of quarantined fingerprints -> trip reason."""
        with self._lock:
            return dict(self._quarantined)

    def _maybe_respawn(self) -> List[str]:
        """Top the fleet back up to `target_workers` (if respawn is
        enabled), within the lifetime budget and the exponential
        backoff. Each newborn gets the full gossip of every live peer's
        stats observations — it joins knowing every observed cap and
        high-water byte count the fleet has ever measured — and hot
        fingerprints re-replicate so its ring arc is warm. Deferred
        (budget- or backoff-blocked) attempts count under
        `respawn_deferred`; the sweep retries them."""
        spawned: List[str] = []
        while True:
            with self._lock:
                if self._closed or not self.respawn:
                    break
                if len(self._routable_locked()) >= self.target_workers:
                    break
                if self.respawned >= self.respawn_max:
                    self.respawn_deferred += 1
                    break
                now = time.monotonic()
                base = self.respawn_backoff_ms / 1e3
                # _respawn_last == 0.0 is the "never respawned" sentinel
                # (monotonic's epoch is arbitrary): the first respawn is
                # never backoff-gated
                if base > 0 and self._respawn_last > 0.0:
                    # a quiet fleet forgets its crash streak; a churning
                    # one doubles its wait (capped) so a crash-looping
                    # root cause cannot spin workers at full speed
                    if now - self._respawn_last > 16 * base:
                        self._respawn_streak = 0
                    wait = base * (2 ** self._respawn_streak)
                    if now - self._respawn_last < wait:
                        self.respawn_deferred += 1
                        break
                wid = self._next_wid_locked()
                w = self._add_worker_locked(
                    wid, scheduler_kwargs=self._scheduler_kwargs)
                self.respawned += 1
                self._respawn_last = now
                self._respawn_streak = min(self._respawn_streak + 1, 8)
                rows = []
                for peer in self._live_workers_locked():
                    if peer is not w:
                        rows.extend(peer.gossip_export())
                if rows:
                    w.gossip_merge(rows)
                    self.gossips += 1
                self._replicate_hot_locked()
                spawned.append(wid)
        return spawned

    def _hot_fps_locked(self) -> Set[str]:
        """Fingerprints worth replicating: >= 2 observed runs AND in
        the top-`hot_k` by run count — one-shot plans are not worth a
        replica slot, and K bounds replication work on wide traffic."""
        import heapq
        cand = [(n, fp) for fp, n in self._fp_runs.items() if n >= 2]
        return {fp for _, fp in heapq.nlargest(self.hot_k, cand)}

    def _replicate_locked(self, fp: str, digest: str) -> None:
        """Warm failover: copy the frozen cache entry for (fp, digest)
        onto the next `hot_replicas` distinct ring successors of `fp`'s
        primary. When the primary dies, the ring rehomes `fp` to
        exactly its first successor — which already holds the entry, so
        the failover serves a hit instead of recompiling. Entries are
        adopted frozen (shared, immutable) and TTL'd/invalidated like
        any other entry: the bus drops primary AND replicas together."""
        owners = self._ring.route_multi(fp, 1 + self.hot_replicas)
        if len(owners) < 2:
            return
        key = (fp, digest)
        ent, src = None, None
        for w in self._live_workers_locked():
            ent = w.scheduler.cache.peek_frozen(key)
            if ent is not None:
                src = w
                break
        if ent is None:
            return                  # nothing computed/cached yet
        for wid in owners[1:]:
            w = self._workers.get(wid)
            if w is None or not w.alive or w is src:
                continue
            if w.scheduler.cache.peek_frozen(key) is None:
                w.scheduler.cache.adopt(key, ent[0], ent[1])
                self.replications += 1

    def _replicate_hot_locked(self) -> None:
        """Re-derive replica placement for every hot fingerprint —
        membership changed (join/respawn), so ring successor sets
        changed with it (minimally: route_multi's walk)."""
        if self.hot_replicas <= 0 or self.hot_k <= 0:
            return
        for fp in self._hot_fps_locked():
            digest = self._digests.get(fp)
            if digest is not None:
                self._replicate_locked(fp, digest)

    def _sweep_loop(self) -> None:
        """Background health sweep: absorb trip logs (quarantine does
        not wait for the next submission), reap stuck-open breakers,
        and retry deferred respawns. Best-effort by design — a sweep
        pass that loses a race with a concurrent kill just retries next
        period."""
        period = max(self.sweep_ms / 1e3, 1e-3)
        while not self._sweep_stop.wait(period):
            try:
                with self._lock:
                    if self._closed:
                        return
                    for w in self._live_workers_locked():
                        self._absorb_trips_locked(w)
                self.reap_unhealthy()
                self._maybe_respawn()
            except Exception:
                pass                # the sweep must outlive any one bug

    # ---- sessions ----------------------------------------------------------

    def open_session(self, session_id: Optional[str] = None, *,
                     weight: float = 1.0, priority: str = "normal",
                     quota_bytes: Optional[int] = None) -> FleetSession:
        if priority not in PRIORITIES:
            raise ValueError(f"unknown priority {priority!r} (expected "
                             f"one of {sorted(PRIORITIES)})")
        if weight <= 0:
            raise ValueError(f"session weight must be > 0, got {weight}")
        with self._lock:
            if self._closed:
                raise ServingRejectedError("closed", "fleet is shut down")
            sid = session_id or f"fs{len(self._sessions) + 1}"
            old = self._sessions.get(sid)
            if old is not None and not old.closed:
                raise ValueError(f"session id {sid!r} already open")
            rec = _SessRec(sid, float(weight), priority, quota_bytes)
            self._sessions[sid] = rec
        return FleetSession(self, rec)

    def _close_session(self, rec: _SessRec) -> None:
        with self._lock:
            rec.closed = True
            handles = list(rec.handles.values())
        for h in handles:
            try:
                h.close()
            except Exception:
                pass

    def _handle_locked(self, rec: _SessRec, w: FleetWorker):
        """The session's ServingSession on worker `w`, opened lazily
        with the fleet-level parameters — the SAME session id on every
        worker, so retry budgets and sticky windows key on the tenant
        wherever its plans land."""
        h = rec.handles.get(w.id)
        if h is None:
            h = w.scheduler.open_session(rec.id, weight=rec.weight,
                                         priority=rec.priority,
                                         quota_bytes=rec.quota_bytes)
            rec.handles[w.id] = h
        return h

    # ---- routing -----------------------------------------------------------

    def _route_locked(self, rec: _SessRec, plan) -> FleetWorker:
        live = self._routable_locked()
        if not live:
            raise ServingRejectedError(
                "closed", "no live workers", session=rec.id)
        if len(live) == 1:
            rec.affinity = live[0].id
            return live[0]
        # 1. affinity: in-flight work pins the session (retry budgets /
        # sticky windows key on (session, worker) — a mid-plan re-home
        # would reset them and un-bound the very storms they bound)
        if rec.affinity is not None:
            w = self._workers.get(rec.affinity)
            if w is not None and w.alive and not w.draining and \
                    any(not t.done() for t in rec.tickets):
                self.routes_affinity += 1
                return w
        # 2. consistent hash on the canonical fingerprint
        wid = self._ring.route(plan.fingerprint)
        w = self._workers.get(wid) if wid is not None else None
        if w is None or not w.alive:
            w = min(live, key=lambda x: x.pressure_score())
        chosen, how = w, "ring"
        # 3. load-aware spillover: locality yields to overload
        if self.spill_ratio > 0:
            best = min(live, key=lambda x: x.pressure_score())
            if best is not w and w.pressure_score() > \
                    self.spill_ratio * (best.pressure_score() + 1.0):
                chosen, how = best, "spill"
        if how == "spill":
            self.routes_spill += 1
        else:
            self.routes_ring += 1
        rec.affinity = chosen.id
        return chosen

    def _publish_invalidation_locked(self, fp: str, digest: str) -> None:
        """A fingerprint re-submitted over CHANGED data: every worker's
        result cache drops its old-digest entries (they answer a
        question nobody is asking anymore) and its stats store forgets
        the plan's observed sizes (measured over the old data). The new
        digest's entries stay — they are sound."""
        for w in self._live_workers_locked():
            try:
                w.scheduler.cache.invalidate_fingerprint(fp,
                                                         keep_digest=digest)
                w.stats.forget_plan(fp)
            except Exception:
                pass                # bus is best-effort: serving goes on
        self.bus_publishes += 1

    def _promote_locked(self, w: FleetWorker, key) -> None:
        """Cross-worker cache promotion: the routed worker would miss,
        but a peer computed this exact (fingerprint, digest) already —
        adopt the peer's frozen entry so the ring-home worker serves the
        hit. The adopted entry keeps its `worker` stamp, so the served
        copy still names the worker that COMPUTED it (the soak's
        locality proof: hit served by a different worker than computed
        it). Affinity and spillover divert computations off their ring
        home; promotion is what brings the results back."""
        if w.scheduler.cache.peek_frozen(key) is not None:
            return
        for other in self._live_workers_locked():
            if other is w:
                continue
            ent = other.scheduler.cache.peek_frozen(key)
            if ent is not None:
                w.scheduler.cache.adopt(key, ent[0], ent[1])
                self.cache_promotions += 1
                return

    # ---- submission --------------------------------------------------------

    def _submit(self, rec: _SessRec, plan, inputs: Optional[Dict], *,
                block: Optional[bool],
                timeout: Optional[float]) -> FleetTicket:
        if self._closed or rec.closed:
            raise ServingRejectedError(
                "closed", "session or fleet is shut down", session=rec.id)
        from ..plan.executor import bind_scan_sources
        ticket = FleetTicket(self, rec.id, plan, inputs)
        # same binding prologue the worker's scheduler applies — the bus
        # must see the digest the cache key will see, or it invalidates
        # on a phantom change
        digest = cache_mod.input_digest(bind_scan_sources(plan, inputs))
        fp = plan.fingerprint
        with self._lock:
            # quarantine arms WITH respawn (and only then): it exists
            # to keep the healer from feeding a crash-amplifying plan
            # to every replacement worker. A fleet without respawn
            # keeps the pre-self-healing admission behavior (breaker
            # trips degrade and recover per worker, nothing fleet-wide)
            pin_cpu = False
            if self.respawn:
                # absorb attributed breaker trips BEFORE admission: a
                # fingerprint that just earned its second distinct-
                # worker trip must not be admitted a third time
                for lw in self._live_workers_locked():
                    self._absorb_trips_locked(lw)
            if self.respawn and fp in self._quarantined:
                self.quarantine_hits += 1
                if self.quarantine_policy == "reject":
                    raise ServingRejectedError(
                        "quarantined",
                        f"fingerprint {fp[:12]} tripped breakers on "
                        f">= 2 distinct workers "
                        f"({self._quarantined[fp]})", session=rec.id)
                pin_cpu = True      # degrade: serve it, CPU tier only
            self._fp_runs[fp] = self._fp_runs.get(fp, 0) + 1
            # the bus is CROSS-worker coherence: with one live worker
            # its own digest-keyed cache is already coherent, and bus
            # eviction would diverge from the single-worker scheduler's
            # behavior (the workers=1 byte-identical regression)
            if digest is not None and len(self._live_workers_locked()) > 1:
                last = self._digests.get(fp)
                if last is not None and last != digest:
                    self._publish_invalidation_locked(fp, digest)
                self._digests[fp] = digest
            w = self._route_locked(rec, plan)
            if digest is not None and len(self._workers) > 1:
                self._promote_locked(w, (fp, digest))
                # warm failover: a fingerprint that just became (or
                # stays) hot keeps its frozen entry replicated on its
                # ring successors
                if (self.hot_replicas > 0 and self.hot_k > 0
                        and self._fp_runs.get(fp, 0) >= 2
                        and fp in self._hot_fps_locked()):
                    self._replicate_locked(fp, digest)
            handle = self._handle_locked(rec, w)
            rec.tickets.add(ticket)
            if len(rec.tickets) > 64:
                rec.tickets = {t for t in rec.tickets if not t.done()}
        try:
            inner = handle.submit(plan, inputs, block=block,
                                  timeout=timeout, pin_cpu=pin_cpu)
        except BaseException:
            # rejected at the worker's front door (queue_full /
            # over_quota / ...): the tenant sees the typed error — the
            # ticket must not linger as a failover-replayable orphan
            with self._lock:
                rec.tickets.discard(ticket)
            raise
        ticket._bind(inner, w.id)
        return ticket

    def _replay(self, ticket: FleetTicket) -> None:
        """Re-run one orphaned submission on a surviving worker
        (idempotent: a ticket already re-bound to a live worker is left
        alone — kill_worker's proactive replay and result()'s self-heal
        may race here)."""
        with ticket._lock:
            if ticket._replaying:
                return      # concurrent replay in flight: it will bind
            ticket._replaying = True
        try:
            self._replay_inner(ticket)
        finally:
            with ticket._lock:
                ticket._replaying = False

    def _replay_inner(self, ticket: FleetTicket) -> None:
        inner, _ = ticket._current()
        if inner is not None and inner.done():
            try:
                inner.result(0)
                return       # finished before the death: result stands
            except ServingRejectedError as e:
                if e.reason != "closed":
                    return   # typed front-door verdict: replay keeps it
            except BaseException:
                return       # execution error IS the answer (the worker
                #              scheduler already spent its retry budget)
        with self._lock:
            rec = self._sessions.get(ticket.session)
            if rec is None:
                ticket._fail(ServingRejectedError(
                    "closed", "session gone during failover",
                    session=ticket.session))
                return
            # already re-bound by a racing replay?
            cur_w = ticket._current()[1]
            w0 = self._workers.get(cur_w)
            if w0 is not None and w0.alive and not ticket.done():
                return
            # a fingerprint quarantined AFTER the original submission
            # replays under the quarantine policy — the whole point is
            # that a replay of a worker-killer must not kill again
            fp = ticket.plan.fingerprint
            pin_cpu = False
            if self.respawn and fp in self._quarantined:
                self.quarantine_hits += 1
                if self.quarantine_policy == "reject":
                    ticket._fail(ServingRejectedError(
                        "quarantined",
                        f"fingerprint {fp[:12]} quarantined during "
                        f"failover ({self._quarantined[fp]})",
                        session=ticket.session))
                    return
                pin_cpu = True
            try:
                w = self._route_locked(rec, ticket.plan)
            except ServingRejectedError as e:
                ticket._fail(e)
                return
            handle = self._handle_locked(rec, w)
            self.replayed_jobs += 1
            ticket.replays += 1
        try:
            inner = handle.submit(ticket.plan, ticket.inputs,
                                  pin_cpu=pin_cpu)
        except BaseException as e:
            ticket._fail(e)
            return
        ticket._bind(inner, w.id)

    # ---- lifecycle / observability -----------------------------------------

    def close(self, drain: bool = True,
              timeout: Optional[float] = None) -> None:
        self._sweep_stop.set()
        with self._lock:
            self._closed = True
            workers = list(self._workers.values())
        if self._sweep_thread is not None:
            self._sweep_thread.join(timeout=5.0)
        for w in workers:
            if w.alive:
                w.scheduler.close(drain=drain, timeout=timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def metrics(self) -> Dict:
        """Fleet snapshot: per-worker serving metrics + pressure +
        liveness, ring membership, and the router's route/failover/bus
        counters (the multi-worker soak's assertion surface)."""
        with self._lock:
            workers = dict(self._workers)
            counters = {"routes_affinity": self.routes_affinity,
                        "routes_ring": self.routes_ring,
                        "routes_spill": self.routes_spill,
                        "failovers": self.failovers,
                        "replayed_jobs": self.replayed_jobs,
                        "bus_publishes": self.bus_publishes,
                        "cache_promotions": self.cache_promotions,
                        # self-healing: failovers split by cause, plus
                        # the healer's own bookkeeping
                        "killed": self.killed,
                        "reaped": self.reaped,
                        "drained": self.drained,
                        "respawned": self.respawned,
                        "respawn_deferred": self.respawn_deferred,
                        "replications": self.replications,
                        "gossips": self.gossips,
                        "quarantine_hits": self.quarantine_hits,
                        "quarantined": sorted(self._quarantined),
                        "target_workers": self.target_workers}
        out = {}
        for wid, w in workers.items():
            out[wid] = {"alive": w.alive,
                        "draining": w.draining,
                        "pressure": w.pressure_score() if w.alive else None,
                        "serving": w.scheduler.metrics() if w.alive
                        else None}
        return {"workers": out, "ring": list(self._ring.members()),
                **counters}
