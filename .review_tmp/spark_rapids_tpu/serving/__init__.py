"""Multi-tenant serving layer (docs/serving.md).

`ServingScheduler` is the front door: N tenant sessions submit plans to
a bounded queue; a fair-share dispatcher (weighted deficit round-robin
over priority lanes, starvation-bounded) admits them through the health
monitor with per-session memory quotas sized by the static resource
certifier, exerts backpressure when the queue saturates, keys retry
budgets per tenant, and serves repeat traffic from a fingerprint +
data-digest result cache.

    from spark_rapids_tpu.serving import ServingScheduler

    with ServingScheduler() as sched:
        tenant = sched.open_session(priority="interactive")
        res = tenant.run(plan, {"t": table})

`FleetScheduler` scales that out: a router tier fronting N such workers
— consistent-hash plan routing (serving/router.py), session affinity,
load spillover, failover replay when a worker dies, and a cross-worker
cache-invalidation bus (serving/fleet.py).

    from spark_rapids_tpu.serving import FleetScheduler

    with FleetScheduler(workers=4) as fleet:
        tenant = fleet.open_session(priority="interactive")
        res = tenant.run(plan, {"t": table})
"""
from .cache import ResultCache, cache_key, cached_copy, input_digest
from .fleet import FleetScheduler, FleetSession, FleetTicket, FleetWorker
from .router import HashRing
from .scheduler import (PRIORITIES, ServingRejectedError, ServingScheduler,
                        ServingSession, Ticket)

__all__ = ["ServingScheduler", "ServingSession", "Ticket",
           "ServingRejectedError", "ResultCache", "cache_key",
           "cached_copy", "input_digest", "PRIORITIES",
           "FleetScheduler", "FleetSession", "FleetTicket", "FleetWorker",
           "HashRing"]
