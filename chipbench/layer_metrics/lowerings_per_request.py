"""compilation: `lowerings=` of a request's `plan.execute` (jit lowerings on
the executing thread while the request ran: in-memory program cache
misses, counted by the program's own listener, utils/tracing.py), mean
over the whole requests of the traced window. 0 in a warm cell."""
from chipbench import host_account


def read(run):
    acc = host_account.of(run)
    return acc.attr_mean("plan.execute", "lowerings") if acc else None
