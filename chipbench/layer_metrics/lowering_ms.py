"""compilation: `lowering_ms=` of a request's `plan.execute` (what the
request's jit lowerings took on its thread), mean over the whole requests
of the traced window."""
from chipbench import host_account


def read(run):
    acc = host_account.of(run)
    return acc.attr_mean("plan.execute", "lowering_ms") if acc else None
