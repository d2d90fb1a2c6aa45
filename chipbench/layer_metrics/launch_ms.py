"""host plan path: a request's `plan.program` (the program cache's key and
its lookup) plus `plan.launch` (the call of the jitted program until it
returns: flattening the tables and enqueueing; on a miss the trace and the
compile too, which `lowering_ms` then says), median over the traced
window. Capped tier only."""
from chipbench import host_account


def read(run):
    acc = host_account.of(run)
    return acc.median_ms(("plan.program", "plan.launch")) if acc else None
