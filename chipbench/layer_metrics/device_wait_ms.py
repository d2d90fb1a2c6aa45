"""operators: a request's `plan.wait` spans summed (the host blocks until
outputs are ready and reads nothing: the eager tiers' wait after each
operator, the capped tier's after its program, the SPMD walk's after each
exchange), median over the traced window. The waits that read a number are
`ops.host_sync`: `host_sync_ms` has those."""
from chipbench import host_account


def read(run):
    acc = host_account.of(run)
    return acc.median_ms("plan.wait") if acc else None
