"""host plan path: a request's `plan.result` spans summed (the tier's
epilogue inside `plan.run`: a metrics row an operator and the `PlanResult`;
and the stamps after it: group and lookup counters, session, worker, up to
`record_result`'s `plan.stats`), median over the traced window."""
from chipbench import host_account


def read(run):
    acc = host_account.of(run)
    return acc.median_ms("plan.result") if acc else None
