"""host plan path: a request's `plan.bind` spans summed (from the entry of
`PlanExecutor._execute_request` to `plan.optimize`: the mesh check, the
scans' binding, the schemas resolved against the bound tables), median
over the traced window."""
from chipbench import host_account


def read(run):
    acc = host_account.of(run)
    return acc.median_ms("plan.bind") if acc else None
