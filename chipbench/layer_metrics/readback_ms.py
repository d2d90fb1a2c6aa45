"""host plan path: a request's `plan.readback` span (the operators' row
counts cross to the host, one transfer a device scalar, two scalars an
operator; `scalars=` on the span), median over the traced window. Capped
tier only."""
from chipbench import host_account


def read(run):
    acc = host_account.of(run)
    return acc.median_ms("plan.readback") if acc else None
