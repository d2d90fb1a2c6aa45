"""host plan path: a request's `plan.caps` span (`_starting_caps`: the plan's
fingerprint, the caps memo, the certified bounds) less the `plan.stats`
child it holds (`stats_ms` has that), median over the traced window.
Capped tier only."""
from chipbench import host_account


def read(run):
    acc = host_account.of(run)
    return acc.median_ms("plan.caps", own=True) if acc else None
