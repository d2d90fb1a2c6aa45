"""serving: a request's `serving.consult` (the worker's part before
`plan.execute`: the deadline check, the dispatch-time cache consult, the
store's scope) plus `serving.complete` (after it: the result cache's
insert, the quota's release, the ticket's completion), median over the
traced window."""
from chipbench import host_account


def read(run):
    acc = host_account.of(run)
    return acc.median_ms(("serving.consult", "serving.complete")) if acc else None
