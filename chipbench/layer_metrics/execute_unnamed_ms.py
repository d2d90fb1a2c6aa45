"""host plan path: a request's `plan.execute` less what its leaves cover: the
summed own time (the span minus its children) of `plan.execute`,
`plan.run` and `plan.attempt`, median over the traced window. An eager
operator's own `plan.op` time is the host's dispatch of that operator and
is not in here: the account printed beside it has it per operator."""
from chipbench import host_account


def read(run):
    acc = host_account.of(run)
    return acc.unnamed_ms() if acc else None
