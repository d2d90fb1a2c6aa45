"""exchange: a request's `plan.exchange` spans summed (each opened inside
the `plan.op` of the operator that moves data between chips, or to the
host at the sink, and closed when the data has arrived), median over the
traced window."""
from chipbench import program_spans


def read(run):
    red = program_spans.of(run)
    if not red or "plan.exchange" not in red.spans:
        return None
    return red.median_ms("plan.exchange")
