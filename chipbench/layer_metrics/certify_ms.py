"""host plan path: a request's `plan.certify` spans summed (both call
sites of `PlanExecutor._certify`: the scheduler's admission on the
submitting thread and `execute` on the worker), median over the traced
window."""
from chipbench import program_spans


def read(run):
    red = program_spans.of(run)
    return red.median_ms("plan.certify") if red else None
