"""host plan path: a request's `plan.optimize` spans summed (the rule
pipeline, `PlanExecutor._optimized`; a cached rewrite costs its key),
median over the traced window."""
from chipbench import program_spans


def read(run):
    red = program_spans.of(run)
    return red.median_ms("plan.optimize") if red else None
