"""host plan path: a request's `plan.stats` spans summed (the stats store's
part of an execution: `observed_caps` before the run, `record_result` after
it; 0 where the store is off), median over the traced window."""
from chipbench import program_spans


def read(run):
    red = program_spans.of(run)
    return red.median_ms("plan.stats") if red else None
