"""host plan path: a request's `plan.verify` spans summed (the
pre-execution gate, `_verify_execution`; 0 where
SPARK_RAPIDS_TPU_VERIFY_PLANS is off and the span never opens), median
over the traced window."""
from chipbench import program_spans


def read(run):
    red = program_spans.of(run)
    return red.median_ms("plan.verify") if red else None
