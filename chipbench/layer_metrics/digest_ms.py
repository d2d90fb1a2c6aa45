"""serving: the program's `serving.digest` span (input digest and the
result-cache consult inside `ServingScheduler._submit`), median per request
over the traced window."""
from chipbench import program_spans


def read(run):
    red = program_spans.of(run)
    return red.median_ms("serving.digest") if red else None
