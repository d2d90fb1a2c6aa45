"""serving: the program's `serving.admit` span (certify, quota charge,
observed charge and the over-quota branch of a submit), median per request
over the traced window."""
from chipbench import program_spans


def read(run):
    red = program_spans.of(run)
    return red.median_ms("serving.admit") if red else None
