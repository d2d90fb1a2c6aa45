"""operators: a request's `ops.host_sync` spans summed (the eager tier's
data-dependent size reads: the device drains, one number crosses to the
host, and only then is the next shape known), median over the traced
window."""
from chipbench import program_spans


def read(run):
    red = program_spans.of(run)
    return red.median_ms("ops.host_sync") if red else None
