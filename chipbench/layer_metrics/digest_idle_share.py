"""serving: device idle time that lies under a `serving.digest` span (while
it is the deepest span open on its thread; union over the callers), over
all device idle time of the traced window."""
from chipbench import program_spans


def read(run):
    red = program_spans.of(run)
    if not red or "serving.digest" not in red.spans or not red.idle_s:
        return None
    return 100.0 * red.spans["serving.digest"]["idle_s"] / red.idle_s
