"""operators: device self time of the ops inside the program's `ops.join`
spans of `how=left_outer` (the outer join's maps and the gathers of its
output columns), over the device's busy time."""
from chipbench import join_spans


def read(run):
    got = join_spans.seconds(run)
    if not got or not got["busy"]:
        return None
    return 100.0 * got["inside"] / got["busy"]
