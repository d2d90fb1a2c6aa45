"""operators: device self time of the ops inside the program's `ops.join`
spans of `how=full_outer` (the full outer join's maps, the packing of
the right side's lonely rows and the gathers of its output columns), over
the device's busy time."""
from chipbench import join_spans


def read(run):
    got = join_spans.seconds(run, how="full_outer")
    if not got or not got["busy"]:
        return None
    return 100.0 * got["inside"] / got["busy"]
