"""operators: device self time of the ops inside the program's
`ops.window` spans (q51's three windows: the sort by (partition, order,
row number) with the child's columns riding, where the child's order was
not taken, and the segmented running scans), over the device's busy
time."""
from chipbench import op_spans


def read(run):
    got = op_spans.seconds(run, "ops.window")
    if not got or not got["busy"]:
        return None
    return 100.0 * got["inside"] / got["busy"]
