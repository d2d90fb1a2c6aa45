"""operators: device self time of the ops inside the program's
`ops.groupby` spans (the two DISTINCTs of q97: the sorted group-by's key
pass, no value plane and no finish), over the device's busy time."""
from chipbench import groupby_spans


def read(run):
    got = groupby_spans.seconds(run)
    if not got or not got["busy"]:
        return None
    return 100.0 * got["inside"] / got["busy"]
