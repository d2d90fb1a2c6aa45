"""kernels: of the device time inside the program's `ops.groupby` spans,
the part in ops whose opcode the trace gives as `sort` (the key sort and
the compaction sort of the `scan` kernel)."""
from chipbench import groupby_spans


def read(run):
    got = groupby_spans.seconds(run)
    if not got or not got["inside"]:
        return None
    return 100.0 * got["sorts"] / got["inside"]
