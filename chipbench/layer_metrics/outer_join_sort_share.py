"""kernels: of the device time inside the program's `ops.join` spans of
`how=left_outer`, the part in ops whose opcode the trace gives as `sort`
(the union sort, the routing sort and the sort that packs the matchable
right rows)."""
from chipbench import join_spans


def read(run):
    got = join_spans.seconds(run)
    if not got or not got["inside"]:
        return None
    return 100.0 * got["sorts"] / got["inside"]
