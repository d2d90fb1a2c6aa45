"""kernels: of the device time inside the program's `ops.join` spans of
`how=full_outer`, the part in ops whose opcode the trace gives as `sort`
(both passes' union sorts, the routing sort, the sort that packs the
matchable right rows, and the one that compacts the unmatched right
rows)."""
from chipbench import join_spans


def read(run):
    got = join_spans.seconds(run, how="full_outer")
    if not got or not got["inside"]:
        return None
    return 100.0 * got["sorts"] / got["inside"]
