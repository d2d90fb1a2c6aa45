"""kernels: of the device time inside the program's `ops.window` spans,
the part in ops whose opcode the trace gives as `sort` (a window whose
child says it lies in (partition, order) order already sorts nothing)."""
from chipbench import op_spans


def read(run):
    got = op_spans.seconds(run, "ops.window")
    if not got or not got["inside"]:
        return None
    return 100.0 * got["sorts"] / got["inside"]
