"""kernels: device time in operations whose opcode the trace gives as
`sort`, over the device's busy time."""
from chipbench.trace import opcode


def read(run):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    ops = run.trace["op_seconds"]
    return 100.0 * sum(s for n, s in ops.items()
                       if opcode(n).startswith("sort")) / run.trace["busy_s"]
