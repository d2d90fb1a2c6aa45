"""operators: device self time of the ops that `*.HashJoin` operators own
(capped tier: `PlanExecutor.device_op_owners`; eager tier: the `plan.op`
span that holds the op), over the device's busy time."""
from chipbench import program_spans


def read(run):
    red = program_spans.of(run)
    return red.kind_share("HashJoin") if red else None
