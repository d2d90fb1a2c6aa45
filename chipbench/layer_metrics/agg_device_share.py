"""operators: device self time of the ops that `*.HashAggregate` operators
own, over the device's busy time (see join_device_share)."""
from chipbench import program_spans


def read(run):
    red = program_spans.of(run)
    return red.kind_share("HashAggregate") if red else None
