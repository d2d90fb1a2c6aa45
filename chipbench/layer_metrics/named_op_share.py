"""operators: device self time of the ops that have a plan operator for an
owner, over the device's busy time: how far join_device_share and
agg_device_share can be trusted. The batch generator's draw is rightly
unowned."""
from chipbench import program_spans


def read(run):
    red = program_spans.of(run)
    return red.named_share() if red else None
