"""operators: device self time of the ops that `*.HashAggregate` operators
own, over the device's busy time, in the cell whose group-by sums and
averages decimals (plane sums, 256-bit totals, the averages' divisions).
`agg_device_share`'s reduction, kept under its own name so that the cells
of int64 sums and the cell of decimal ones are not read as one series."""
from chipbench import program_spans


def read(run):
    red = program_spans.of(run)
    return red.kind_share("HashAggregate") if red else None
