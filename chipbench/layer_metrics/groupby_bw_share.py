"""kernels: the bytes the plan's keyed aggregates must move (the plan
file's `groupby_bytes`: key and value columns read once, keys and
DECIMAL128 sums written once, over the rows and groups the reference
counted) per completed request, over the device seconds inside the
program's `ops.groupby` spans, over the peaks table's HBM bytes/s. The
kernel's share of its roofline: the group-by is sorts, scans and 32-bit
limb arithmetic, the peaks table has no integer-ALU peak, and bandwidth
is what it can be held to. The bytes follow the plan and the data, not
the kernel, so a later kernel is held to the same work. Above 100% the
byte count is wrong, not the chip fast."""
from chipbench import groupby_spans


def read(run):
    got = groupby_spans.seconds(run)
    done = sum(1 for r in run.requests if r["ok"])
    plan = run.cell.plan
    if not got or not got["inside"] or not done \
            or not hasattr(plan, "groupby_bytes"):
        return None
    nbytes = plan.groupby_bytes(run.cell.batch, run.cell.sizes)
    return 100.0 * nbytes * done / got["inside"] \
        / run.peaks["hbm_bytes_per_s"]
