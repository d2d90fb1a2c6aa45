"""kernels: the bytes the plan's outer join must move (the plan file's
`outer_join_bytes`: both sides' key columns read once, the output's columns
and validity written once, over the rows the reference counted) per
completed request, over the device seconds inside the program's `ops.join`
spans of `how=left_outer`, over the peaks table's HBM bytes/s. The join's
share of its roofline: it is sorts, scans and gathers of 32- and 64-bit
words, the peaks table has no integer-ALU peak, and bandwidth is what it
can be held to. The bytes follow the plan and the data, not the kernel, so
a later kernel is held to the same work. Above 100% the byte count is
wrong, not the chip fast."""
from chipbench import harness, join_spans


def read(run):
    got = join_spans.seconds(run)
    if not got or not got["inside"]:
        return None
    done = sum(1 for r in run.requests if r["ok"])
    plan = run.cell.plan
    if not done or not hasattr(plan, "outer_join_bytes"):
        return None
    counts = getattr(plan, "COUNTS", {})
    harness.log(f"outer join: the program's span says matched "
                f"{got['matched']}, unmatched {got['unmatched']}; the "
                f"reference counted matched {counts.get('matched')}, "
                f"unmatched {counts.get('unmatched')}")
    nbytes = plan.outer_join_bytes(run.cell.batch, run.cell.sizes)
    return 100.0 * nbytes * done / got["inside"] \
        / run.peaks["hbm_bytes_per_s"]
