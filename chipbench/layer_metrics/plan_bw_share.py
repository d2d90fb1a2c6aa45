"""kernels: the least bytes the plan must move (its plan file's
`least_bytes`: every scanned column the optimizer keeps, read once, plus
the result) per completed request, over device busy time, over the peaks
table's HBM bytes/s. The whole plan against one pass over its data: not a
kernel's roofline share."""


def read(run):
    done = sum(1 for r in run.requests if r["ok"])
    if run.trace is None or not done or not run.trace["busy_s"]:
        return None
    cell = run.cell
    least = cell.plan.least_bytes(cell.batch, cell.sizes, run.result_rows)
    return 100.0 * least * done / run.trace["busy_s"] \
        / run.peaks["hbm_bytes_per_s"]
