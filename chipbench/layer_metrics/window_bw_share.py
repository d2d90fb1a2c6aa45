"""kernels: the bytes the plan's windows must move (the plan file's
`window_bytes`: each window's partition, order and value columns and their
validity read once, each function's column and validity written once, over
the rows the reference counted) per completed request, over the device
seconds inside the program's `ops.window` spans, over the peaks table's
HBM bytes/s. The windows' share of their roofline, whatever kernel
implements them: the bytes follow the plan and the data. Above 100% the
byte count is wrong, not the chip fast."""
from chipbench import harness, op_spans


def _said(spans) -> dict:
    """Over the traced window's last request: the `ops.window` spans'
    `rows` and `partitions` summed, and how each was `sorted`."""
    last = max(a.get("request", -1) for a in spans)
    mine = [a for a in spans if a.get("request", -1) == last]
    total = lambda k: sum(int(a.get(k) or 0) for a in mine)
    return {"windows": len(mine), "rows": total("rows"),
            "partitions": total("partitions"),
            "sorted": "+".join(str(a.get("sorted")) for a in mine)}


def read(run):
    got = op_spans.seconds(run, "ops.window")
    if not got or not got["inside"]:
        return None
    done = sum(1 for r in run.requests if r["ok"])
    plan = run.cell.plan
    if not done or not hasattr(plan, "window_bytes"):
        return None
    counts = getattr(plan, "COUNTS", {})
    harness.log("windows: the program's spans say " + ", ".join(
        f"{k} {v}" for k, v in _said(got["attrs"]).items())
        + "; the reference counted " + ", ".join(
            f"{k} {counts.get(k)}" for k in (
                "window_rows", "window_partitions", "web_groups",
                "store_groups", "join_rows", "filter_rows"))
        + "; the batch states " + ", ".join(
            f"{k} {run.cell.batch.get(k)}" for k in (
                "web_groups", "store_groups", "join_rows", "filter_rows")))
    nbytes = plan.window_bytes(run.cell.batch, run.cell.sizes)
    return 100.0 * nbytes * done / got["inside"] \
        / run.peaks["hbm_bytes_per_s"]
