"""kernels: the bytes the plan's full outer join must move (the plan
file's `full_join_bytes`: both sides' two key columns read once, every
output row's two customer keys and their validity bytes written once, over
the rows the reference counted) per completed request, over the device
seconds inside the program's `ops.join` spans of `how=full_outer`, over
the peaks table's HBM bytes/s. The join's share of its roofline, whatever
kernel implements it: the bytes follow the plan and the data. Above 100%
the byte count is wrong, not the chip fast."""
from chipbench import harness, join_spans, program_spans


def _last_span(run) -> dict:
    """The attributes of the traced window's last `ops.join` span of
    `how=full_outer` (`join_spans.seconds` keeps `matched` and `unmatched`
    of them; `unmatched_right` is read here)."""
    loaded = program_spans.load(program_spans.find_trace(run.trace_dir))
    held = [s for s in loaded["spans"] if s["name"] == join_spans.SPAN
            and s["attrs"].get("how") == "full_outer"]
    return max(held, key=lambda s: s["t0"])["attrs"] if held else {}


def read(run):
    got = join_spans.seconds(run, how="full_outer")
    if not got or not got["inside"]:
        return None
    done = sum(1 for r in run.requests if r["ok"])
    plan = run.cell.plan
    if not done or not hasattr(plan, "full_join_bytes"):
        return None
    counts = getattr(plan, "COUNTS", {})
    said = _last_span(run)
    harness.log("full outer join: the program's span says " + ", ".join(
        f"{k} {said.get(k)}" for k in ("matched", "unmatched",
                                       "unmatched_right"))
        + "; the reference counted " + ", ".join(
            f"{k} {counts.get(k)}" for k in ("matched", "unmatched",
                                             "unmatched_right")))
    nbytes = plan.full_join_bytes(run.cell.batch, run.cell.sizes)
    return 100.0 * nbytes * done / got["inside"] \
        / run.peaks["hbm_bytes_per_s"]
