"""exchange: `local_ops` of `plan.execute` (operators above a sharded
input that ran through the one-chip fallback, the sink's gather apart),
median over the traced window's requests."""
import statistics

from chipbench import program_spans


def read(run):
    red = program_spans.of(run)
    if not red or "plan.execute" not in red.spans:
        return None
    values = red.spans["plan.execute"]["attrs"].get("local_ops")
    return float(statistics.median(values)) if values else None
