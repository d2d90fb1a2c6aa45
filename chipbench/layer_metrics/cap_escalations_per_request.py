"""compilation: mean of `PlanResult.attempts - 1` over the capped
executions of the window (a run that overflowed a capacity ran again with
grown ones; warm-up should have climbed that ladder). Where the program's
`plan.attempt` spans were traced, their count past each request's first
is printed beside it."""
from chipbench import harness, program_spans


def read(run):
    rows = [e["attempts"] - 1 for e in run.executes
            if e["t0"] >= run.t_window0 and e["mode"] == "capped"]
    if not rows:
        return None
    red = program_spans.of(run)
    if red and red.requests["plan.execute"]:
        wanted = set(red.requests["plan.execute"])
        later = sum(1 for s in red.whole if s["name"] == "plan.attempt"
                    and s["attrs"].get("request") in wanted
                    and s["attrs"].get("attempt", 1) > 1)
        harness.log(f"cap escalations: {sum(rows)} by PlanResult.attempts "
                    f"over {len(rows)} executions of the window; {later} "
                    f"plan.attempt spans past the first over {len(wanted)} "
                    "traced requests")
    return sum(rows) / len(rows)
