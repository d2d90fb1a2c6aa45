"""host plan path: the span around PlanExecutor.execute() minus
PlanResult.wall_ms (optimize, verify, certify, fingerprint and stats run
before the result's own clock starts), median over the window."""
from chipbench.harness import median


def read(run):
    return median((e["t1"] - e["t0"]) / 1e6 - e["wall_ms"]
                  for e in run.executes if e["t0"] >= run.t_window0)
