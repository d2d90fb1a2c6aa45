"""operators: the eager tier's profile(): HashJoin rows' wall_ms over
PlanResult.wall_ms, median over the window's executions. The capped tier
has no per-operator clock, so there is nothing to read there."""
from chipbench.harness import median


def read(run):
    shares = [100.0 * sum(w for k, w in e["profile"] if k == "HashJoin")
              / e["wall_ms"]
              for e in run.executes
              if e["t0"] >= run.t_window0 and e.get("profile") and e["wall_ms"]]
    return median(shares)
