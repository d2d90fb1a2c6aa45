"""serving: the benchmark's span around session.submit() (it holds the
digest of the fresh input and admission), median over the window."""
from chipbench.harness import median


def read(run):
    return median(run.rec.durations_ms("submit", run.t_window0, run.t_window1))
