"""operators: device busy time of the traced window over the requests that
completed in it (the generator's draw, the scan's stand-in, included)."""


def read(run):
    done = sum(1 for r in run.requests if r["ok"])
    if run.trace is None or not done:
        return None
    return run.trace["busy_s"] * 1e3 / done
