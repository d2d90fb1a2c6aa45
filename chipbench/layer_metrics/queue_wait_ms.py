"""serving: Ticket.queue_wait_ms of the window's requests, median."""
from chipbench.harness import median


def read(run):
    return median(r["queue_wait_ms"] for r in run.requests
                  if r.get("queue_wait_ms") is not None)
