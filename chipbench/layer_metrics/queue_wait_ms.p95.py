"""serving: Ticket.queue_wait_ms of the window's requests, 95th percentile."""
from chipbench.harness import percentile


def read(run):
    return percentile([r["queue_wait_ms"] for r in run.requests
                       if r.get("queue_wait_ms") is not None], 0.95)
