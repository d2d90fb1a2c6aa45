"""device: of the device's idle time in the traced window, the part during
which some thread of the program is in `plan.wait` or `ops.host_sync` and
no thread is in another leaf span: the device idles while the host waits
for it (launch and completion latency, a round trip a sync). One device
plane only."""
from chipbench import host_account


def read(run):
    acc = host_account.of(run)
    shares = acc.idle_shares() if acc else None
    return shares["wait"] if shares else None
