"""device: of the device's idle time in the traced window, the part during
which no thread of the program is in any leaf span (under brackets only,
or under no span at all): idle time the program's names do not explain.
One device plane only."""
from chipbench import host_account


def read(run):
    acc = host_account.of(run)
    shares = acc.idle_shares() if acc else None
    return shares["unnamed"] if shares else None
