"""exchange: device self time of the collective ops (`all-to-all`,
`all-gather`, `all-reduce`, `reduce-scatter`, `collective-permute`, their
`-start` / `-done` halves) over the device's busy time, both averaged
over the chips of the mesh."""
from chipbench import collectives


def read(run):
    c = collectives.of(run)
    if not c or not c["seconds"] or not run.trace["busy_s"]:
        return None
    return 100.0 * c["seconds"] / run.trace["busy_s"]
