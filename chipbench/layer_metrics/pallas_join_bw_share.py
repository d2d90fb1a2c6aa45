"""kernels: bytes the `pallas_hash_join_*` custom calls move (operands and
results, each buffer once, from the shapes in the op's own HLO text) over
their device time, over the peaks table's HBM bytes/s. A share of a peak:
above 100% the byte count is wrong, not the chip fast."""
from chipbench import program_spans


def read(run):
    red = program_spans.of(run)
    if not red:
        return None
    seconds, nbytes = red.kernel("pallas_hash_join_")
    if not seconds:
        return None
    return 100.0 * nbytes / seconds / run.peaks["hbm_bytes_per_s"]
