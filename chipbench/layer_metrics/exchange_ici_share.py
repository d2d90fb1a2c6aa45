"""exchange: the collectives' roofline. Bytes that must leave a chip (the
collective ops' operand shapes from their HLO text, times what each
opcode sends of them to other chips: `chipbench/collectives.py:leaving`) over those ops' device seconds, over
the peaks table's interconnect bits/s / 8. A share of a peak: above 100%
the byte count is wrong, not the interconnect fast."""
from chipbench import collectives


def read(run):
    c = collectives.of(run)
    if not c or not c["seconds"] or not c["bytes"]:
        return None
    return 100.0 * c["bytes"] / c["seconds"] \
        / (run.peaks["ici_bits_per_s"] / 8)
