"""operators: device self time of the ops a decimal kernel ran (the
`decimal.*` scopes of `ops/decimal_utils.py`, whichever operator they lie
in), over the device's busy time. A fusion counts under the scope of its
root instruction."""
from chipbench import decimal_scopes, program_spans


def read(run):
    by_scope = decimal_scopes.seconds(run)
    red = program_spans.of(run)
    if by_scope is None or not red or not red.busy_s:
        return None
    return 100.0 * sum(by_scope.values()) / red.busy_s
