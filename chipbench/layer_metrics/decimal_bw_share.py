"""kernels: the bytes the row-wise decimal kernels must move (the plan
file's `decimal_bytes`: the DECIMAL64 inputs read once, the DECIMAL128
products written once) per completed request, over the device time of the
ops under the `decimal.mul` and `decimal.rescale` scopes of the row-wise
operators (Project, FusedSelect), over the peaks table's HBM bytes/s. The
same scopes inside a HashAggregate are the averages' casts over a handful
of groups, which move none of these bytes, and are left out. A share of a
peak: above 100% the byte count is wrong, not the chip fast. The decimal path is 32-bit limb arithmetic on
the vector unit and the peaks table has no integer-ALU peak, so bandwidth
is the roofline it can be held to."""
from chipbench import decimal_scopes


def read(run):
    by_scope = decimal_scopes.seconds(run)
    done = sum(1 for r in run.requests if r["ok"])
    if by_scope is None or not done \
            or not hasattr(run.cell.plan, "decimal_bytes"):
        return None
    spent = sum(s for (kind, name), s in by_scope.items()
                if kind in ("Project", "FusedSelect")
                and name in ("mul", "rescale"))
    if not spent:
        return None
    nbytes = run.cell.plan.decimal_bytes(run.cell.batch, run.cell.sizes)
    return 100.0 * nbytes * done / spent / run.peaks["hbm_bytes_per_s"]
