"""The comparison that decides `correct`: an answer of the timed path
against the pandas reference, row for row, and the guarantees each
configuration states (not degraded, on the device, not from the result
cache where the data is fresh). Both limits are 0: int64 keys, counts and
sums are exact.
"""
import numpy as np

# number compared -> limit. `ordered_mismatch`: positions at which the
# presentation sort's columns disagree, plus the gap in row counts.
# `rows_unmatched`: rows of either side without a partner in the other
# (rows tied on the whole sort key may legally swap places).
LIMITS = {"ordered_mismatch": 0, "rows_unmatched": 0}


def result_arrays(res):
    arrays = [res.valid] if getattr(res, "valid", None) is not None else []
    for c in res.table.columns:
        arrays.append(c.data)
        if c.validity is not None:
            arrays.append(c.validity)
    return arrays


def guarantees_broken(res, arrays, cached, devs, platform, fresh) -> str:
    """'' or what the answer broke."""
    if res.degraded is not False:
        return f"degraded={res.degraded!r}: part of the plan ran on the CPU tier"
    if cached and fresh:
        return "answered from the plan-result cache, not by an execution"
    allowed = set(devs)
    for a in arrays:
        where = a.devices()
        if not where <= allowed or any(d.platform != platform for d in where):
            return f"a result array lives on {sorted(map(str, where))}"
    return ""


def to_host(res) -> dict:
    """The answer as host arrays: live rows only (a capped result is
    padded, `valid` marks its live rows)."""
    t = res.table
    keep = None if res.valid is None else np.asarray(res.valid)
    out = {}
    for name in t.names:
        c = t[name]
        a = np.asarray(c.data)
        live = None if c.validity is None else np.asarray(c.validity)
        if keep is not None:
            a = a[keep]
            live = None if live is None else live[keep]
        if live is not None and not bool(live.all()):
            raise ValueError(f"result column {name} holds nulls in live rows")
        out[name] = a
    return out


def compare(got: dict, ref, columns, ordered) -> dict:
    """`got`: column -> host array; `ref`: the reference's DataFrame."""
    n_got = len(next(iter(got.values()))) if got else 0
    n = min(n_got, len(ref))
    mismatch = abs(n_got - len(ref))
    if n:
        differs = np.zeros(n, dtype=bool)
        for c in ordered:
            differs |= np.asarray(got[c][:n]) != ref[c].values[:n]
        mismatch += int(differs.sum())
    a = sorted(zip(*(np.asarray(got[c]).tolist() for c in columns)))
    b = sorted(zip(*(ref[c].values.tolist() for c in columns)))
    unmatched, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        if a[i] == b[j]:
            i, j = i + 1, j + 1
        elif a[i] < b[j]:
            unmatched, i = unmatched + 1, i + 1
        else:
            unmatched, j = unmatched + 1, j + 1
    unmatched += len(a) - i + len(b) - j
    return {"ordered_mismatch": mismatch, "rows_unmatched": unmatched}
