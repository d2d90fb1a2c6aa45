"""Groupby hash-aggregate with Spark semantics (BASELINE.json configs[1]:
"groupby hash-aggregate (sum/count) on single int32 key, 10M rows").

The reference stack gets this from cudf's hash groupby. TPU-first design:
hash tables are a poor fit for the MXU/VPU, but XLA's on-device sort is
excellent — so aggregate = ONE multi-operand `lax.sort` over the key
columns' orderable operands (shared with ops/sort.py, so null rank / NaN
normalization / -0.0 grouping match Spark comparison semantics for free),
then fused segment reductions over the sorted runs:

    sort keys (+row iota) → run boundaries → group ids (prefix sum)
    → jax.ops.segment_{sum,min,max} per aggregation → slice to num_groups

Everything up to the final slice is a single jit; the only host sync is the
group count, exactly like the reference's JNI ops returning row counts.

Spark agg semantics implemented: sum/min/max ignore nulls (all-null group →
null); count counts non-nulls; `size` is count(*); mean = double sum/count;
integer sums widen to INT64 (Spark SUM(int) is LongType) and wrap on
overflow like Java longs (non-ANSI).

Decimals: `sum` and `mean` over a decimal(p, s) column have Spark's types
(Sum: decimal(p + 10, s) bounded; Average: that sum over the count at the
divide rule's type, cast HALF_UP to decimal(p + 4, s + 4)) and are null on
overflow. The kernels never see a limb: a decimal is summed as its 32-bit
planes (`decimal_utils.limb_planes`), each an ordinary int64 sum, and
`decimal_utils.finish_sum` / `finish_mean` put a group's planes together
in 256 bits. With decimal payloads the sort kernels let up to
`RIDE_PAYLOADS` planes ride the key sort and, past that, sort the keys and
a row iota alone and gather the planes afterwards.

A third kernel, `direct`, sorts nothing: for a key cap of at most
`DIRECT_KEY_CAP` groups and exact (integer, plane) aggregates it finds the
distinct keys by repeated lexicographic minima and reduces each aggregate
under a one-hot of the group's slot. The key cap the plan carries selects
it (`groupby_signature`); the same contract as the other two.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from .. import dtypes
from ..columnar import Column, Table
from ..dtypes import Kind
from ..utils.tracing import Tally, span
from .gather import plane_words, take, words_ride
from .scans import running
from .sort import NULLS_LAST, _key_operands

AGG_OPS = ("sum", "count", "min", "max", "mean", "size")


# Most groups the sort-free kernel is chosen for. Its cost grows with the
# slots: Q1's 6M-row batch with 26 plane and count payloads ran 167 M rows/s
# at a key cap of 8 and 110 M at 64 on a v5e, 0.33 ms a slot or 1% of the
# request each, while the sort kernels' program for the same batch was
# still compiling after 11 minutes (PERF.md, PR 28: flat 64-bit scans and
# 26 gathered planes). Since PR 34 the `scan` kernel's scans run in two
# levels and it compiles at 60 M rows in 107 s on the chip (`q18.batch`:
# 15 M groups, two planes riding the sort, 1.76 s a request, 34 M rows/s);
# Q1's shape under it has not been tried again, and an int64 shape at a
# handful of groups has not been swept: the limit stays at the smaller
# measured point until one is.
DIRECT_KEY_CAP = 8
# fixed-width kinds min/max read as plain integers
_EXACT_KINDS = (Kind.DATE32, Kind.TIMESTAMP_US, Kind.TIMESTAMP_S,
                Kind.TIMESTAMP_MS, Kind.DECIMAL32, Kind.DECIMAL64)


def _agg_value_dtype(op: str, dt: dtypes.DType) -> dtypes.DType:
    if op in ("count", "size"):
        return dtypes.INT64
    if dt.is_decimal and op in ("sum", "mean"):
        from . import decimal_utils
        return (decimal_utils.sum_type(dt) if op == "sum"
                else decimal_utils.average_types(dt)[2])
    if op == "mean":
        return dtypes.FLOAT64
    if op == "sum":
        if dt.is_integer:
            return dtypes.INT64
        if dt.is_floating:
            return dtypes.FLOAT64
        raise TypeError(f"sum unsupported for {dt}")
    return dt  # min/max keep the input type


# Most payloads that ride the main sort where a caller asks for gathers
# (decimal planes). A 32-bit operand that rides costs the sort one more
# word a row; gathered by the sort's order it costs 7.0-7.1 ns a row
# (PERF.md, PR 31). Q18's two planes ride; Q1's 26 would make a sort of
# 28 operands, which is the program that did not compile (PR 28).
RIDE_PAYLOADS = 4


def _sort_with_payloads(key_operands, iota, payloads, n_ops: int,
                        gather: bool, by_row: bool = False):
    """The main key sort -> (sorted key operands, order, payloads in that
    order). The payloads ride the sort as operands, or, with `gather`
    (decimal planes: a dozen operands would ride) and more than
    `RIDE_PAYLOADS` of them, the sort moves the keys and the iota alone
    and the payloads are gathered by it afterwards. `by_row` (a DISTINCT,
    which has no payload, and a window, whose payloads ride): the iota is
    the sort's last key and the sort not a stable one; no two rows tie,
    the order is the stable sort's, and the program compiles in half the
    time (ops/join.py:_union_sort)."""
    gather = gather and len(payloads) > RIDE_PAYLOADS
    operands = [*key_operands, iota] + ([] if gather else list(payloads))
    if by_row:
        sorted_all = jax.lax.sort(operands, num_keys=n_ops + 1,
                                  is_stable=False)
        return sorted_all[:n_ops], sorted_all[n_ops], sorted_all[n_ops + 1:]
    sorted_all = jax.lax.sort(operands, num_keys=n_ops, is_stable=True)
    order = sorted_all[n_ops]
    spay = ([jnp.take(p, order, axis=0) for p in payloads] if gather
            else sorted_all[n_ops + 1:])
    return sorted_all[:n_ops], order, spay


def run_boundaries(sorted_ops, n: int):
    """(n,) bool: the rows of a sorted frame at which a run of equal keys
    starts (row 0 always), from the key operands in their sorted order."""
    neq = jnp.zeros((n,), bool)
    for o in sorted_ops:
        neq = neq | (o != jnp.roll(o, 1))
    return neq.at[0].set(True) if n else neq   # guard: empty scatter OOB


def sorted_runs(key_operands, iota, payloads, n_ops: int, gather: bool,
                by_row: bool = False, n_run_ops: Optional[int] = None):
    """What the sorted group-by kernels and the window kernel
    (ops/window.py) share: the main key sort with its payloads, and the
    flags of the runs of equal keys in it -> (sorted key operands, order,
    payloads in that order, boundary). A run is a group; a window's run
    is a partition, the first `n_run_ops` operands alone."""
    sorted_ops, order, spay = _sort_with_payloads(
        key_operands, iota, payloads, n_ops, gather, by_row=by_row)
    boundary = run_boundaries(sorted_ops[:n_run_ops], iota.shape[0])
    return sorted_ops, order, spay, boundary


def _key_words(op):
    """A key operand as the 32-bit words that ride the compaction sort: a
    64-bit one as its low and its high word. The chip's compiler sorts it
    as two words either way; handed back as words, the 64-bit values are
    put together by the caller over the groups (`_from_key_words`), not
    here over the frame (a frame of the two halves beside the frame of
    the whole: `q13.batch`'s and `q18.batch`'s peaks stand at this
    kernel)."""
    if op.dtype.itemsize < 8:
        return [op]
    return [op.astype(jnp.uint32), (op >> 32).astype(jnp.int32)]


@partial(jax.jit, static_argnames=("dtypes", "g"))
def _from_key_words(words, *, dtypes: Tuple[str, ...], g: int):
    """`_key_words` undone over the first `g` rows -> the operands of
    `dtypes`, in order. One program for a group-by's keys."""
    words = iter(words)
    out = []
    for dtype in dtypes:
        low = next(words)[:g]
        if jnp.dtype(dtype).itemsize == 8:
            high = next(words)[:g].astype(jnp.int64)
            low = ((high << 32) | low.astype(jnp.int64)).astype(dtype)
        out.append(low)
    return out


@partial(jax.jit,
         static_argnames=("n_ops", "agg_kinds", "has_valids", "has_alive",
                          "gather_payloads", "ride_keys", "with_starts"))
def _groupby_kernel(key_operands, agg_datas, agg_valids, *, n_ops: int,
                    agg_kinds: Tuple[str, ...], has_valids: Tuple[bool, ...],
                    has_alive: bool = False, gather_payloads: bool = False,
                    ride_keys: Tuple[int, ...] = (),
                    with_starts: bool = False):
    """Scatter-free, gather-free sorted aggregation (round-4 redesign).

    On-chip primitive costs (round-2 TPU measurement, recorded in
    docs/architecture.md "Sorts, cumsums and gathers"; the sweep
    tool and its CPU capture left the tree with PR 30 and are in git
    history; 10M rows): sort ≈ 38 ms with cheap marginal payload operands,
    cumsum ≈ 16 ms, but a RANDOM GATHER ≈ 160 ms and a random scatter ≈
    930 ms. `q18.batch`'s traced run (PERF.md, PR 34; 60 M rows into 15 M
    groups, per 10 M rows) bears the order out and corrects the sizes:
    the key sort of an int64 key, the iota and two 32-bit planes 65 ms,
    the compaction sort with its seven payload words 101 ms, a 64-bit
    cumsum in two levels 8.5 ms, a gather of int64 keys 183 ms per 10 M
    slots (18 ns a slot at this size; 7 ns at 360,000 slots, PR 31), a
    scatter 7 ns a row where no two rows write one slot (PR 33) and 87
    ns where they collide (`jnp.nonzero`'s, PR 34). PR 42's probe at
    15 M rows prices the parts apart: a sort on one 32-bit key 1.34 ns a
    row and 0.93 more for every riding 32-bit word (12 words 12.4 ns), a
    stable one a sixth more (the compiler adds the row numbers as an
    operand), a gathered int64 slot 15 ns through a map that only rises,
    a rank scan with its scatter 6 to 8 ns a row. The
    tradeoff is BACKEND-SPECIFIC: on CPU a random scatter-add costs ~163 ms
    against ~233 ms per tuple-carry scan (same CPU capture), so this design
    measures ~0.49× the old scatter-based kernel there (an A/B of the
    same vintage) — the win this layout buys exists on TPU, where
    scatters are ~25× a cumsum; `_use_scan_kernel` therefore dispatches
    the segment/scatter design (_groupby_kernel_scatter) on CPU, so CPU
    users no longer pay the regression. The
    previous kernel did one value gather per aggregation plus 4 positional
    gathers per cumsum-difference — gathers dominated (~0.9 s at 10M). This
    version has zero data-sized gathers:

      * value/validity columns ride the MAIN key sort as payload operands
        (stable sort ⇒ payload order == the old gather-by-order);
      * int sums/counts: one exclusive cumsum each; the per-group value is
        the difference of the cumsum between CONSECUTIVE group starts, read
        off adjacent entries after compaction — no positional gathers. The
        compaction pad value is the cumsum total, which makes the adjacent
        difference correct for the last group for free;
      * float sums and min/max: one REVERSE segmented associative_scan each
        (result lands on the group's first row — the row compaction keeps);
      * ONE boundary-compaction sort packs every group-start row to the
        front, replacing both the old starts sort and every per-agg
        gather. Its ONE key is the start's position (`n` at every other
        row): the starts' positions are unique and ascending, and the
        other rows tie with payloads that are all alike (`n`, the scan's
        total, 0.0, the identity, 0 for a key word), so the sort is not a
        stable one (for which the chip's compiler adds the row numbers
        as an operand, runs a sixth longer and compiles in twice the
        time: PR 42, PR 43). It carries the per-agg results and what the
        caller makes the groups' keys from: the key operands named by
        `ride_keys` (indices into `key_operands`) at the start rows, which
        for an integer, date, timestamp, DECIMAL32/64 or boolean key ARE
        the key (ops/sort.py:_key_operands: the data zeroed under a null,
        the null rank in front; 0.93 ns a 32-bit word a row, where the
        gather of a key plane through the first rows was 15-18 ns a
        slot: PR 48; a 64-bit operand as its two words, `_key_words`),
        and the group's first ORIGINAL row number only where some key
        operand does not ride (a float, DECIMAL128 or string key, whose
        operands are not its data; a caller that gathers). searchsorted
        stays banned (it lowers to ~log2(n) whole-array gather passes).

    Returns (num_groups, starts, first_rows, outs, rode): all n-length,
    entries past num_groups are padding (positions hold n), sliced/masked
    by the caller; `first_rows` is None where every key operand rides,
    `rode` the compacted words of `ride_keys`' operands, in its order
    (`_key_words`; the caller puts them together, `_from_key_words`),
    `starts` None unless `with_starts` (the string extremes read it: every
    frame-long output is held in HBM until the caller's finish ends, and
    `q13.batch`'s and `q18.batch`'s peaks stand there).

    `has_alive`: key_operands[0] is a dead-row flag (0 alive, 1 dead) the
    caller prepended — the jit-pipeline contract where upstream capped ops
    emit padded rows. Dead rows sort LAST (behind every alive group, never
    mixing with one, since the flag operand differs) and num_groups counts
    only alive groups, so the caller's `iota < num_groups` mask drops the
    dead tail for free. Group sizes/aggregates need no special-casing: the
    group after the last alive group starts exactly where the dead region
    does, so the adjacent-difference reads stay exact.
    """
    n = key_operands[0].shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)

    # ---- payload layout for the main sort --------------------------------
    payloads: List = []
    slots: List[Tuple[Optional[int], Optional[int]]] = []  # (data, valid)
    for data, valid, op, hv in zip(agg_datas, agg_valids, agg_kinds,
                                   has_valids):
        d_slot = v_slot = None
        if op not in ("size", "count"):
            d_slot = len(payloads)
            payloads.append(data)
        if hv:
            v_slot = len(payloads)
            payloads.append(valid.astype(jnp.int8))
        slots.append((d_slot, v_slot))

    sorted_ops, order, spay, boundary = sorted_runs(
        key_operands, iota, payloads, n_ops, gather_payloads,
        by_row=not agg_kinds)
    ends_flag = jnp.roll(boundary, -1).at[-1].set(True) if n else boundary
    if has_alive:
        num_groups = jnp.sum((boundary & (sorted_ops[0] == 0))
                             .astype(jnp.int32))
    else:
        num_groups = jnp.sum(boundary.astype(jnp.int32))

    def rev_segscan(vals, kind: str):
        """Reverse segmented sum/min/max: resets walking backwards at group
        ENDS, so each group's reduction lands on its FIRST row (which the
        compaction keeps). Floats use this for sums too — a global-cumsum
        difference would let one NaN/Inf poison every group sorted after
        it."""
        def combine(a, b):
            abound, aval = a
            bbound, bval = b
            if kind == "sum":
                merged0 = aval + bval
            elif kind == "min":
                merged0 = jnp.minimum(aval, bval)
            else:
                merged0 = jnp.maximum(aval, bval)
            return abound | bbound, jnp.where(bbound, bval, merged0)
        _, res = jax.lax.associative_scan(combine, (ends_flag, vals),
                                          reverse=True)
        return res

    # compaction operands: group-start rows to the front, everything they
    # need riding along as payloads
    pad_i32 = jnp.int32(n)
    comp_pay: List = [jnp.where(boundary, iota, pad_i32)]       # position
    # the first original row, unless every key operand rides instead
    row_slot = None
    if set(ride_keys) != set(range(int(has_alive), n_ops)):
        row_slot = len(comp_pay)
        comp_pay.append(jnp.where(boundary, order, pad_i32))
    first_key = len(comp_pay)
    comp_pay.extend(jnp.where(boundary, w, jnp.zeros((), w.dtype))
                    for k in ride_keys for w in _key_words(sorted_ops[k]))
    key_slots = slice(first_key, len(comp_pay))
    # per-agg: (payload index in comp_pay, mode, pad-side info)
    agg_comp: List = []
    totals = {}          # comp_pay slot -> cumsum grand total (traced scalar)
    for (d_slot, v_slot), op in zip(slots, agg_kinds):
        ok = (spay[v_slot] == 1) if v_slot is not None else None
        # the non-null count: without a validity it is the group's size
        # (read off the compacted starts, no scan); with one, a running
        # count in 32 bits (n rows fit an int32 iota already)
        cnt_slot = None
        if ok is not None:
            csum = running(ok.astype(jnp.int32))
            excl = csum - ok.astype(jnp.int32)
            total = csum[-1] if n else jnp.int32(0)
            cnt_slot = len(comp_pay)
            totals[cnt_slot] = total
            comp_pay.append(jnp.where(boundary, excl, total))
        if op in ("size", "count"):
            agg_comp.append((None, op, cnt_slot))
            continue
        v = spay[d_slot]
        okv = ok if ok is not None else jnp.ones((n,), bool)
        if op in ("sum", "mean"):
            if v.dtype.kind == "f" or op == "mean":
                acc = jnp.where(okv, v.astype(jnp.float64), 0.0)
                res = rev_segscan(acc, "sum")
                slot = len(comp_pay)
                comp_pay.append(jnp.where(boundary, res, 0.0))
                agg_comp.append((slot, "fsum" if op == "sum" else "mean",
                                 cnt_slot))
            else:
                acc = jnp.where(okv, v.astype(jnp.int64), jnp.int64(0))
                # a frame-long 64-bit scan, in two levels (ops/scans.py)
                csum = running(acc)
                excl = csum - acc
                total = csum[-1] if n else jnp.int64(0)
                slot = len(comp_pay)
                totals[slot] = total
                # pad value = total ⇒ the adjacent difference of the last
                # real group reads (total - its exclusive prefix) — exact
                comp_pay.append(jnp.where(boundary, excl, total))
                agg_comp.append((slot, "isum", cnt_slot))
            continue
        # min / max with null-ignoring identities. Floats go through the
        # total-order transform so NaN behaves like Spark: NaN is greatest,
        # min returns NaN only for an all-NaN group (plain jnp.minimum would
        # propagate NaN over smaller real values).
        if v.dtype.kind == "f":
            from .sort import _float_total_order
            tv = _float_total_order(v)
            info = jnp.iinfo(tv.dtype)
            ident = jnp.asarray(info.max if op == "min" else info.min,
                                tv.dtype)
            masked = jnp.where(okv, tv, ident)
            ext = rev_segscan(masked, "min" if op == "min" else "max")
            slot = len(comp_pay)
            comp_pay.append(jnp.where(boundary, ext, ident))
            agg_comp.append((slot, "fext:" + str(v.dtype), cnt_slot))
        else:
            info = jnp.iinfo(v.dtype)
            ident = jnp.asarray(info.max if op == "min" else info.min,
                                v.dtype)
            masked = jnp.where(okv, v, ident)
            ext = rev_segscan(masked, "min" if op == "min" else "max")
            slot = len(comp_pay)
            comp_pay.append(jnp.where(boundary, ext, ident))
            agg_comp.append((slot, "ext", cnt_slot))

    # the starts' positions are their own key; every other row holds `n`
    # and the pads, so the sort need not be a stable one
    comp = jax.lax.sort(comp_pay, num_keys=1, is_stable=False)
    starts = comp[0]
    first_rows = None if row_slot is None else comp[row_slot]
    rode = tuple(comp[key_slots])

    def adj_diff(arr, tail):
        if n == 0:
            return arr
        return jnp.concatenate([arr[1:], jnp.full((1,), tail, arr.dtype)]) - arr

    # sizes from the compacted start positions (pad n makes the last group's
    # difference read n - start — exact)
    sizes = adj_diff(starts.astype(jnp.int64), n)

    def adj_diff_total(arr, total):
        """Adjacent difference whose final element reads against the scalar
        `total`; pad entries equal `total` so padded diffs are 0."""
        if n == 0:
            return arr
        return jnp.concatenate([arr[1:], total[None]]) - arr

    outs = []
    for (slot, mode, cnt_slot), op in zip(agg_comp, agg_kinds):
        cnt = sizes
        if cnt_slot is not None:
            cnt = adj_diff_total(comp[cnt_slot], totals[cnt_slot]) \
                .astype(jnp.int64)
        if op == "size":
            outs.append((sizes, None))
        elif op == "count":
            outs.append((cnt, None))
        elif mode == "isum":
            s = adj_diff_total(comp[slot], totals[slot])
            outs.append((s, cnt > 0))
        elif mode == "fsum":
            outs.append((comp[slot], cnt > 0))
        elif mode == "mean":
            s = comp[slot] / jnp.where(cnt == 0, 1, cnt).astype(jnp.float64)
            outs.append((s, cnt > 0))
        elif mode.startswith("fext:"):
            ext = comp[slot]
            info = jnp.iinfo(ext.dtype)
            sign_bit = jnp.asarray(info.min, ext.dtype)
            bits = jnp.where(ext < 0, ~(ext ^ sign_bit), ext)
            fdt = jnp.dtype(mode.split(":", 1)[1])
            outs.append((jax.lax.bitcast_convert_type(bits, fdt), cnt > 0))
        else:   # "ext"
            outs.append((comp[slot], cnt > 0))

    return (num_groups, starts if with_starts else None, first_rows, outs,
            rode)


@partial(jax.jit,
         static_argnames=("n_ops", "agg_kinds", "has_valids", "has_alive",
                          "gather_payloads"))
def _groupby_kernel_scatter(key_operands, agg_datas, agg_valids, *,
                            n_ops: int, agg_kinds: Tuple[str, ...],
                            has_valids: Tuple[bool, ...],
                            has_alive: bool = False,
                            gather_payloads: bool = False):
    """Scatter/segment-op groupby kernel — the CPU-preferred design.

    Same contract as _groupby_kernel (the scan design): (num_groups,
    starts, first_rows, outs), group order = key sort order, padding past
    num_groups sliced/masked by the caller. The difference is the
    aggregation step: after the ONE main key sort, per-sorted-row group ids
    come from a cumsum of the run boundaries and every aggregate is one
    `jax.ops.segment_{sum,min,max}` — a data-sized random scatter-add.
    That is the round-3 design this file replaced for TPU, kept here
    because the tradeoff is BACKEND-SPECIFIC (the CPU capture cited at
    _groupby_kernel: scatter-add ~163 ms vs ~233 ms per tuple-carry scan
    at 10M rows; the scan design measured ~0.49x the scatter kernel on
    CPU in the same A/B). `_use_scan_kernel` picks per backend, like
    row_conversion's _use_word_kernel.

    Dead rows under `has_alive` sort last as their own groups (the leading
    flag operand differs), so their segment ids land past every alive
    group and their results fall in the sliced-away tail."""
    n = key_operands[0].shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)

    payloads: List = []
    slots: List[Tuple[Optional[int], Optional[int]]] = []
    for data, valid, op, hv in zip(agg_datas, agg_valids, agg_kinds,
                                   has_valids):
        d_slot = v_slot = None
        if op not in ("size", "count"):
            d_slot = len(payloads)
            payloads.append(data)
        if hv:
            v_slot = len(payloads)
            payloads.append(valid.astype(jnp.int8))
        slots.append((d_slot, v_slot))

    sorted_ops, order, spay, boundary = sorted_runs(
        key_operands, iota, payloads, n_ops, gather_payloads)
    if has_alive:
        num_groups = jnp.sum((boundary & (sorted_ops[0] == 0))
                             .astype(jnp.int32))
    else:
        num_groups = jnp.sum(boundary.astype(jnp.int32))

    # group id per sorted row; groups numbered in sorted-key order, so the
    # per-group results land directly in compaction order
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    # stable sort => order is increasing within a group: min(order) is the
    # group's FIRST row, and min(position) its start
    starts = jax.ops.segment_min(iota, seg, num_segments=n)
    first_rows = jax.ops.segment_min(order, seg, num_segments=n)
    sizes = jax.ops.segment_sum(jnp.ones((n,), jnp.int64), seg,
                                num_segments=n)

    outs = []
    for (d_slot, v_slot), op in zip(slots, agg_kinds):
        ok = (spay[v_slot] == 1) if v_slot is not None else None
        okv = ok if ok is not None else jnp.ones((n,), bool)
        cnt = None
        if op != "size":
            cnt = jax.ops.segment_sum(okv.astype(jnp.int64), seg,
                                      num_segments=n)
        if op == "size":
            outs.append((sizes, None))
            continue
        if op == "count":
            outs.append((cnt, None))
            continue
        v = spay[d_slot]
        if op in ("sum", "mean"):
            if v.dtype.kind == "f" or op == "mean":
                acc = jnp.where(okv, v.astype(jnp.float64), 0.0)
                s = jax.ops.segment_sum(acc, seg, num_segments=n)
                if op == "mean":
                    s = s / jnp.where(cnt == 0, 1, cnt).astype(jnp.float64)
                outs.append((s, cnt > 0))
            else:
                acc = jnp.where(okv, v.astype(jnp.int64), jnp.int64(0))
                outs.append((jax.ops.segment_sum(acc, seg, num_segments=n),
                             cnt > 0))
            continue
        # min / max with null-ignoring identities; floats via the same
        # total-order transform + bit cast back as the scan kernel
        is_float = v.dtype.kind == "f"
        if is_float:
            from .sort import _float_total_order
            tv = _float_total_order(v)
        else:
            tv = v
        info = jnp.iinfo(tv.dtype)
        ident = jnp.asarray(info.max if op == "min" else info.min, tv.dtype)
        masked = jnp.where(okv, tv, ident)
        ext = (jax.ops.segment_min(masked, seg, num_segments=n)
               if op == "min"
               else jax.ops.segment_max(masked, seg, num_segments=n))
        if is_float:
            sign_bit = jnp.asarray(info.min, ext.dtype)
            bits = jnp.where(ext < 0, ~(ext ^ sign_bit), ext)
            outs.append((jax.lax.bitcast_convert_type(bits, v.dtype),
                         cnt > 0))
        else:
            outs.append((ext, cnt > 0))

    return num_groups, starts, first_rows, outs


@partial(jax.jit,
         static_argnames=("n_ops", "agg_kinds", "has_valids", "has_alive",
                          "cap"))
def _groupby_kernel_direct(key_operands, agg_datas, agg_valids, *,
                           n_ops: int, agg_kinds: Tuple[str, ...],
                           has_valids: Tuple[bool, ...],
                           has_alive: bool = False, cap: int = 1):
    """Sort-free groupby for a handful of groups (`cap` <= DIRECT_KEY_CAP).

    Same contract as the sort kernels: (num_groups, starts, first_rows,
    outs), groups in key sort order — but every array holds `cap + 1`
    slots, not n rows, and `starts` is unused (no sorted frame exists;
    string extremes, its one reader, keep the sort kernels). Aggregates
    are exact ones only: integer sum/min/max, count, size.

    Slot g's key is the lexicographic minimum of the live keys above slot
    g - 1's: one masked min per key operand, `cap + 1` times, so the slot
    past the cap tells an overflow from a full house. A row's slot is then
    a one-hot against `arange(cap + 1)`, and each aggregate one reduction
    of the (n, cap + 1) product over the rows. A few dozen passes over the
    key columns and one over each payload, against a sort of every row
    plus a gather per payload."""
    n = key_operands[0].shape[0]
    slots = cap + 1
    keys = key_operands[1:] if has_alive else key_operands
    alive = (key_operands[0] == 0) if has_alive else jnp.ones((n,), bool)
    iota = jnp.arange(n, dtype=jnp.int32)

    slot = jnp.full((n,), slots, jnp.int32)       # no slot: matches none
    found = []
    above = alive                                 # keys above the last slot's
    for g in range(slots):
        cand = above
        for o in keys:
            top = jnp.iinfo(o.dtype).max
            m = jnp.min(jnp.where(cand, o, top), initial=top)
            cand = cand & (o == m)                # rows holding the minimum
        found.append(jnp.any(cand))
        slot = jnp.where(cand, jnp.int32(g), slot)
        above = above & (slot == slots)           # cand took every equal key
    num_groups = jnp.sum(jnp.stack(found).astype(jnp.int32))
    hot = slot[:, None] == jnp.arange(slots, dtype=jnp.int32)[None, :]

    def reduce(vals, ok, ident, fn):
        mask = hot if ok is None else hot & ok[:, None]
        return fn(jnp.where(mask, vals[:, None], ident), axis=0,
                  initial=ident)      # an empty batch reduces to it

    first_rows = reduce(iota, None, jnp.int32(n), jnp.min)
    sizes = reduce(jnp.ones((n,), jnp.int64), None, jnp.int64(0), jnp.sum)
    outs = []
    for data, valid, op, hv in zip(agg_datas, agg_valids, agg_kinds,
                                   has_valids):
        ok = valid if hv else None
        if op == "size":
            outs.append((sizes, None))
            continue
        cnt = sizes if ok is None else reduce(
            jnp.ones((n,), jnp.int64), ok, jnp.int64(0), jnp.sum)
        if op == "count":
            outs.append((cnt, None))
        elif op == "sum":
            outs.append((reduce(data.astype(jnp.int64), ok, jnp.int64(0),
                                jnp.sum), cnt > 0))
        else:       # min / max over integers
            info = jnp.iinfo(data.dtype)
            ident = jnp.asarray(info.max if op == "min" else info.min,
                                data.dtype)
            outs.append((reduce(data, ok, ident,
                                jnp.min if op == "min" else jnp.max),
                         cnt > 0))
    return num_groups, jnp.zeros((slots,), jnp.int32), first_rows, outs


def _direct_supports(sig) -> bool:
    return 0 < sig.extra("key_cap", 0) <= DIRECT_KEY_CAP \
        and bool(sig.extra("exact", False))


def groupby_signature(table: Table, key_names, aggs,
                      key_cap: Optional[int] = None):
    """What the registry's `groupby` kernels may condition on: the key
    columns, the key cap the plan carries (0: none, the eager tier) and
    whether every aggregate is exact as a plain integer reduction."""
    from .registry import Signature
    exact = all(
        op in ("size", "count")
        or (op in ("sum", "mean") and table[c].dtype.is_decimal)
        or (op in ("sum", "min", "max") and (
            table[c].dtype.is_integer or table[c].dtype.kind in _EXACT_KINDS))
        for c, op in aggs)
    return Signature.of([table[k] for k in key_names],
                        key_cap=int(key_cap or 0), exact=exact)


def _use_scan_kernel() -> bool:
    """Backend dispatch for the groupby kernel (see _groupby_kernel vs
    _groupby_kernel_scatter — the scan design wins on TPU where scatters
    are ~25x a cumsum, the segment/scatter design wins ~2x on CPU).
    Selection lives in the kernel registry (ops/registry.py,
    docs/kernels.md): "scan" is the universal fallback, "scatter"
    registers for the cpu backend. Override:
    SPARK_RAPIDS_TPU_KERNELS=groupby=scan|scatter (legacy
    SPARK_RAPIDS_TPU_GROUPBY_KERNEL honored as an alias)."""
    from .registry import REGISTRY
    return REGISTRY.select("groupby").name == "scan"


def groupby_aggregate(table: Table,
                      key_names: Sequence[Union[int, str]],
                      aggs: Sequence[Tuple[Union[int, str], str]],
                      _cap: Optional[int] = None,
                      _alive: Optional[jnp.ndarray] = None):
    """Group by `key_names`, apply `aggs` [(column, op)] with op in
    sum|count|min|max|mean|size. Returns keys + one column per agg, named
    "op(col)". Group order = key sort order (deterministic).

    `_cap` is internal (see groupby_aggregate_capped): a static output size
    that makes the whole aggregation traceable under jax.jit. `_alive` is a
    (num_rows,) bool excluding padded rows entirely (see
    groupby_aggregate_capped's `alive`).

    The kernel and its finish run in an `ops.groupby` span (with `rows`,
    `groups`: the count once it is read, the key cap under a cap; `kernel`;
    `planes`, the 32-bit decimal planes summed; `keys`, how the groups'
    keys came back: `ride`, `take` or `ride+take`) and under the scope of
    that name, which a capped program's `device_op_owners(nested=True)`
    reads back below its operator's."""
    with span("ops.groupby", rows=table.num_rows) as sp, \
            jax.named_scope("ops.groupby"):
        out = _groupby(table, key_names, aggs, _cap, _alive, sp)
        if _cap is None:
            # the eager tier: blocked inside the span, so that the span
            # holds the finish's device work too (the group count's read
            # has waited for the kernel already)
            with span("plan.wait", site="groupby"):
                jax.block_until_ready([c.data for c in out.columns])
        return out


# what the keyed aggregates under a `with group_keys.collect()` did with
# their groups' keys: (`ride` / `take` / `ride+take`, key planes x slots
# that went through `take`) each (the executor's `group_key_slots_gathered`)
group_keys = Tally()


def _key_rides(col: Column) -> bool:
    """Whether the key's sort operands ARE its data (`_key_operands`: the
    data zeroed under a null, behind the null rank), so that a group's key
    is read off the sorted operands at the run's first row. Not a float
    (the total-order transform), a DECIMAL128 (biased limbs) or a string
    (packed words)."""
    k = col.dtype.kind
    return k == Kind.BOOL or col.dtype.is_integer or k in _EXACT_KINDS


def _key_planes(cols) -> int:
    """The planes a `take` of `cols` gathers: a column's data, its mask."""
    return sum(1 + (c.validity is not None) for c in cols)


def _groupby(table, key_names, aggs, _cap, _alive, sp):
    """`groupby_aggregate` inside its span `sp`, which it stamps with the
    groups, the kernel, the planes and how the keys came back once they
    are known."""
    keys = [table[k] for k in key_names]
    if not keys:
        raise ValueError("groupby requires at least one key column")
    for c in keys:
        if c.dtype.kind in (Kind.LIST, Kind.STRUCT):
            raise TypeError("nested group keys are not supported")

    operands = []
    key_ops: List[Tuple[int, ...]] = []     # key -> its operands' indices
    for c in keys:
        ops_c = _key_operands(c, True, None)
        key_ops.append(tuple(range(len(operands),
                                   len(operands) + len(ops_c))))
        operands.extend(ops_c)
    if _alive is not None:
        # leading dead-flag operand: dead rows sort last as their own
        # groups, counted out of num_groups by the kernel (has_alive)
        operands = [jnp.where(_alive, jnp.int32(0), jnp.int32(1))] + operands
        key_ops = [tuple(k + 1 for k in ks) for ks in key_ops]

    from . import decimal_utils
    n = table.num_rows
    agg_datas: List = []
    agg_valids: List = []
    agg_kinds: List[str] = []

    def push(data, valid, kind: str) -> int:
        agg_datas.append(data)
        agg_valids.append(valid)
        agg_kinds.append(kind)
        return len(agg_kinds) - 1

    slot_of: List[int] = []                 # agg -> its first kernel slot
    decimal_parts = {}                      # agg -> (plane slots, count slot)
    string_extremes: List[Tuple] = []       # (agg idx, col, col_ref, op)
    placeholder = jnp.zeros((n,), jnp.int8)
    for i, (col_ref, op) in enumerate(aggs):
        if op not in AGG_OPS:
            raise ValueError(f"unknown aggregation {op!r}")
        if op in ("size", "count"):
            # only validity (or nothing) is consumed; data is a placeholder
            c = keys[0] if op == "size" else table[col_ref]
            slot_of.append(push(placeholder,
                                None if op == "size" else c.validity, op))
        elif op in ("min", "max") and table[col_ref].dtype.is_string:
            # strings: resolved by an extra value-ordered sort (below); the
            # kernel carries a placeholder so outputs stay index-aligned.
            # A column's first slot carries the per-group non-null count
            # (locates max when one shared asc sort serves both extremes).
            first_for_col = col_ref not in [r for _, _, r, _ in string_extremes]
            string_extremes.append((i, table[col_ref], col_ref, op))
            slot_of.append(push(
                placeholder,
                table[col_ref].validity if first_for_col else None,
                "count" if first_for_col else "size"))
        elif op in ("sum", "mean") and table[col_ref].dtype.is_decimal:
            # a decimal is summed as its 32-bit planes, each an int64 sum;
            # finish_sum / finish_mean put a group's planes together
            c = table[col_ref]
            planes = [push(p, c.validity, "sum")
                      for p in decimal_utils.limb_planes(c)]
            decimal_parts[i] = (planes, push(placeholder, c.validity,
                                             "count"))
            slot_of.append(planes[0])
        else:
            c = table[col_ref]
            if not (c.dtype.is_integer or c.dtype.is_floating
                    or c.dtype.kind in _EXACT_KINDS) or (
                        c.dtype.is_decimal and op not in ("min", "max")):
                raise TypeError(f"{op} over {c.dtype} values is not supported")
            slot_of.append(push(c.data, c.validity, op))

    from .registry import REGISTRY
    choice = REGISTRY.select("groupby",
                             groupby_signature(table, key_names, aggs, _cap))
    # "direct" is chosen only for exact aggregates: no string extreme
    extra = ({"cap": _cap} if choice.name == "direct"
             else {"gather_payloads": bool(decimal_parts)})
    # The groups' keys: gathered through the groups' first rows, or (the
    # `scan` kernel's compaction sort) the keys' own sort operands riding
    # in the row number's place. Priced before the kernel runs, over the
    # most groups there can be: their gathered slots a plane against the
    # frame's rows a word added (the row number leaves when every key
    # rides). A key whose operands are not its data keeps `take`.
    riding = [i for i, c in enumerate(keys) if _key_rides(c)]
    ride_ops = [k for i in riding for k in key_ops[i]]
    if choice.name != "scan" or not words_ride(
            n, sum(plane_words([operands[k] for k in ride_ops]))
            - (len(riding) == len(keys)),
            n if _cap is None else min(_cap, n),
            _key_planes(keys[i] for i in riding)):
        riding, ride_ops = [], []
    if choice.name == "scan":
        extra.update(ride_keys=tuple(ride_ops),
                     with_starts=bool(string_extremes))
    num_groups, first_sorted, first_rows_full, outs, *rode = choice.fn(
        tuple(operands), tuple(agg_datas), tuple(agg_valids),
        n_ops=len(operands), agg_kinds=tuple(agg_kinds),
        has_valids=tuple(v is not None for v in agg_valids),
        has_alive=_alive is not None, **extra)
    if _cap is None:
        with span("ops.host_sync", site="groupby.groups"):
            g = int(num_groups)  # the one host sync: waits for the kernel
    else:
        # slice what exists, pad the rest below (a fixed-cap jit pipeline
        # must accept small batches, and a too-small cap must be retryable
        # with a bigger one regardless of n)
        g = min(_cap, n)
    taken = [c for i, c in enumerate(keys) if i not in riding]
    path = "+".join(w for w, some in (("ride", riding), ("take", taken))
                    if some)
    group_keys.note((path, _key_planes(taken) * g))
    sp.set_metadata(groups=g if _cap is None else _cap, kernel=choice.name,
                    planes=sum(len(p) for p, _ in decimal_parts.values()),
                    keys=path)
    # key columns. A key that rode: its operands at the groups' starts,
    # the data as it is (0 under a null) and the null rank (1: valid,
    # `_key_operands`' NULLS_FIRST ascending). Any other: gathered at the
    # row index (original frame) of each group's first sorted row, carried
    # through the compaction sort, no order gather
    if taken:
        first_rows = jnp.clip(first_rows_full[:g], 0, max(n - 1, 0))
    rode = iter(_from_key_words(
        rode[0], dtypes=tuple(str(operands[k].dtype) for k in ride_ops),
        g=g) if riding else ())
    out_cols = []
    for i, c in enumerate(keys):
        if i not in riding:
            # first_rows is non-negative by construction: skip take()'s
            # any<0 sync
            out_cols.append(take(c, first_rows, _has_negative=False))
            continue
        rank = None if c.validity is None else next(rode)
        out_cols.append(Column(
            dtype=c.dtype, length=g,
            data=next(rode).astype(c.dtype.storage_dtype()),
            validity=None if rank is None else rank == 1))
    names = [table.names[k] if isinstance(k, int) else k for k in key_names]

    # string min/max: ONE extra value-ordered sort per string column. With
    # ascending NULLS_LAST order, each group's min sits at its first sorted
    # row and its max at (start + non-null count - 1); a max-only column
    # sorts descending so its extreme also sits at the start. take()
    # propagates the gathered row's validity, so an all-null group (whose
    # extreme row is null under NULLS_LAST) comes out null — Spark semantics.
    string_results = {}
    by_col = {}
    for agg_idx, c, ref, op in string_extremes:
        by_col.setdefault(ref, {"col": c, "ops": [], "cnt_idx": None})
        by_col[ref]["ops"].append((agg_idx, op))
        if by_col[ref]["cnt_idx"] is None:
            by_col[ref]["cnt_idx"] = agg_idx        # first slot carries count
    for ref, info in by_col.items():
        c = info["col"]
        wants = {op for _, op in info["ops"]}
        ascending = "min" in wants                  # max-only sorts desc
        vops = _key_operands(c, ascending, NULLS_LAST)
        srt = jax.lax.sort([*operands, *vops,
                            jnp.arange(n, dtype=jnp.int32)],
                           num_keys=len(operands) + len(vops), is_stable=True)
        order2 = srt[-1]
        # padded entries hold n: clip for the gathers — rows past
        # num_groups are garbage by contract, masked by the capped valid
        # vector
        starts = jnp.clip(first_sorted[:g], 0, max(n - 1, 0))
        at_start = take(c, jnp.take(order2, starts, axis=0),
                        _has_negative=False)
        at_last = None
        if wants == {"min", "max"}:
            cnt = outs[slot_of[info["cnt_idx"]]][0][:g]   # non-null count
            last_pos = starts + jnp.maximum(cnt, 1).astype(jnp.int32) - 1
            at_last = take(c, jnp.take(order2, last_pos, axis=0),
                           _has_negative=False)
        for agg_idx, op in info["ops"]:
            if op == "min" or wants != {"min", "max"}:
                string_results[agg_idx] = at_start
            else:
                string_results[agg_idx] = at_last

    for i, (col_ref, op) in enumerate(aggs):
        data, valid = outs[slot_of[i]]
        cname = (col_ref if isinstance(col_ref, str)
                 else table.names[col_ref]) if op != "size" else "*"
        names.append(f"{op}({cname})")
        if i in string_results:
            out_cols.append(string_results[i])
            continue
        if i in decimal_parts:
            planes, cnt_slot = decimal_parts[i]
            finish = (decimal_utils.finish_sum if op == "sum"
                      else decimal_utils.finish_mean)
            out_cols.append(finish([outs[k][0][:g] for k in planes],
                                   outs[cnt_slot][0][:g],
                                   table[col_ref].dtype))
            continue
        src_dt = dtypes.INT64 if op == "size" else table[col_ref].dtype
        dt = _agg_value_dtype(op, src_dt)
        d = data[:g]
        if dt.kind == Kind.INT64 and d.dtype != jnp.int64:
            d = d.astype(jnp.int64)
        v = None if valid is None else valid[:g]
        out_cols.append(Column(dtype=dt, length=g,
                               data=d.astype(dt.storage_dtype()), validity=v))

    if _cap is None:
        # every kernel puts its groups out in the key operands' order
        return Table(out_cols, names, ordered_by=names[:len(keys)])
    out_cols = [_pad_column(c, _cap) for c in out_cols]
    valid = jnp.arange(_cap, dtype=jnp.int32) < num_groups
    return Table(out_cols, names), valid, num_groups > _cap


def _pad_column(col: Column, to: int) -> Column:
    """Pad a column to `to` rows with masked garbage (capped-output
    contract: rows past the real group count are selected away by the
    caller's valid vector)."""
    n = col.length
    if n >= to:
        return col
    extra = to - n
    validity = None
    if col.validity is not None:
        validity = jnp.concatenate([col.null_mask,
                                    jnp.zeros((extra,), bool)])
    if col.dtype.is_string:
        last = col.offsets[-1] if n else jnp.int32(0)
        offsets = jnp.concatenate(
            [col.offsets, jnp.full((extra,), last, jnp.int32)])
        return Column(dtype=col.dtype, length=to, data=col.data,
                      offsets=offsets, validity=validity)
    data = jnp.concatenate(
        [col.data, jnp.zeros((extra,) + col.data.shape[1:], col.data.dtype)])
    return Column(dtype=col.dtype, length=to, data=data, validity=validity)


def groupby_aggregate_capped(table: Table,
                             key_names: Sequence[Union[int, str]],
                             aggs: Sequence[Tuple[Union[int, str], str]],
                             key_cap: int,
                             alive: Optional[jnp.ndarray] = None):
    """Jit-friendly groupby: identical semantics to groupby_aggregate but a
    static `key_cap` output size instead of the group-count host sync, so
    whole pipelines fuse into one XLA program (the same padded contract as
    parallel.distributed_groupby).

    `alive`, if given, is a (num_rows,) bool excluding rows entirely (not
    null-semantics — the row just isn't there): the contract that lets a
    capped upstream op (inner_join_capped, a filter-as-mask) feed this
    groupby inside ONE jit without compaction.

    Returns (Table padded to key_cap rows, valid (key_cap,) bool, overflow
    scalar). Rows past the real group count are garbage and masked by
    `valid`; overflow True means key_cap was too small — retry bigger
    (SplitAndRetry contract)."""
    return groupby_aggregate(table, key_names, aggs, _cap=key_cap,
                             _alive=alive)


# ---- kernel-registry wiring (ops/registry.py, docs/kernels.md) --------------
# the scan design is the universal lowering (TPU-first: scatters are ~25x a
# cumsum there); the scatter/segment design registers for the cpu backend,
# where it measured ~2x the scan design (a CPU A/B, see _groupby_kernel)
from .registry import REGISTRY as _REGISTRY  # noqa: E402

_REGISTRY.register("groupby", "scan", fn=_groupby_kernel, fallback=True)
# before "scatter": where the signature allows it (a key cap of at most
# DIRECT_KEY_CAP, exact aggregates) it outranks both sort designs
_REGISTRY.register("groupby", "direct", fn=_groupby_kernel_direct,
                   backends=("cpu", "tpu"), supports=_direct_supports)
_REGISTRY.register("groupby", "scatter", fn=_groupby_kernel_scatter,
                   backends=("cpu",))
