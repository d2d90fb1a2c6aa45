"""Window functions over partitions (Spark's `WindowExec`; the RAPIDS
plugin's "running window"): `sum`, `min`, `max` and `count` over the frame
`ROWS BETWEEN UNBOUNDED PRECEDING AND CURRENT ROW`.

The kernel is the sorted group-by's first half with another second half
(ops/aggregate.py: `sorted_runs` is the one definition of the key sort
with its riding payloads and of the flags of equal-key runs): one
`lax.sort` by (partition keys, order keys, row number), the child's
columns riding it, then per function a FORWARD segmented inclusive scan
whose value stays on every row (`ops/scans.py:running_in_runs`, two-level
scans throughout). No compaction sort, no finish, and nothing is scattered
back to input order: the output is in (partition, order) order, which SQL
leaves open. A key column of plain integer storage is read back from its
sorted operands instead of riding beside them.

A child that says its rows lie in (partition, order) order already
(`Table.ordered_by`: the sorted group-by's output does) is not sorted
again: the same program without the sort.

Null rules (Spark's): a NULL partition key is a partition of its own, as
in GROUP BY; a NULL order key sorts first ascending and last descending;
a NULL value is skipped; `sum` / `min` / `max` are NULL while the running
count of non-null values is 0; `count` is never NULL. `sum` over an
integer is INT64 and wraps like a Java long; over a decimal(p, s) it is
Spark's `decimal(p + 10, s)`, summed as 32-bit planes and put together by
`decimal_utils.finish_sum`, NULL on overflow.

Capped tier (`alive`): the dead-row flag leads the partition operands, so
dead rows sort last as a partition of their own and carry into nothing.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes
from ..columnar import Column, Table
from ..dtypes import Kind
from ..utils.tracing import Tally, span
from .aggregate import _EXACT_KINDS, run_boundaries, sorted_runs
from .gather import take
from .scans import running, running_in_runs
from .sort import _key_operands

WINDOW_OPS = ("sum", "min", "max", "count")
FRAMES = ("running",)     # ROWS BETWEEN UNBOUNDED PRECEDING AND CURRENT ROW
KERNEL = "sort_scan"
# what the eager windows under a `with windows.collect()` did: (partitions,
# `sort` or `child`, the sort's key: `packed`, `operands` or `none`) each
# (the executor's metrics and counters)
windows = Tally()


def check_frame(frame: str, op: str) -> None:
    """ValueError, by name, for a frame or function the kernel does not
    lower (the node and the verifier ask before anything runs)."""
    if frame not in FRAMES:
        raise ValueError(f"window frame {frame!r} is not lowered (have "
                         f"{FRAMES})")
    if op not in WINDOW_OPS:
        raise ValueError(f"window function {op!r} is not lowered (have "
                         f"{WINDOW_OPS})")


def _plain(dt: dtypes.DType) -> bool:
    """Storage a sort operand IS: read as a signed integer as it lies."""
    return dt.is_integer or dt.kind in _EXACT_KINDS


def result_type(op: str, dt: Optional[dtypes.DType]) -> dtypes.DType:
    """Spark's type of `op(column of dt) OVER (...)`; TypeError, by name,
    for a value type the kernel does not lower. `count` reads validity
    alone and takes any column."""
    if op == "count":
        return dtypes.INT64
    if dt is None:
        return None
    if dt.kind == Kind.DECIMAL128 or not _plain(dt):
        raise TypeError(f"window {op} over {dt!r} values is not lowered "
                        "(integers, dates, timestamps and decimals of at "
                        "most 18 digits are)")
    if op != "sum":
        return dt
    if dt.is_decimal:
        from . import decimal_utils
        return decimal_utils.sum_type(dt)
    if not dt.is_integer:
        raise TypeError(f"window sum over {dt!r} values is not lowered")
    return dtypes.INT64


def check_key(name: str, dt: Optional[dtypes.DType], role: str) -> None:
    """TypeError, by name, for a partition or order key that is not
    fixed-width."""
    if dt is not None and (dt.is_string or dt.is_nested):
        raise TypeError(f"window {role} key {name!r} is {dt!r}: a STRING or "
                        "nested key is not lowered (fixed-width keys are)")


@jax.jit
def _operand_ranges(key_operands):
    """(k, 2) int64: each key operand's least and greatest value."""
    return jnp.stack([jnp.stack([jnp.min(o), jnp.max(o)]).astype(jnp.int64)
                      for o in key_operands])


def _key_packing(key_operands, n: int, n_part_ops: int):
    """The layout of ONE sort key that holds every key operand and the row
    number, or None where they do not fit 63 bits: per operand its least
    value and the position and mask of its field (the first operand
    highest, the row number lowest), the shift that leaves the partition's
    fields, and the row number's mask. One read of the operands' ranges: a
    sort's compile time follows its KEY operands (five of them, two
    64-bit, with eight riding words: 494 s for a described v5e; one 64-bit
    key with the same words: PERF.md section 6, PR 47), and item and day
    numbers fill a fraction of their 64 bits. The layout is data, not
    shape: other ranges run the same program."""
    with span("ops.host_sync", site="window.key_ranges"):
        ranges = jax.device_get(_operand_ranges(tuple(key_operands)))
    row_bits = max(1, (n - 1).bit_length())
    bits = [(int(hi) - int(lo)).bit_length() for lo, hi in ranges]
    if row_bits + sum(bits) > 63:
        return None
    shifts, at = [], row_bits
    for b in reversed(bits):
        shifts.append(at)
        at += b
    shifts.reverse()
    # host arrays: they cross with the kernel's call, not one by one
    wide = lambda xs: np.asarray(xs, np.int64)
    return (wide([int(lo) for lo, _ in ranges]), wide(shifts),
            wide([(1 << b) - 1 for b in bits]),
            wide(shifts[n_part_ops - 1] if n_part_ops else 63),
            wide((1 << row_bits) - 1))


@partial(jax.jit, static_argnames=("n_part_ops", "fns", "presorted"))
def _window_kernel(key_operands, planes, packing=None, *, n_part_ops: int,
                   fns: Tuple[Tuple, ...], presorted: bool = False):
    """-> (partitions, sorted key operands, order, planes in that order,
    [(data, count or None)] a function). `key_operands`: the partition
    keys' `n_part_ops` operands (a dead-row flag first under a cap), then
    the order keys'. `fns`: (op, data plane, validity plane or -1, the
    value's DType where a decimal is summed) a function. `presorted`: the
    rows lie in key order; nothing is sorted and `order` is the row
    number. `packing` (`_key_packing`): the operands and the row number
    are sorted as one 64-bit key and read back from it. `partitions`
    counts every run, a cap's dead one too."""
    n = key_operands[0].shape[0] if key_operands else planes[0].shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    if presorted:
        sorted_ops, order, spay = list(key_operands), iota, list(planes)
        head = run_boundaries(sorted_ops[:n_part_ops], n)
    elif packing is not None:
        lo, shift, mask, part_shift, row_mask = packing
        key = iota.astype(jnp.int64)
        for j, o in enumerate(key_operands):
            key = key | ((o.astype(jnp.int64) - lo[j]) << shift[j])
        # unique keys: the sort need not be a stable one
        key, *spay = jax.lax.sort([key, *planes], num_keys=1,
                                  is_stable=False)
        sorted_ops = [(((key >> shift[j]) & mask[j]) + lo[j]).astype(o.dtype)
                      for j, o in enumerate(key_operands)]
        order = (key & row_mask).astype(jnp.int32)
        head = run_boundaries([key >> part_shift], n)
    else:
        # the row number is the last key: no two rows tie, the order is
        # the stable sort's (rows that tie on every key keep their input
        # order), and the program compiles in half the time
        sorted_ops, order, spay, head = sorted_runs(
            key_operands, iota, planes, len(key_operands), False,
            by_row=True, n_run_ops=n_part_ops)
    rank = running(head.astype(jnp.int32)) - 1
    outs: List = []
    counts = {}              # validity plane -> running non-null count
    for op, d_slot, v_slot, dt in fns:
        ok = cnt = None
        if v_slot >= 0:
            ok = spay[v_slot] == 1
            if v_slot not in counts:        # in 32 bits: rows fit an int32
                counts[v_slot] = running_in_runs(
                    ok.astype(jnp.int32), head, rank)
            cnt = counts[v_slot]
        if op == "count":
            # without a validity: the row's number in its partition
            if cnt is None:
                cnt = running_in_runs(jnp.ones((n,), jnp.int32), head, rank)
            outs.append((cnt.astype(jnp.int64), None))
            continue
        v = spay[d_slot]
        if op == "sum" and dt is not None:
            from . import decimal_utils
            parts = decimal_utils.limb_planes(
                Column(dtype=dt, length=n, data=v))
            sums = [running_in_runs(
                p.astype(jnp.int64) if ok is None
                else jnp.where(ok, p.astype(jnp.int64), jnp.int64(0)),
                head, rank) for p in parts]
            outs.append((tuple(sums), cnt))
            continue
        v = v.astype(jnp.int64)
        if op == "sum":
            acc = v if ok is None else jnp.where(ok, v, jnp.int64(0))
            outs.append((running_in_runs(acc, head, rank), cnt))
            continue
        # min is the maximum of the complements
        if op == "min":
            v = ~v
        lowest = jnp.int64(jnp.iinfo(jnp.int64).min)
        ext = running_in_runs(v if ok is None else jnp.where(ok, v, lowest),
                              head, rank, "max")
        outs.append((~ext if op == "min" else ext, cnt))
    partitions = jnp.sum(head.astype(jnp.int32))
    return partitions, sorted_ops, order, spay, outs


def window_functions(table: Table, partition_by: Sequence[str],
                     order_by: Sequence[str], ascending: Sequence[bool],
                     functions: Sequence[Tuple[str, str, str]],
                     frame: str = "running",
                     alive: Optional[jnp.ndarray] = None):
    """`table`'s columns and one column a function `(out_name, op,
    column)`: `op(column) OVER (PARTITION BY partition_by ORDER BY order_by
    <frame>)`, the rows in (partition, order) order.

    Eager (`alive` None): -> Table; the kernel and the wait for it lie in
    an `ops.window` span (`rows`, `partitions`, `functions`, `frame`,
    `kernel`, `planes`: the 32-bit words riding the sort, `sorted`: `sort`,
    or `child` where `table.ordered_by` says the rows lie in order).
    Capped (`alive`: (rows,) bool, dead rows excluded entirely):
    -> (Table, alive in the output's order); traceable under jit."""
    with span("ops.window", rows=table.num_rows, functions=len(functions),
              frame=frame, kernel=KERNEL) as sp, \
            jax.named_scope("ops.window"):
        out = _window(table, list(partition_by), list(order_by),
                      list(ascending), list(functions), frame, alive, sp)
        if alive is None:
            # blocked inside the span, so that the span holds the device
            # work of the kernel and of the finish
            with span("plan.wait", site="window"):
                jax.block_until_ready([c.data for c in out.columns])
        return out


def _ordered_already(table: Table, keys: List[str],
                     ascending: List[bool]) -> bool:
    """Whether `table` says its rows lie in the order the kernel's sort
    would give: sorted by exactly these leading columns, ascending, nulls
    first (what `_key_operands` orders by), ties left in place."""
    return bool(keys) and all(ascending) \
        and tuple(table.ordered_by[:len(keys)]) == tuple(keys)


def _window(table, partition_by, order_by, ascending, functions, frame,
            alive, sp):
    n = table.num_rows
    for _, op, _ in functions:
        check_frame(frame, op)
    for k in partition_by:
        check_key(k, table[k].dtype, "partition")
    for k in order_by:
        check_key(k, table[k].dtype, "order")
    out_types = [result_type(op, table[c].dtype) for _, op, c in functions]
    read = {c for _, op, c in functions}
    presorted = alive is None and _ordered_already(
        table, partition_by + order_by, [True] * len(partition_by) + ascending)

    # ---- key operands, and the key columns read back from them -----------
    operands: List = []
    if alive is not None:
        operands.append(jnp.where(alive, jnp.int32(0), jnp.int32(1)))
    rebuilt = {}            # column -> (first operand, past last, ascending)
    n_part_ops = len(operands)
    for i, (k, asc) in enumerate(zip(partition_by + order_by,
                                     [True] * len(partition_by) + ascending)):
        ops = _key_operands(table[k], asc, None)
        if _plain(table[k].dtype) and k not in read and k not in rebuilt:
            rebuilt[k] = (len(operands), len(operands) + len(ops), asc)
        operands.extend(ops)
        if i < len(partition_by):
            n_part_ops = len(operands)

    # ---- planes riding the sort (under `presorted`: read where they lie) --
    planes: List = []
    riding = {}             # column -> (data plane, validity plane), or -1
    gathered = []           # columns whose buffers are not one word a row

    def put(c: Column, data_too: bool):
        d = v = -1
        if data_too:
            d = len(planes)
            planes.append(c.data)
        if c.validity is not None:
            v = len(planes)
            planes.append(c.validity.astype(jnp.int8))
        return d, v

    for name, c in zip(table.names, table.columns):
        rides = not (c.dtype.is_string or c.dtype.is_nested) \
            and c.data.ndim == 1
        if name in rebuilt:
            continue
        if presorted:
            if name in read:
                riding[name] = put(c, rides)
        elif rides:
            riding[name] = put(c, True)
        else:
            gathered.append(name)
            if name in read:        # a count: the validity alone
                riding[name] = put(c, False)
    fns = tuple(
        (op, *riding[c], table[c].dtype
         if op == "sum" and table[c].dtype.is_decimal else None)
        for _, op, c in functions)
    words = sum(max(1, p.dtype.itemsize // 4) for p in planes)
    # one key for the sort where the operands' ranges allow it (eager: a
    # read decides; under a cap the operands stay the sort's keys)
    packing = None
    if alive is None and not presorted and n:
        packing = _key_packing(operands, n, n_part_ops)
    key = ("none" if presorted else
           "packed" if packing is not None else "operands")
    sp.set_metadata(planes=0 if presorted else words,
                    sorted="child" if presorted else "sort", key=key)

    partitions, sorted_ops, order, spay, outs = _window_kernel(
        tuple(operands), tuple(planes), packing, n_part_ops=n_part_ops,
        fns=fns, presorted=presorted)
    if alive is None:
        with span("ops.host_sync", site="window.partitions"):
            held = int(partitions)          # waits for the kernel
        sp.set_metadata(partitions=held)
        windows.note((held, "child" if presorted else "sort", key))

    # ---- the child's columns in the output's order ---------------------------
    cols: List[Column] = []
    for name, c in zip(table.names, table.columns):
        if presorted:
            cols.append(c)
        elif name in rebuilt:
            i0, i1, asc = rebuilt[name]
            data = sorted_ops[i1 - 1]
            valid = None if c.validity is None else \
                sorted_ops[i0] == (1 if asc else 0)     # the null rank
            cols.append(Column(dtype=c.dtype, length=n,
                               data=data if asc else ~data, validity=valid))
        elif name in gathered:
            cols.append(take(c, order, _has_negative=False))
        else:
            d, v = riding[name]
            cols.append(Column(dtype=c.dtype, length=n, data=spay[d],
                               validity=None if v < 0 else spay[v] == 1))

    # ---- one column a function ---------------------------------------------
    names = list(table.names)
    for (out_name, op, c), dt, (data, cnt) in zip(functions, out_types, outs):
        names.append(out_name)
        if op == "count":
            cols.append(Column(dtype=dt, length=n, data=data))
        elif isinstance(data, tuple):
            from . import decimal_utils
            cols.append(decimal_utils.finish_sum(
                list(data), jnp.ones((n,), jnp.int32) if cnt is None else cnt,
                table[c].dtype))
        else:
            cols.append(Column(dtype=dt, length=n,
                               data=data.astype(dt.storage_dtype()),
                               validity=None if cnt is None else cnt > 0))
    if alive is not None:
        return Table(cols, names), sorted_ops[0] == 0
    return Table(cols, names, ordered_by=(
        partition_by + order_by if all(ascending) else ()))
