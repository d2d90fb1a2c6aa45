"""An eager outer join's output columns (ops/gather.py:outer_join_columns):
made the way the join's own counts say, and row for row, through validity,
what `take_table(left, lmap) ++ take_table(right, rmap)` gives over the
public `left_join` / `full_join` maps."""
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu import dtypes, ops
from spark_rapids_tpu.columnar import Column, Table
from spark_rapids_tpu.ops import gather
from spark_rapids_tpu.ops.gather import (FEW_KEPT, KEPT_FLOOR,
                                         outer_join_paths)

FLOOR = KEPT_FLOOR


# ---- the path table: arithmetic over the join's counts, nothing else -----

@pytest.mark.parametrize("counts, want", [
    # (rows_left, rows_right, matched, unmatched, unmatched_right)
    # the left side: as many slots as rows, and one off
    ((100, 50, 0, 100, 50), ("as_is", "nulls", "none")),
    ((100, 50, 40, 60, 10), ("as_is", "sparse", "positions")),
    ((100, 50, 41, 60, 10), ("take", "sparse", "positions")),
    ((1, 1, 1, 0, 0), ("as_is", "sparse", "empty")),
    ((0, 50, 0, 0, 50), ("as_is", "nulls", "none")),
    ((100, 0, 0, 100, 0), ("as_is", "nulls", "empty")),
    ((0, 0, 0, 0, 0), ("as_is", "nulls", "empty")),
    # the right side's body: nothing, few of the slots, few in all, many
    ((10 ** 7, 10 ** 7, 0, 10 ** 7, 10 ** 7), ("as_is", "nulls", "none")),
    ((10 ** 7, 10 ** 7, 440, 10 ** 7 - 440, 10 ** 7 - 440),
     ("as_is", "sparse", "sort")),
    ((FEW_KEPT * 10 ** 6, 1, 10 ** 6, (FEW_KEPT - 1) * 10 ** 6, 0),
     ("as_is", "sparse", "empty")),
    ((FEW_KEPT * 10 ** 6, 1, 10 ** 6 + 1, (FEW_KEPT - 1) * 10 ** 6 - 1, 0),
     ("as_is", "take", "empty")),
    ((FLOOR, FLOOR, FLOOR, 0, 0), ("as_is", "sparse", "empty")),
    ((FLOOR + 1, FLOOR + 1, FLOOR + 1, 0, 0), ("as_is", "take", "empty")),
    ((1_500_000, 14_834_663, 14_834_663, 500_002, 0),      # q13.batch
     ("take", "take", "empty")),
    ((6_597_944, 3_350_369, 440, 6_597_504, 3_349_929),    # q97.batch
     ("as_is", "sparse", "sort")),
    # a full join's tail: none lonely, all, few of the rows, few, many
    ((10, 10 ** 6, 10 ** 6, 0, 0), ("take", "take", "empty")),
    ((10, 10 ** 6, 0, 10, 10 ** 6), ("as_is", "nulls", "none")),
    ((10, FEW_KEPT * 10 ** 6, 5, 5, 10 ** 6),
     ("as_is", "sparse", "positions")),
    ((10, FEW_KEPT * 10 ** 6, 5, 5, 10 ** 6 + 1),
     ("as_is", "sparse", "sort")),
    ((10, FLOOR + 5, 5, 5, FLOOR), ("as_is", "sparse", "positions")),
    ((10, FLOOR + 6, 5, 5, FLOOR + 1), ("as_is", "sparse", "sort")),
], ids=lambda v: "-".join(map(str, v)))
def test_the_paths_follow_the_counts(counts, want):
    assert outer_join_paths("full_outer", *counts) == want
    # a left join has no tail, and nothing else differs
    assert outer_join_paths("left_outer", *counts[:4], 0) == want[:2] + ("",)
    # a column without a plane takes the plain gather beside the others
    ragged = outer_join_paths("full_outer", *counts, ragged=True)
    assert ragged == (want[0],
                      want[1] + "+gather" * (want[1] == "sparse"),
                      want[2] + "+gather" * (want[2] == "sort"))


# ---- every path equals the gathers through the public maps ---------------

DEC64, DEC128 = dtypes.decimal(15, 2), dtypes.decimal(25, 2)
TYPES = {
    "int64": (dtypes.INT64, lambda i: 1000 + 7 * i),
    "date32": (dtypes.DATE32, lambda i: 10_000 + i),
    "decimal64": (DEC64, lambda i: 10 ** 12 + 31 * i),
    "decimal128": (DEC128, lambda i: (1 << 70) + 3 * i),
    "bool": (dtypes.BOOL, lambda i: i % 3 == 0),
    "string": (dtypes.STRING, lambda i: "s" * (i % 4) + str(i)),
}


def _table(side: str, keys, nullable: bool, key_nulls=()) -> Table:
    """A side: its int64 key (nulls at `key_nulls`) and one payload column
    of every type, each nullable (a null every fifth row) or not."""
    n = len(keys)
    cols = {f"{side}k": Column.from_pylist(
        [None if i in key_nulls else int(k) for i, k in enumerate(keys)],
        dtypes.INT64)}
    for name, (dt, value) in TYPES.items():
        cols[f"{side}_{name}"] = Column.from_pylist(
            [None if nullable and i % 5 == 2 else value(i)
             for i in range(n)], dt)
    return Table(list(cols.values()), names=list(cols))


def _sides(case: str):
    """-> (left keys, right keys, null left rows, null right rows)."""
    rng = np.random.default_rng(len(case))
    if case == "sparse":                # 3 of 200 slots match
        return np.arange(200), np.r_[[5, 50, 150], 1000 + np.arange(80)], \
            (), ()
    if case == "no_match":
        return np.arange(60), 100 + np.arange(40), (), ()
    if case == "matched_once":          # a permutation: as_is, no null
        return np.arange(70), rng.permutation(70), (), ()
    if case == "dense":                 # half the slots match, unique keys
        return np.arange(120), rng.permutation(240)[:120], (), ()
    if case == "fan_out":               # duplicate right keys: left `take`
        return np.arange(50), rng.integers(0, 60, 200), (), ()
    if case == "few_lonely":            # 2 of 130 right rows are alone
        return np.arange(128), np.r_[rng.permutation(128), [500, 501]], \
            (), ()
    if case == "null_keys":
        return np.arange(90), rng.permutation(120)[:80], \
            (3, 4, 40), (0, 7, 79)
    if case == "empty_left":
        return np.arange(0), np.arange(30), (), ()
    if case == "empty_right":
        return np.arange(30), np.arange(0), (), ()
    if case == "both_empty":
        return np.arange(0), np.arange(0), (), ()
    raise KeyError(case)


CASES = {   # case -> (left, right body, a full join's tail) under FLOOR 4
    "sparse": ("as_is", "sparse+gather", "sort+gather"),
    "no_match": ("as_is", "nulls", "none"),
    "matched_once": ("as_is", "take", "empty"),
    "dense": ("as_is", "take", "sort+gather"),
    "fan_out": ("take", "take", "sort+gather"),
    "few_lonely": ("as_is", "take", "positions"),
    "null_keys": ("as_is", "take", "sort+gather"),
    "empty_left": ("as_is", "nulls", "none"),
    "empty_right": ("as_is", "nulls", "empty"),
    "both_empty": ("as_is", "nulls", "empty"),
}


def _gathered(table: Table, idx) -> list:
    """`take_table(table, idx)` as python rows a column (from no rows a
    gather has none to read: every index is then a -1)."""
    if not table.num_rows:
        assert (np.asarray(idx) == -1).all()
        return [[None] * len(idx) for _ in table.columns]
    return [c.to_pylist() for c in
            ops.take_table(table, np.asarray(idx)).columns]


@pytest.fixture
def low_floor(monkeypatch):
    """`take` and the sort at a few hundred rows: the floor under which
    every count goes by positions, lowered for the test alone."""
    monkeypatch.setattr(gather, "KEPT_FLOOR", 4)


@pytest.mark.parametrize("nullable", [False, True],
                         ids=["not_null", "nullable"])
@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("how", ["left_outer", "full_outer"])
def test_every_path_equals_the_gathers_through_the_maps(how, case, nullable,
                                                        low_floor):
    lk, rk, lnull, rnull = _sides(case)
    lt = _table("l", lk, nullable, lnull)
    rt = _table("r", rk, nullable, rnull)
    lkeys, rkeys = [lt["lk"]], [rt["rk"]]
    if how == "full_outer":
        lm, rm, lonely, matched, unmatched, unmatched_right = \
            ops.full_join_parts(lkeys, rkeys)
        wl, wr = ops.full_join(lkeys, rkeys)
    else:
        lm, rm, matched, unmatched = ops.left_join_counted(lkeys, rkeys)
        lonely, unmatched_right = None, 0
        wl, wr = ops.left_join(lkeys, rkeys)
    cols, made = ops.outer_join_columns(
        lt, rt, how, lm.data, rm.data, lonely, matched, unmatched,
        unmatched_right)
    want = _gathered(lt, wl.data) + _gathered(rt, wr.data)
    assert [c.length for c in cols] == [wl.length] * len(cols)
    assert [c.dtype for c in cols] == [c.dtype for c in lt.columns
                                       + rt.columns]
    assert [c.to_pylist() for c in cols] == want
    # ... by the path the counts name
    left, body, tail = CASES[case]
    assert made["left_out"] == left
    assert made["right_out"] == (f"{body}/{tail}" if how == "full_outer"
                                 else body)
    assert (left, body, tail if how == "full_outer" else "") == \
        outer_join_paths(how, len(lk), len(rk), matched, unmatched,
                         unmatched_right, ragged=True)
    # ... and the frame-long gathers that are left are counted
    slots = matched + unmatched

    def planes_of(table, holds_null):
        return sum(1 + (c.validity is not None or holds_null)
                   for c in table.columns)
    planes = planes_of(lt, False) * (left == "take") + (
        planes_of(rt, unmatched > 0) if body == "take" else
        planes_of(rt.select(["r_string"]), matched < slots)
        if body == "sparse+gather" else 0)
    assert made["planes_gathered"] == planes
    assert made["slots_gathered"] == planes * slots


@pytest.mark.parametrize("how", ["left_outer", "full_outer"])
def test_at_the_floor_as_it_stands_a_small_join_goes_by_positions(how):
    """With the constants as PR 42 set them a join of a few hundred rows
    never takes a frame-long gather for its fixed-width columns."""
    lk, rk, _, _ = _sides("dense")
    lt, rt = (Table([Column.from_pylist([int(k) for k in keys], dtypes.INT64),
                     Column.from_pylist(list(range(len(keys))), dtypes.INT64)],
                    names=[f"{s}k", f"{s}v"])
              for s, keys in (("l", lk), ("r", rk)))
    if how == "full_outer":
        lm, rm, lonely, *counts = ops.full_join_parts([lt["lk"]], [rt["rk"]])
        wl, wr = ops.full_join([lt["lk"]], [rt["rk"]])
    else:
        lm, rm, *counts = ops.left_join_counted([lt["lk"]], [rt["rk"]])
        lonely = None
        wl, wr = ops.left_join([lt["lk"]], [rt["rk"]])
    cols, made = ops.outer_join_columns(lt, rt, how, lm.data, rm.data,
                                        lonely, *counts)
    assert made == {"left_out": "as_is",
                    "right_out": "sparse" + "/positions" * (how ==
                                                            "full_outer"),
                    "planes_gathered": 0, "slots_gathered": 0}
    assert [c.to_pylist() for c in cols] \
        == _gathered(lt, wl.data) + _gathered(rt, wr.data)


def test_as_is_hands_the_left_columns_over_untouched():
    """No program runs for a left side whose map is the identity: the
    output holds the input's own buffers."""
    lt = _table("l", np.arange(40), True)
    rt = _table("r", 100 + np.arange(10), False)
    lm, rm, matched, unmatched = ops.left_join_counted([lt["lk"]],
                                                       [rt["rk"]])
    cols, made = ops.outer_join_columns(lt, rt, "left_outer", lm.data,
                                        rm.data, None, matched, unmatched)
    assert made["left_out"] == "as_is" and made["right_out"] == "nulls"
    for got, src in zip(cols, lt.columns):
        assert got is src


@pytest.mark.parametrize("case", ["sparse", "dense", "null_keys",
                                  "empty_left", "empty_right"])
def test_the_public_maps_are_what_they_were(case):
    """`full_join` stays `full_join_parts`' maps, then a (-1, j) per right
    row j without a match, ascending; `left_join` is the parts' body."""
    lk, rk, lnull, rnull = _sides(case)
    lkeys = [_table("l", lk, False, lnull)["lk"]]
    rkeys = [_table("r", rk, False, rnull)["rk"]]
    lm, rm, lonely, matched, unmatched, unmatched_right = \
        ops.full_join_parts(lkeys, rkeys)
    wl, wr = ops.left_join(lkeys, rkeys)
    np.testing.assert_array_equal(np.asarray(lm.data), np.asarray(wl.data))
    np.testing.assert_array_equal(np.asarray(rm.data), np.asarray(wr.data))
    extra = np.nonzero(np.asarray(lonely))[0]
    assert len(extra) == unmatched_right
    fl, fr = ops.full_join(lkeys, rkeys)
    assert fl.length == fr.length == matched + unmatched + unmatched_right
    np.testing.assert_array_equal(
        np.asarray(fl.data),
        np.r_[np.asarray(wl.data), np.full(len(extra), -1)])
    np.testing.assert_array_equal(np.asarray(fr.data),
                                  np.r_[np.asarray(wr.data), extra])
    assert ops.full_join_counted(lkeys, rkeys)[2:] \
        == (matched, unmatched, unmatched_right)


def test_lists_and_structs_ride_every_path(low_floor):
    """Columns with children: as they stand on the left, null rows, the
    plain gather and the compaction's `sort+gather` on the right."""
    def table(side, keys):
        n = len(keys)
        return Table(
            [Column.from_pylist([int(k) for k in keys], dtypes.INT64),
             Column.make_list(      # row i holds i % 3 elements
                 jnp.asarray(np.cumsum([0] + [i % 3 for i in range(n)]),
                             jnp.int32),
                 Column.from_pylist(list(range(sum(i % 3 for i in
                                                   range(n)))), dtypes.INT64),
                 jnp.asarray([i % 4 != 1 for i in range(n)])),
             Column.make_struct(
                 a=Column.from_pylist(list(range(n)), dtypes.INT64),
                 b=Column.from_pylist([f"x{i}" for i in range(n)],
                                      dtypes.STRING))],
            names=[f"{side}k", f"{side}l", f"{side}s"])
    for lk, rk in [(np.arange(40), np.r_[[3, 9], 100 + np.arange(30)]),
                   (np.arange(20), 50 + np.arange(10))]:
        lt, rt = table("l", lk), table("r", rk)
        lm, rm, lonely, *counts = ops.full_join_parts([lt["lk"]], [rt["rk"]])
        cols, made = ops.outer_join_columns(lt, rt, "full_outer", lm.data,
                                            rm.data, lonely, *counts)
        wl, wr = ops.full_join([lt["lk"]], [rt["rk"]])
        assert [c.to_pylist() for c in cols] \
            == _gathered(lt, wl.data) + _gathered(rt, wr.data)
        assert made["left_out"] == "as_is"
