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
    # a join that read its matching left rows' keys distinct: the body
    # that would be `take` rides one sort, and nothing else differs; a
    # column without a plane cannot ride, and the word is the parent's
    sorted_body = "sort" if want[1] == "take" else want[1]
    assert outer_join_paths("full_outer", *counts, distinct=True) \
        == (want[0], sorted_body, want[2])
    assert outer_join_paths("left_outer", *counts[:4], 0, distinct=True) \
        == (want[0], sorted_body, "")
    assert outer_join_paths("full_outer", *counts, ragged=True,
                            distinct=True) == ragged


@pytest.mark.parametrize("counts, distinct, want", [
    # q13.batch: 1.5 M distinct customers, their 14.83 M orders
    ((1_500_000, 14_834_663, 14_834_663, 500_002, 0), True,
     ("take", "sort", "")),
    ((1_500_000, 14_834_663, 14_834_663, 500_002, 0), False,
     ("take", "take", "")),
    # q97.batch: `sparse` before the keys are asked about
    ((6_597_944, 3_350_369, 440, 6_597_504, 3_349_929), True,
     ("as_is", "sparse", "sort")),
    # q3.share's shape as an outer join (2.25 M sales against 6,000 dates):
    # a sale's date repeats, the join reads so, and the word is the parent's
    ((2_250_000, 6_000, 330_000, 1_920_000, 0), False,
     ("as_is", "take", "")),
    ((2_250_000, 6_000, 330_000, 1_920_000, 0), True,
     ("as_is", "sort", "")),
    # one to one, every row matched: no null slot, still a permutation
    ((FLOOR + 1, FLOOR + 1, FLOOR + 1, 0, 0), True, ("as_is", "sort", "")),
    ((FLOOR, FLOOR, FLOOR, 0, 0), True, ("as_is", "sparse", "")),
    ((100, 50, 0, 100, 0), True, ("as_is", "nulls", "")),
], ids=lambda v: "-".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_the_sort_body_follows_the_counts_and_the_distinct_keys(
        counts, distinct, want):
    how = "full_outer" if want[2] else "left_outer"
    assert outer_join_paths(how, *counts, distinct=distinct) == want


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


def _joined(how: str, lt: Table, rt: Table, null_equal: bool = False):
    """The eager outer join as the executor runs it -> (parts, columns,
    what was done)."""
    parts = ops.outer_join_parts(how, [lt["lk"]], [rt["rk"]], rt, null_equal)
    return (parts, *ops.outer_join_columns(lt, rt, parts))


def _reference_maps(how: str, lk, rk, lnull=(), rnull=(),
                    null_equal: bool = False):
    """The join's gather maps by two plain loops, no code of the package:
    the left rows in order, under each its matching right rows ascending
    or one -1; then, for a full join, the right rows nothing matched,
    ascending, under a -1."""
    lkey = [None if i in lnull else int(k) for i, k in enumerate(lk)]
    rkey = [None if j in rnull else int(k) for j, k in enumerate(rk)]
    lmap, rmap, met = [], [], set()
    for i, k in enumerate(lkey):
        rows = [j for j, q in enumerate(rkey)
                if k == q and (k is not None or null_equal)]
        met.update(rows)
        lmap += [i] * max(len(rows), 1)
        rmap += rows or [-1]
    if how == "full_outer":
        lonely = [j for j in range(len(rkey)) if j not in met]
        lmap += [-1] * len(lonely)
        rmap += lonely
    return np.asarray(lmap, np.int64), np.asarray(rmap, np.int64)


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
    parts, cols, made = _joined(how, lt, rt)
    matched, unmatched, unmatched_right = parts[4:]
    wl, wr = (ops.full_join if how == "full_outer" else ops.left_join)(
        lkeys, rkeys)
    for got, ref in zip((wl, wr), _reference_maps(how, lk, rk, lnull, rnull)):
        np.testing.assert_array_equal(np.asarray(got.data), ref)
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
    assert (left, body, tail if how == "full_outer" else "") == parts.paths \
        == outer_join_paths(how, len(lk), len(rk), matched, unmatched,
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
    _, cols, made = _joined(how, lt, rt)
    wl, wr = (ops.full_join if how == "full_outer" else ops.left_join)(
        [lt["lk"]], [rt["rk"]])
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
    _, cols, made = _joined("left_outer", lt, rt)
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
    # (both run one kernel since PR 45: held to the plain loops as well)
    for got, ref in zip((wl, wr), _reference_maps("left_outer", lk, rk,
                                                  lnull, rnull)):
        np.testing.assert_array_equal(np.asarray(got.data), ref)
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
        _, cols, made = _joined("full_outer", lt, rt)
        wl, wr = ops.full_join([lt["lk"]], [rt["rk"]])
        assert [c.to_pylist() for c in cols] \
            == _gathered(lt, wl.data) + _gathered(rt, wr.data)
        assert made["left_out"] == "as_is"


# ---- a right side that is a permutation rides one sort (PR 45) ------------

PLANES = {k: v for k, v in TYPES.items() if k != "string"}


def _plain_table(side: str, keys, nullable: bool, key_nulls=()) -> Table:
    """`_table` without its string column: every column has planes."""
    full = _table(side, keys, nullable, key_nulls)
    return full.select([n for n in full.names if not n.endswith("_string")])


def _slot_sides(case: str):
    """-> (left keys, right keys, null left rows, null right rows); the
    left rows that match hold distinct keys unless the case says not."""
    rng = np.random.default_rng(len(case))
    if case == "one_to_one":            # no null slot at all
        return np.arange(70), rng.permutation(70), (), ()
    if case == "dense":                 # half the left rows match once
        return np.arange(120), rng.permutation(240)[:120], (), ()
    if case == "fan_out":               # a left row's matches fan out
        return rng.permutation(50), rng.integers(0, 60, 300), (), ()
    if case == "alone_first_last_runs":  # unmatched left rows: 0-4, 20-29,
        lk = np.arange(60)               # 45, 55-59; right rows alone too
        alone = np.r_[0:5, 20:30, 45, 55:60]
        rk = np.r_[rng.choice(np.setdiff1d(lk, alone), 150), 900, 901, 902]
        return lk, rng.permutation(rk), (), ()
    if case == "null_keys":             # on either side
        return np.arange(90), rng.integers(0, 120, 200), \
            (0, 3, 4, 40, 89), (0, 7, 100, 199)
    if case == "one_null_left_key":     # <=> pairs it with the right's
        return np.arange(40), rng.integers(0, 50, 100), (5,), (1, 2, 50)
    if case == "repeated_alone":        # a repeated left key, not matched
        return np.r_[np.arange(50), 500, 500, 500], \
            rng.integers(0, 50, 160), (), ()
    if case == "repeated_matched":      # one repeated matching left key
        return np.r_[np.arange(50), 7], rng.integers(0, 50, 160), (), ()
    raise KeyError(case)


SLOT_CASES = {  # case -> the body under FLOOR 4, null_equal False / True
    "one_to_one": ("sort", "sort"),
    "dense": ("sort", "sort"),
    "fan_out": ("sort", "sort"),
    "alone_first_last_runs": ("sort", "sort"),
    "null_keys": ("sort", "take"),      # five null left keys match under <=>
    "one_null_left_key": ("sort", "sort"),
    "repeated_alone": ("sort", "sort"),
    "repeated_matched": ("take", "take"),
}


@pytest.mark.parametrize("null_equal", [False, True], ids=["eq", "null_eq"])
@pytest.mark.parametrize("nullable", [False, True],
                         ids=["not_null", "nullable"])
@pytest.mark.parametrize("case", list(SLOT_CASES))
@pytest.mark.parametrize("how", ["left_outer", "full_outer"])
def test_the_sort_body_equals_the_gathers_through_the_maps(
        how, case, nullable, null_equal, low_floor):
    """A join that hands back its right rows' slots builds, row for row
    and null for null, what `take_table` gives through the join's maps as
    two plain loops make them; one repeated matching left key and it runs
    `take` as before, through maps that are those loops' too."""
    lk, rk, lnull, rnull = _slot_sides(case)
    lt = _plain_table("l", lk, nullable, lnull)
    rt = _plain_table("r", rk, nullable, rnull)
    parts, cols, made = _joined(how, lt, rt, null_equal)
    matched, unmatched, unmatched_right = parts[4:]
    wl, wr = _reference_maps(how, lk, rk, lnull, rnull, null_equal)
    slots = matched + unmatched
    assert (matched, unmatched, unmatched_right) == (
        int((wr[:slots] >= 0).sum()), int((wr[:slots] < 0).sum()),
        len(wl) - slots if how == "full_outer" else 0)
    body = SLOT_CASES[case][null_equal]
    assert parts.paths[1] == body
    np.testing.assert_array_equal(np.asarray(parts.left_map), wl[:slots])
    if body == "sort":      # the right map's inverse, and no map
        slot, alone = (np.asarray(a) for a in parts.right_map)
        want = np.full(len(rk), slots)
        want[wr[:slots][wr[:slots] >= 0]] = np.nonzero(wr[:slots] >= 0)[0]
        np.testing.assert_array_equal(slot, want)
        want = np.full(len(lk), slots)
        want[wl[:slots][wr[:slots] < 0]] = np.nonzero(wr[:slots] < 0)[0]
        np.testing.assert_array_equal(alone, want)
    else:
        np.testing.assert_array_equal(np.asarray(parts.right_map),
                                      wr[:slots])
    assert [c.length for c in cols] == [len(wl)] * len(cols)
    assert [c.dtype for c in cols] == [c.dtype for c in lt.columns
                                       + rt.columns]
    assert [c.to_pylist() for c in cols] \
        == _gathered(lt, wl) + _gathered(rt, wr)
    assert made["right_out"].split("/")[0] == body
    # a column that holds no null, joined without a null slot, has no mask
    if body == "sort" and not nullable and not unmatched \
            and not unmatched_right:
        assert all(c.validity is None for c in cols[len(lt.columns):])
    # only a frame-long `take` counts: the left side's where a row fans
    # out, the right side's where the body is `take`
    left = "as_is" if slots == len(lk) else "take"
    planes = sum(1 + (c.validity is not None) for c in lt.columns) \
        * (left == "take") + sum(
            1 + (c.validity is not None or unmatched > 0)
            for c in rt.columns) * (body == "take")
    assert (made["left_out"], made["planes_gathered"],
            made["slots_gathered"]) == (left, planes, planes * slots)


@pytest.mark.parametrize("kind", ["string", "list", "struct"])
def test_a_column_without_planes_keeps_the_right_side_on_take(kind,
                                                              low_floor):
    """A string, list or struct column cannot ride a sort: the join,
    handed the right table, says `take`, builds the map, and every plane
    of the right side is counted as gathered, as before; without that
    column the same keys ride the sort."""
    lk, rk, _, _ = _slot_sides("dense")
    n = len(rk)
    extra = {
        "string": lambda: Column.from_pylist([f"s{i}" for i in range(n)],
                                             dtypes.STRING),
        "list": lambda: Column.make_list(
            jnp.arange(n + 1, dtype=jnp.int32),
            Column.from_pylist(list(range(n)), dtypes.INT64)),
        "struct": lambda: Column.make_struct(
            a=Column.from_pylist(list(range(n)), dtypes.INT64)),
    }[kind]()
    lt = _plain_table("l", lk, False)
    plain = _plain_table("r", rk, False)
    rt = Table([*plain.columns, extra], names=[*plain.names, "r_extra"])
    parts, cols, made = _joined("left_outer", lt, rt)
    wl, wr = _reference_maps("left_outer", lk, rk)
    assert parts.paths == ("as_is", "take", "")
    np.testing.assert_array_equal(np.asarray(parts.right_map), wr)
    assert [c.to_pylist() for c in cols] \
        == _gathered(lt, wl) + _gathered(rt, wr)
    assert (made["left_out"], made["right_out"]) == ("as_is", "take")
    assert made["planes_gathered"] == 2 * len(rt.columns)   # unmatched > 0
    assert _joined("left_outer", lt, plain)[0].paths == ("as_is", "sort", "")


@pytest.mark.parametrize("how", ["left_outer", "full_outer"])
@pytest.mark.parametrize("case", ["dense", "fan_out", "null_keys"])
def test_a_caller_that_wants_the_maps_gets_the_maps(how, case, low_floor):
    """`left_join_counted` / `full_join_parts` hand no table in: whatever
    the counts say, the right map is a map, the plain loops' own."""
    lk, rk, lnull, rnull = _slot_sides(case)
    lkeys = [_plain_table("l", lk, False, lnull)["lk"]]
    rkeys = [_plain_table("r", rk, False, rnull)["rk"]]
    wl, wr = _reference_maps("left_outer", lk, rk, lnull, rnull)
    lm, rm, *rest = (ops.full_join_parts if how == "full_outer"
                     else ops.left_join_counted)(lkeys, rkeys)
    assert isinstance(lm, Column) and isinstance(rm, Column)
    np.testing.assert_array_equal(np.asarray(lm.data), wl)
    np.testing.assert_array_equal(np.asarray(rm.data), wr)
    assert tuple(rest[-3:] if how == "full_outer" else rest)[:2] \
        == (int((wr >= 0).sum()), int((wr < 0).sum()))
