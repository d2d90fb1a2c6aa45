"""The controls of `q13.batch`'s comparison, its byte functions against
hand counts, the generator's fixed counts, and the reference against a
second computation.

    python3 -m chipbench.tests.test_correct_q13 --seeds 5,6,7     # the chip, the cell's size
    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_correct_q13.py

Three controls, each the plain reference with one thing wrong, put in the
program's place; each has to come out as NOT correct. `inner` joins inner:
the customers without a surviving order are gone, and the row `c_count = 0`
(the result's largest) with them. `count_star` counts rows where the query
counts non-null `o_orderkey`: the null-extended rows count, and those
customers land in `c_count = 1`. `filter_above` applies the comment's
predicate to the join's output, where a null-extended row's `o_special` is
null and fails it: what pushing the predicate the wrong way through an
outer join gives. `chipbench.control` adds bfloat16 join keys.
"""
import argparse
import os
import sys

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CELL = "q13.batch"
CONTROLS = ("inner", "count_star", "filter_above")


def tables_of(cell, seed: int, gen) -> dict:
    import jax
    from chipbench import harness
    return dict(jax.device_get(
        gen(*harness.batch_keys(cell, seed, harness.TABLE_STREAM))))


def one_seed(cell, seed: int, gen) -> dict:
    """-> per control: the comparison's numbers and whether it fails."""
    from chipbench import check
    plan_mod = cell.plan
    tables = tables_of(cell, seed, gen)
    ref = plan_mod.reference(tables)
    out = {"rows": len(ref), "counts": dict(plan_mod.COUNTS)}
    for control in CONTROLS:
        other = plan_mod.reference(tables, control=control)
        got = {c: other[c].values for c in plan_mod.RESULT_COLUMNS}
        numbers = check.compare(got, ref, plan_mod.RESULT_COLUMNS,
                                plan_mod.ORDERED)
        out[control] = {"numbers": numbers,
                        "fails": any(numbers[k] > lim for k, lim
                                     in check.LIMITS.items())}
    return out


def main(argv=None, platform: str = "tpu", tiny: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="5,6,7")
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = harness.Cell(CELL, tiny=tiny)
    harness.require_devices(cell, platform)
    import spark_rapids_tpu  # noqa: F401  (64-bit integers on)
    gen = cell.plan.batch_generator(cell.sizes, cell.batch)
    held = True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = one_seed(cell, seed, gen)
        held = held and all(out[c]["fails"] for c in CONTROLS)
        print(f"control {CELL} seed {seed} at "
              f"{cell.batch['orders_rows']} orders, {out['rows']} result "
              f"rows, the join {out['counts']}: " + "; ".join(
                  f"{c}: {out[c]['numbers']} -> "
                  f"{'fails' if out[c]['fails'] else 'PASSES'} the comparison"
                  for c in CONTROLS), flush=True)
    return 0 if held else 1


@pytest.fixture
def on_the_cpu(monkeypatch):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from chipbench import harness, rehearse
    monkeypatch.setattr(harness, "require_devices", rehearse.cpu_devices)


@pytest.fixture(scope="module")
def rehearsal():
    """-> (the tiny cell, its generator, {seed: tables})."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import spark_rapids_tpu  # noqa: F401
    from chipbench import harness
    cell = harness.Cell(CELL, tiny=True)
    gen = cell.plan.batch_generator(cell.sizes, cell.batch)
    return cell, gen, {seed: tables_of(cell, seed, gen)
                       for seed in (3, 77, 4100000001)}


def test_rehearsal_cell_runs_end_to_end_and_is_correct(on_the_cpu,
                                                       monkeypatch):
    from chipbench import rehearse
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert rehearse.main(["--workload", CELL, "--seconds", "1"]) == 0


def test_every_control_is_not_correct_at_the_rehearsal_size(on_the_cpu):
    assert main(["--seeds", "3,77,4100000001"], platform="cpu",
                tiny=True) == 0


def test_the_bfloat16_control_fails_and_the_reference_passes(on_the_cpu):
    from chipbench import control
    assert control.main(["--workload", CELL, "--seeds", "3,77"],
                        platform="cpu", tiny=True) == 0


def test_byte_functions_against_hand_counts():
    from chipbench.plans import tpch_q13 as q13
    batch = {"customer_rows": 300, "orders_rows": 3000,
             "matched_pairs": 2970, "unmatched_customers": 101}
    # 8 B a customer row, 24 an orders row; a result row is two int64
    assert q13.least_bytes(batch, {}, 30) == 300 * 8 + 3000 * 24 + 30 * 16
    # both sides' keys read (8 B a row), every output row three int64
    # columns and two validity bytes
    counts = {"left_rows": 300, "right_rows": 2970, "matched": 2970,
              "unmatched": 101}
    want = (300 + 2970) * 8 + (2970 + 101) * 26
    assert q13.outer_join_bytes(batch, {}, counts) == want
    q13.COUNTS.clear()
    assert q13.outer_join_bytes(batch, {}) == want
    # the cell: 130.7 MB read, 398.7 MB written
    cell = {"customer_rows": 1500000, "orders_rows": 15000000,
            "matched_pairs": 14834663, "unmatched_customers": 500002}
    assert q13.outer_join_bytes(cell, {}) \
        == (1500000 + 14834663) * 8 + (14834663 + 500002) * 26


def test_the_generator_holds_its_fixed_counts_whatever_the_seed(rehearsal):
    """Matched pairs, null-extended customers and the groups of both
    aggregates are the configuration's; every array is the seed's; the
    keys follow dbgen's rules."""
    cell, gen, drawn = rehearsal
    q13, batch = cell.plan, cell.batch
    arrays = {}
    for seed, tables in drawn.items():
        ref = q13.reference(tables)
        assert (q13.COUNTS["matched"], q13.COUNTS["unmatched"]) \
            == (batch["matched_pairs"], batch["unmatched_customers"])
        assert q13.COUNTS["left_rows"] == batch["customer_rows"]
        assert q13.COUNTS["right_rows"] == batch["matched_pairs"]
        assert q13.COUNTS["groups"] \
            == (batch["customer_rows"], batch["count_groups"]) \
            and len(ref) == batch["count_groups"]
        ckey = np.asarray(tables["customer"][0]["c_custkey"])
        orders = {c: np.asarray(a) for c, a in tables["orders"][0].items()}
        assert sorted(ckey.tolist()) \
            == list(range(1, batch["customer_rows"] + 1))
        assert (orders["o_custkey"] % 3 != 0).all()
        assert orders["o_custkey"].min() >= 1 \
            and orders["o_custkey"].max() <= batch["customer_rows"]
        okey = orders["o_orderkey"]
        assert len(set(okey.tolist())) == batch["orders_rows"]
        assert ((okey - 1) % 32 < 8).all()      # 8 keys used of every 32
        assert set(np.unique(orders["o_special"]).tolist()) == {0, 1}
        assert 0.008 < orders["o_special"].mean() < 0.014
        arrays[seed] = (ckey, orders)
    (a_c, a_o), (b_c, b_o) = list(arrays.values())[:2]
    assert (a_c != b_c).any()
    assert all((a_o[c] != b_o[c]).any() for c in a_o)
    # a configuration that states another count is refused
    from chipbench import harness
    wrong = cell.plan.batch_generator(
        cell.sizes, dict(batch, matched_pairs=batch["matched_pairs"] + 1))
    with pytest.raises(ValueError, match="the configuration states"):
        wrong(*harness.batch_keys(cell, 3, harness.TABLE_STREAM))


def test_the_reference_against_a_second_computation(rehearsal):
    """pandas `merge(how="left")`, `count` of the non-null order keys a
    customer, `size` a count, the query's order."""
    import pandas as pd
    cell, _, drawn = rehearsal
    q13 = cell.plan
    for tables in drawn.values():
        ref = q13.reference(tables)
        cust = pd.DataFrame({c: np.asarray(a) for c, a in
                             tables["customer"][0].items()})
        orders = pd.DataFrame({c: np.asarray(a) for c, a in
                               tables["orders"][0].items()})
        joined = cust.merge(orders[orders["o_special"] == 0],
                            left_on="c_custkey", right_on="o_custkey",
                            how="left")
        c_count = joined.groupby("c_custkey")["o_orderkey"].count()
        dist = c_count.value_counts().rename_axis("c_count") \
            .reset_index(name="custdist") \
            .sort_values(["custdist", "c_count"], ascending=[False, False])
        assert ref["c_count"].values.tolist() == dist["c_count"].tolist()
        assert ref["custdist"].values.tolist() == dist["custdist"].tolist()
        assert ref["c_count"].values[0] == 0       # a third have no order
        assert int(ref["custdist"].values.sum()) == len(cust)


def test_the_comparisons_limits_are_zero(rehearsal):
    """Both columns are exact counts: one customer moved from a count to
    its neighbour fails, and so does a swap of two rows."""
    from chipbench import check
    cell, _, drawn = rehearsal
    q13 = cell.plan
    ref = q13.reference(next(iter(drawn.values())))
    assert check.LIMITS == {"ordered_mismatch": 0, "rows_unmatched": 0}
    same = {c: ref[c].values.copy() for c in q13.RESULT_COLUMNS}
    assert check.compare(same, ref, q13.RESULT_COLUMNS, q13.ORDERED) \
        == {"ordered_mismatch": 0, "rows_unmatched": 0}
    moved = {c: v.copy() for c, v in same.items()}
    moved["custdist"][0] -= 1
    moved["custdist"][1] += 1
    off = check.compare(moved, ref, q13.RESULT_COLUMNS, q13.ORDERED)
    assert off["ordered_mismatch"] == 2 and off["rows_unmatched"] == 4
    swapped = {c: v.copy() for c, v in same.items()}
    for v in swapped.values():
        v[[2, 3]] = v[[3, 2]]
    assert check.compare(swapped, ref, q13.RESULT_COLUMNS,
                         q13.ORDERED)["ordered_mismatch"] == 2


if __name__ == "__main__":
    sys.exit(main())
