"""The controls of `q97.batch`'s comparison, its byte functions against
hand counts, the generator's fixed counts, and the reference against a
second computation.

    python3 -m chipbench.tests.test_correct_q97 --seeds 5,6,7     # the chip, the cell's size
    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_correct_q97.py

Five controls, each the plain reference with one thing wrong, put in the
program's place; each has to come out as NOT correct. `inner` and
`left_outer` join that way: the null-extended rows of one side or of both
are gone, and `catalog_only` (under `inner` `store_only` too) is 0.
`null_equal` joins null keys as equal (what pandas' `merge` does, and a
join without SQL's `=`): a (customer, NULL item) pair of both channels
counts as both where it is store_only once and catalog_only once.
`null_as_value` evaluates the `CASE WHEN`s over the data under a null, as
an engine whose expressions read the data buffer alone does: no key is
null, every output row counts as both. `no_distinct` joins rows, not
pairs. `chipbench.control` adds bfloat16 join keys.
"""
import argparse
import os
import sys

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CELL = "q97.batch"


def tables_of(cell, seed: int, gen) -> dict:
    import jax
    from chipbench import harness
    tables = {n: (c, {}) for n, c in
              cell.plan.dimensions(cell.sizes).items()}
    tables.update(jax.device_get(
        gen(*harness.batch_keys(cell, seed, harness.TABLE_STREAM))))
    return tables


def one_seed(cell, seed: int, gen) -> dict:
    """-> per control: the comparison's numbers and whether it fails."""
    from chipbench import check
    plan_mod = cell.plan
    tables = tables_of(cell, seed, gen)
    ref = plan_mod.reference(tables)
    out = {"answer": [int(ref[c].values[0]) for c in plan_mod.RESULT_COLUMNS],
           "counts": dict(plan_mod.COUNTS)}
    for control in plan_mod.CONTROLS:
        other = plan_mod.reference(tables, control=control)
        got = {c: other[c].values for c in plan_mod.RESULT_COLUMNS}
        numbers = check.compare(got, ref, plan_mod.RESULT_COLUMNS,
                                plan_mod.ORDERED)
        out[control] = {"numbers": numbers,
                        "answer": [int(v[0]) for v in got.values()],
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
        held = held and all(out[c]["fails"] for c in cell.plan.CONTROLS)
        print(f"control {CELL} seed {seed} at {cell.batch['store_rows']} + "
              f"{cell.batch['catalog_rows']} rows, the answer "
              f"{out['answer']}, the reference counted {out['counts']}: "
              + "; ".join(
                  f"{c}: {out[c]['answer']} -> "
                  f"{'fails' if out[c]['fails'] else 'PASSES'} the comparison"
                  for c in cell.plan.CONTROLS), flush=True)
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
    from chipbench import tpcds
    from chipbench.plans import q97
    batch = {"store_rows": 3000, "catalog_rows": 1500, "store_pairs": 600,
             "catalog_pairs": 300, "matched_pairs": 7}
    # 27 B a fact row (three int64 and their validity bytes), 16 a day,
    # three int64 sums
    assert q97.fact_rows(batch) == 4500
    assert q97.least_bytes(batch, {}, 1) \
        == 4500 * 27 + tpcds.N_DATES * 16 + 24
    # both sides' two key columns read, every output row two int64 and two
    # validity bytes
    counts = {"left_rows": 600, "right_rows": 300, "matched": 7,
              "unmatched": 593, "unmatched_right": 293}
    want = (600 + 300) * 16 + (7 + 593 + 293) * 18
    assert q97.full_join_bytes(batch, {}, counts) == want
    q97.COUNTS.clear()
    assert q97.full_join_bytes(batch, {}) == want


def test_the_generator_holds_its_fixed_counts_whatever_the_seed(rehearsal):
    """The rows that pass the date joins, the distinct pairs, the
    null-customer groups and the matched pairs are the configuration's;
    every array is the seed's; the keys follow the rank's rules."""
    cell, gen, drawn = rehearsal
    q97, batch, sizes = cell.plan, cell.batch, cell.sizes
    arrays = {}
    for seed, tables in drawn.items():
        q97.reference(tables)
        got = q97.COUNTS
        assert {k: got[k] for k in (
            "store_date_rows", "catalog_date_rows",
            "store_null_customer_groups", "catalog_null_customer_groups")} \
            == {k: batch[k] for k in (
                "store_date_rows", "catalog_date_rows",
                "store_null_customer_groups",
                "catalog_null_customer_groups")}
        assert (got["left_rows"], got["right_rows"], got["matched"]) == (
            batch["store_pairs"], batch["catalog_pairs"],
            batch["matched_pairs"])
        # nearly every output row is null-extended on one side
        assert got["unmatched"] == batch["store_pairs"] - got["matched"]
        assert got["matched"] * 50 < got["left_rows"]
        for name, rows in (("store_sales", batch["store_rows"]),
                           ("catalog_sales", batch["catalog_rows"])):
            cols, validity = tables[name]
            date, cust, item = (np.asarray(cols[c])
                                for c in q97.COLUMNS[name])
            assert date.shape == cust.shape == item.shape == (rows,)
            assert (cust % sizes["ranks"] == sizes["rank"] + 1).all()
            assert item.min() >= 1 and item.max() <= sizes["items"]
            for c in q97.COLUMNS[name]:     # 4.5% of every key is null
                assert 0.04 < 1 - np.asarray(validity[c]).mean() < 0.05
        arrays[seed] = tables
    a, b = list(arrays.values())[:2]
    for name in ("store_sales", "catalog_sales"):
        assert all((np.asarray(a[name][0][c]) != np.asarray(b[name][0][c]))
                   .any() for c in q97.COLUMNS[name])
    # a configuration that states another count is refused
    from chipbench import harness
    wrong = q97.batch_generator(
        sizes, dict(batch, matched_pairs=batch["matched_pairs"] + 1))
    with pytest.raises(ValueError, match="the configuration states"):
        wrong(*harness.batch_keys(cell, 3, harness.TABLE_STREAM))


def test_the_reference_against_a_second_computation(rehearsal):
    """Python sets of (customer, item) tuples with None for a null: a
    pair matches iff it is in both sets and holds no None."""
    cell, _, drawn = rehearsal
    q97 = cell.plan
    for tables in drawn.values():
        ref = q97.reference(tables)
        dd = tables["date_dim"][0]
        days = set(np.asarray(dd["d_date_sk"])[
            (np.asarray(dd["d_month_seq"]) >= 1200)
            & (np.asarray(dd["d_month_seq"]) <= 1211)].tolist())
        assert len(days) == 366                 # 2000 is a leap year
        pairs = []
        for name in ("store_sales", "catalog_sales"):
            cols, validity = tables[name]
            d, c, i = ([v if ok else None for v, ok in zip(
                np.asarray(cols[n]).tolist(),
                np.asarray(validity[n]).tolist())]
                for n in q97.COLUMNS[name])
            pairs.append({(cc, ii) for dd_, cc, ii in zip(d, c, i)
                          if dd_ in days})
        store, catalog = pairs
        both = {p for p in store & catalog if None not in p}
        want = (sum(p[0] is not None for p in store - both),
                sum(p[0] is not None for p in catalog - both),
                len(both))
        assert tuple(int(ref[c].values[0])
                     for c in q97.RESULT_COLUMNS) == want
        assert want[2] > 0 and want[0] > want[1] > want[2]


if __name__ == "__main__":
    sys.exit(main())
