"""The controls of `q18.batch`'s comparison, its byte functions against
hand counts, and the generator's shape.

    python3 -m chipbench.tests.test_correct_q18 --seeds 5,6,7     # the chip, the cell's size
    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_correct_q18.py

Three controls, each the plain reference with one thing wrong, put in the
program's place; each has to come out as NOT correct. `float64` holds
money as a float64 of units, sums and compares in float64, and casts back
to cents by truncation: quantities are whole and every value lies under
2**53, so a float64 engine that ROUNDS its casts is exact here, and what
fails is the truncating one, on the 5.1% of prices that come back a cent
short (one row or more of the cell's 100 in 99.4% of draws, of the
rehearsal's 21 in two draws of three: the seeds below are such draws).
`having_ge` keeps the orders that sum to 300 exactly (3 of the
rehearsal's 400,000 orders). `ascending` sorts by price ascending.
"""
import argparse
import os
import sys

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CELL = "q18.batch"
CONTROLS = ("float64", "having_ge", "ascending")


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
              f"{cell.batch['lineitem_rows']} rows, {out['rows']} result "
              f"rows, aggregates {out['counts']}: " + "; ".join(
                  f"{c}: {out[c]['numbers']} -> "
                  f"{'fails' if out[c]['fails'] else 'PASSES'} the comparison"
                  for c in CONTROLS), flush=True)
    return 0 if held else 1


@pytest.fixture
def on_the_cpu(monkeypatch):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from chipbench import harness, rehearse
    monkeypatch.setattr(harness, "require_devices", rehearse.cpu_devices)


def test_rehearsal_cell_runs_end_to_end_and_is_correct(on_the_cpu,
                                                       monkeypatch):
    from chipbench import rehearse
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert rehearse.main(["--workload", CELL, "--seconds", "1"]) == 0


def test_every_control_is_not_correct_at_the_rehearsal_size(on_the_cpu):
    assert main(["--seeds", "3,77,3400000001"], platform="cpu",
                tiny=True) == 0


def test_byte_functions_against_hand_counts():
    from chipbench.plans import tpch_q18 as q18
    batch = {"orders_rows": 1000, "lineitem_rows": 4000}
    sizes = {"customer_rows": 300}
    # 16 B a lineitem row, 32 an orders row, 16 a customer row; a result
    # row is five int64 and one 16-byte decimal
    assert q18.least_bytes(batch, sizes, 100) \
        == 4000 * 16 + 1000 * 32 + 300 * 16 + 100 * 56
    # the subquery: key and value read (16 B a row), key and DECIMAL128 sum
    # written (24 B a group); the outer: five keys and the value read (48),
    # five keys and the sum written (56)
    counts = {"subquery": (4000, 1000), "outer": (70, 10)}
    assert q18.groupby_bytes(batch, sizes, counts) \
        == 4000 * 16 + 1000 * 24 + 70 * 48 + 10 * 56
    q18.COUNTS.clear()
    assert q18.groupby_bytes(batch, sizes) == 4000 * 16 + 1000 * 24
    # the cell: 960 MB read and 360 MB written by the subquery alone
    cell = {"orders_rows": 15000000, "lineitem_rows": 59998501}
    assert q18.groupby_bytes(cell, sizes) == 59998501 * 16 + 15000000 * 24


def test_generator_at_a_million_orders(on_the_cpu):
    """The row count the draw states, the survivors' share of the orders
    (seven lines of 1..50 that sum past 300: 3.8e-5), and no tie on
    (o_totalprice, o_orderdate) among the orders the query ranks."""
    import jax
    import spark_rapids_tpu  # noqa: F401
    from chipbench import tpcds
    from chipbench.plans import tpch_q18 as q18
    sizes = {"customer_rows": 100000, "dsdgen_seed": 19980802}
    batch = {"orders_rows": 1000000, "lineitem_rows": 3996614}
    gen = q18.batch_generator(sizes, batch)
    drawn = jax.device_get(gen(tpcds.run_key(19980802, 0),
                               tpcds.run_key(2 ** 31 + 18, 1)))
    li, orders = drawn["lineitem"][0], drawn["orders"][0]
    assert len(li["l_orderkey"]) == batch["lineitem_rows"]
    keys, sums = q18._order_sums(np.asarray(li["l_orderkey"]),
                                 np.asarray(li["l_quantity"]))
    assert len(keys) == batch["orders_rows"]
    large = keys[sums > q18.QUANTITY * 100]
    assert 3e-5 < len(large) / len(keys) < 5e-5
    at = np.isin(np.asarray(orders["o_orderkey"]), large)
    ranked = list(zip(np.asarray(orders["o_totalprice"])[at].tolist(),
                      np.asarray(orders["o_orderdate"])[at].tolist()))
    assert len(ranked) == len(large) and len(set(ranked)) == len(ranked)
    with pytest.raises(ValueError, match="the configuration states"):
        q18.batch_generator(sizes, dict(batch, lineitem_rows=4000000))(
            tpcds.run_key(1, 0), tpcds.run_key(1, 1))


if __name__ == "__main__":
    sys.exit(main())
