"""The control of `q1.tasks`' comparison in the cell's own lower
precisions: the plain reference with both products and every sum computed
in float64 (what an engine without decimals does), and with digits
dropped where Spark rounds HALF_UP. Each, put in the program's place, has
to come out as NOT correct; how many of a result's 28 decimal values (4
groups x 7 columns) differ is printed.

    python3 -m chipbench.tests.test_correct_q1 --seeds 5,6,7     # the chip, the cell's size
    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_correct_q1.py

float64 holds every integer up to 2**53: at the rehearsal's 20,000 rows
the sums stay under it and that control is exact, so the test below holds
only truncation to failing; at 6,000,000 rows `sum_charge` passes 2**53
and float64 fails too (PERF.md section 6, PR 28).
"""
import argparse
import os
import sys

import numpy as np

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CELL = "q1.tasks"
CONTROLS = ("float64", "truncate")


def one_seed(cell, seed: int, gen) -> dict:
    """-> per control: the comparison's numbers and how many decimal values
    differ from the sound reference's."""
    import jax
    from chipbench import check, harness
    plan_mod = cell.plan
    tables = jax.device_get(
        gen(*harness.batch_keys(cell, seed, harness.TABLE_STREAM)))
    ref = plan_mod.reference(tables)
    out = {"rows": len(ref)}
    for control in CONTROLS:
        other = plan_mod.reference(tables, control=control)
        got = {c: other[c].values for c in plan_mod.RESULT_COLUMNS}
        numbers = check.compare(got, ref, plan_mod.RESULT_COLUMNS,
                                plan_mod.ORDERED)
        differ = sum(
            int((np.asarray(got[c]) != ref[c].values).any(axis=1).sum())
            for c in plan_mod.RESULT_COLUMNS if ref[c].values.ndim == 2)
        out[control] = {"numbers": numbers, "values_differ": differ,
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
    must_fail = ("truncate",) if tiny else CONTROLS
    held = True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = one_seed(cell, seed, gen)
        held = held and all(out[c]["fails"] for c in must_fail)
        print(f"control {CELL} seed {seed} at "
              f"{cell.batch['lineitem_rows']} rows, {out['rows']} groups: "
              + "; ".join(
                  f"{c}: {out[c]['numbers']}, {out[c]['values_differ']} of "
                  f"{7 * out['rows']} decimal values differ -> "
                  f"{'fails' if out[c]['fails'] else 'PASSES'} the comparison"
                  for c in CONTROLS), flush=True)
    return 0 if held else 1


def test_truncation_is_not_correct_at_the_rehearsal_size(monkeypatch):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from chipbench import harness, rehearse
    monkeypatch.setattr(harness, "require_devices", rehearse.cpu_devices)
    assert main(["--seeds", "3,2147483659,77"], platform="cpu",
                tiny=True) == 0


if __name__ == "__main__":
    sys.exit(main())
