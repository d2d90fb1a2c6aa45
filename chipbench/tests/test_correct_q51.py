"""The controls of `q51.batch`'s comparison, at the cell's own size on the
chip and at the rehearsal size here.

    python3 -m chipbench.tests.test_correct_q51 --seeds 5,6,7     # the chip, the cell's size
    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_correct_q51.py

Six controls, each the plain reference with one thing wrong, put in the
program's place; each has to come out as NOT correct. `no_partition` runs
each channel's running sum over the whole table (a window kernel that
loses its partition flags). `null_as_zero` compares a NULL side as 0: a
row on which the store has not sold the item yet stays, where `NULL >
x` is not TRUE. `no_carry` takes the row's own total for the running
maximum: `web_cumulative` is NULL on every day the web channel did not
sell, and the carried rows, the query's point, are gone. `restart_at_null`
resets every carry at a NULL (a scan that treats a NULL as a boundary).
`inner` joins the channels as an inner join: the days only one channel
sold are gone. `bfloat16` (chipbench.control's) rounds the money before
it is summed. The same run prints what the reference counted, which the
configuration states (`filter_rows` among them: the one count the
generator cannot take from its sort of the group keys).
"""
import argparse
import os
import sys

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CELL = "q51.batch"
STATED = ("store_date_rows", "web_date_rows", "store_groups", "web_groups",
          "join_rows", "filter_rows")


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
    from chipbench import check, control
    plan_mod = cell.plan
    tables = tables_of(cell, seed, gen)
    ref = plan_mod.reference(tables)
    out = {"counts": dict(plan_mod.COUNTS),
           "first": [int(ref[c].values[0]) for c in plan_mod.RESULT_COLUMNS]}
    for name in plan_mod.CONTROLS + ("bfloat16",):
        other = (plan_mod.reference(tables, lossy=control.bf16)
                 if name == "bfloat16"
                 else plan_mod.reference(tables, control=name))
        got = {c: other[c].values for c in plan_mod.RESULT_COLUMNS}
        numbers = check.compare(got, ref, plan_mod.RESULT_COLUMNS,
                                plan_mod.ORDERED)
        out[name] = {"numbers": numbers,
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
    names = cell.plan.CONTROLS + ("bfloat16",)
    held = True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = one_seed(cell, seed, gen)
        stated = all(out["counts"][k] == cell.batch[k] for k in STATED) \
            and out["counts"]["matched"] == cell.batch["matched_pairs"]
        held = held and stated and all(out[c]["fails"] for c in names)
        print(f"control {CELL} seed {seed} at {cell.batch['store_rows']} + "
              f"{cell.batch['web_rows']} rows, the first row "
              f"{out['first']}, the reference counted {out['counts']} "
              f"({'as' if stated else 'NOT as'} the configuration states): "
              + "; ".join(
                  f"{c}: {out[c]['numbers']} -> "
                  f"{'fails' if out[c]['fails'] else 'PASSES'} the comparison"
                  for c in names), flush=True)
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
    assert main(["--seeds", "3,77,4100000001"], platform="cpu",
                tiny=True) == 0


def test_the_bfloat16_control_fails_and_the_reference_passes(on_the_cpu):
    from chipbench import control
    assert control.main(["--workload", CELL, "--seeds", "3,77"],
                        platform="cpu", tiny=True) == 0


if __name__ == "__main__":
    sys.exit(main())
