"""The control of the comparison that decides `correct`, at a cell's own
size: the reference, put in the program's place, with every gathered
payload passed through bfloat16 - what a one-hot gather on the MXU at
default precision does (PERF.md, PR 24: the Pallas hash join and fused
select were wrong on the chip in exactly this way until pinned to
Precision.HIGHEST). It has to come out as NOT correct.

    python3 -m chipbench.control --workload q3.tasks --seeds 5,6,7

One batch per seed, drawn on the device as the cell draws it. Prints each
number compared beside its limit; exits 0 if every seed's control failed
the comparison and the reference itself passed it.
"""
import argparse
import sys

import numpy as np


def bf16(a):
    import ml_dtypes
    return np.asarray(a).astype(ml_dtypes.bfloat16).astype(np.int64)


def one_seed(cell, seed: int, gen, dims_np) -> dict:
    """-> the numbers of the sound reference (against itself, through the
    comparison's own path) and of the control."""
    import jax
    from chipbench import check, harness
    tables = {n: (c, {}) for n, c in dims_np.items()}
    tables.update(jax.device_get(
        gen(*harness.batch_keys(cell, seed, harness.TABLE_STREAM))))
    plan_mod = cell.plan
    ref = plan_mod.reference(tables)
    as_answer = lambda df: {c: df[c].values for c in plan_mod.RESULT_COLUMNS}
    sound = check.compare(as_answer(ref), ref, plan_mod.RESULT_COLUMNS,
                          plan_mod.ORDERED)
    control = check.compare(as_answer(plan_mod.reference(tables, lossy=bf16)),
                            ref, plan_mod.RESULT_COLUMNS, plan_mod.ORDERED)
    return {"seed": seed, "reference_rows": len(ref), "sound": sound,
            "control": control}


def main(argv=None, platform: str = "tpu", tiny: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from chipbench import check, harness
    cell = harness.Cell(args.workload, tiny=tiny)
    harness.require_devices(cell, platform)
    import spark_rapids_tpu  # noqa: F401  (64-bit integers on)
    dims_np = cell.plan.dimensions(cell.sizes)
    gen = cell.plan.batch_generator(cell.sizes, cell.batch)
    held = True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = one_seed(cell, seed, gen, dims_np)
        failed = any(out["control"][k] > lim
                     for k, lim in check.LIMITS.items())
        sound = all(out["sound"][k] <= lim for k, lim in check.LIMITS.items())
        held = held and failed and sound
        print(f"control {cell.name} seed {seed}: reference rows "
              f"{out['reference_rows']}; " + "; ".join(
                  f"{k}: sound {out['sound'][k]}, control "
                  f"{out['control'][k]}, limit {lim}"
                  for k, lim in check.LIMITS.items())
              + f" -> control {'fails' if failed else 'PASSES'} the "
              "comparison", flush=True)
    return 0 if held else 1


if __name__ == "__main__":
    sys.exit(main())
