"""The controls of `q5.shuffle`'s comparison, the born-sharded generator
against the same draw on one device, and the byte function of the
`exchange` layer's readers.

    python3 -m chipbench.tests.test_correct_q5 --seeds 5,6,7     # the chip, the cell's size
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 -m pytest chipbench/tests/test_correct_q5.py

Two controls, each the plain reference with one thing taken away, put in
the program's place; each has to come out as NOT correct. `int32` carries
every measure through 32 bits: it fails wherever a subtotal passes 2**31,
which the cell's size does and the rehearsal's does not, so the rehearsal
holds only the other control to failing and a third test draws 1,000,000
`catalog_sales` rows on the CPU (the window keeps 1.04% of them at some
505,000 cents a row: a catalog subtotal of 5e9). `no_returns_join` drops the merge of
`web_returns` with `web_sales`: a web return then has no site, counts
nowhere, and the grand total's `returns` and `profit` are off.
"""
import argparse
import os
import sys

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CELL = "q5.shuffle"
CONTROLS = ("int32", "no_returns_join")


def one_seed(cell, seed: int, gen) -> dict:
    import jax
    from chipbench import check, harness
    plan_mod = cell.plan
    tables = {n: (c, {}) for n, c in plan_mod.dimensions(cell.sizes).items()}
    tables.update(jax.device_get(
        gen(*harness.batch_keys(cell, seed, harness.TABLE_STREAM))))
    ref = plan_mod.reference(tables)
    catalog = ref[(ref.channel == 0) & (ref.id == -1)]
    out = {"rows": len(ref), "catalog_sales": int(catalog.sales.iloc[0])}
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
    must_fail = ("no_returns_join",) if tiny else CONTROLS
    held = True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = one_seed(cell, seed, gen)
        held = held and all(out[c]["fails"] for c in must_fail)
        print(f"control {CELL} seed {seed} at "
              f"{cell.plan.fact_rows(cell.batch)} fact rows, {out['rows']} "
              "result rows: " + "; ".join(
                  f"{c}: {out[c]['numbers']} -> "
                  f"{'fails' if out[c]['fails'] else 'PASSES'} the comparison"
                  for c in CONTROLS), flush=True)
    return 0 if held else 1


def _needs_four_devices():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices: XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4")


def test_a_dropped_returns_join_is_not_correct_at_the_rehearsal_size(
        monkeypatch):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _needs_four_devices()
    from chipbench import harness, rehearse
    monkeypatch.setattr(harness, "require_devices", rehearse.cpu_devices)
    assert main(["--seeds", "3,2147483659,77"], platform="cpu",
                tiny=True) == 0


@pytest.mark.parametrize("seed", [3, 2147483659, 77])
def test_a_32_bit_path_is_not_correct_once_a_subtotal_passes_2_31(seed):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _needs_four_devices()
    import spark_rapids_tpu  # noqa: F401
    from chipbench import harness
    cell = harness.Cell(CELL, tiny=True)
    cell.batch = dict(cell.batch, catalog_sales_rows=1_000_000,
                      catalog_returns_rows=100_000)
    gen = cell.plan.batch_generator(cell.sizes, cell.batch)
    out = one_seed(cell, seed, gen)
    assert out["catalog_sales"] > 2**31, out
    assert out["int32"]["fails"] and out["no_returns_join"]["fails"], out


def test_born_sharded_generator_equals_the_draw_on_one_device():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _needs_four_devices()
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import spark_rapids_tpu  # noqa: F401
    from chipbench import harness, tpcds
    cell = harness.Cell(CELL, tiny=True)
    plan_mod = cell.plan
    gen = plan_mod.batch_generator(cell.sizes, cell.batch)
    keys = (tpcds.run_key(cell.sizes["dsdgen_seed"], 0),
            tpcds.run_key(2**31 + 11, harness.TABLE_STREAM))
    drawn = gen(*keys)
    chips = int(cell.batch["chips"])
    one = [plan_mod.draw_shard(*keys, jnp.int32(i), cell.sizes, cell.batch)
           for i in range(chips)]
    for name, (cols, validity) in drawn.items():
        assert not validity and list(cols) == sorted(plan_mod.COLUMNS[name])
        for c, a in cols.items():
            assert a.sharding.spec == P(plan_mod.AXIS)
            assert len(a.sharding.device_set) == chips
            whole = np.concatenate([np.asarray(o[name][c]) for o in one])
            assert (np.asarray(a) == whole).all(), (name, c)
    # what the plan leans on: a unique (item, order) per sale, and every
    # return's sale among them
    ws, wr = drawn["web_sales"][0], drawn["web_returns"][0]
    sold = set(zip(np.asarray(ws["ws_item_sk"]).tolist(),
                   np.asarray(ws["ws_order_number"]).tolist()))
    assert len(sold) == len(ws["ws_item_sk"])
    returned = list(zip(np.asarray(wr["wr_item_sk"]).tolist(),
                        np.asarray(wr["wr_order_number"]).tolist()))
    assert len(set(returned)) == len(returned) and set(returned) <= sold


def test_collective_bytes_come_from_the_operands():
    from chipbench import collectives
    a2a = ("%all-to-all.3 = (u32[4,2048]{1,0}, u32[4,2048]{1,0}) "
           "all-to-all(u32[4,2048]{1,0} %fusion.1, u32[4,2048]{1,0} "
           "%fusion.2), channel_id=1, replica_groups={{0,1,2,3}}")
    assert collectives.operand_bytes(a2a) == 2 * 4 * 2048 * 4
    gathered = ("%all-gather.1 = s64[12]{0} all-gather(s64[3]{0} %x.1), "
                "channel_id=2, dimensions={0}")
    assert collectives.operand_bytes(gathered) == 24
    # what leaves a chip of four, per byte of operand
    assert collectives.leaving("all-to-all", 4) == 0.75
    assert collectives.leaving("reduce-scatter", 4) == 0.75
    assert collectives.leaving("all-gather-start", 4) == 3.0
    assert collectives.leaving("all-reduce", 4) == 1.5
    assert collectives.leaving("collective-permute-start", 4) == 1.0
    assert collectives.leaving("all-to-all", 1) == 0.0
    assert collectives.is_collective("all-to-all")
    assert collectives.is_collective("all-gather-start")
    assert collectives.is_collective("collective-permute-done")
    assert not collectives.is_collective("fusion")
    assert not collectives.is_collective("sort")


if __name__ == "__main__":
    sys.exit(main())
