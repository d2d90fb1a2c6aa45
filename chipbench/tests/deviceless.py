"""Compile a capped cell's programs for a DESCRIBED v5e chip, at the cell's
real shapes, without the chip (on-chip-measurement guide, section 2):

    JAX_PLATFORMS=cpu python3 -m chipbench.tests.deviceless --workload q3.tasks

The batch generator and the capped tier's one whole-plan program, traced
as the chip would trace them (code that asks jax.default_backend() sees
"tpu"). Prints compile seconds and the compiler's memory analysis. A
compile that passes is not a chip run; the sandbox took about six times
the chip host's time in PR 24. The eager tier has no one program to
capture (its shapes follow the data), so a resident cell only gets its
generator compiled here.
"""
import argparse
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench import harness, tpcds
    from spark_rapids_tpu.plan import PlanExecutor

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    cell = harness.Cell(args.workload)
    plan_mod = cell.plan
    dims_np = plan_mod.dimensions(cell.sizes)
    gen = plan_mod.batch_generator(cell.sizes, cell.batch)
    key = jax.random.key(0)
    kshape = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=chip)
    t0 = time.perf_counter()
    compiled = gen.lower(kshape, kshape).compile()
    print(f"{cell.name}: generator compiled in {time.perf_counter() - t0:.1f} s; "
          f"{compiled.memory_analysis()}", flush=True)
    if cell.traffic["tier"] != "capped":
        return 0
    drawn = jax.eval_shape(gen, key, key)
    inputs = {n: tpcds.table(c) for n, c in dims_np.items()}
    for name, (cols, validity) in drawn.items():
        inputs[name] = tpcds.table(
            {n: jnp.zeros(a.shape, a.dtype) for n, a in cols.items()},
            {n: jnp.ones(a.shape, a.dtype) for n, a in validity.items()},
            plan_mod.COLUMNS[name])
    captured = {}
    real = PlanExecutor._jitted_capped

    class Captured(Exception):
        pass

    def capturing(self, plan, schemas, caps, input_key):
        fn, bm, km, hit = real(self, plan, schemas, caps, input_key)

        def stop(tables):
            captured.update(fn=fn, tables=tables, caps=caps)
            raise Captured()
        return stop, bm, km, hit

    PlanExecutor._jitted_capped = capturing
    ex = PlanExecutor(mode="capped", caps=plan_mod.caps(cell.batch),
                      **cell.config.get("executor", {}))
    try:
        ex.execute(plan_mod.plan(), inputs)
    except Captured:
        pass
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        captured["tables"])
    jax.default_backend = lambda: "tpu"
    print(f"{cell.name}: capped program at caps {captured['caps']}: lowering",
          flush=True)
    t0 = time.perf_counter()
    compiled = captured["fn"].lower(shapes).compile()
    print(f"{cell.name}: capped program compiled in "
          f"{time.perf_counter() - t0:.1f} s; {compiled.memory_analysis()}",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
