"""How `data/program.xplane.pb` and `data/program.owners.json` were
recorded (on the chip, PR 26):

    python -m chipbench.tests.record_program_trace chiprun_out/program_probe
    python -m chipbench.tests.record_program_trace chiprun_out/program_probe --again

A join and an aggregate over small tables, traced for a fraction of a
second: three requests through a serving session into the capped tier (one
program, `jit_capped_plan`, two operators with instructions of their own),
then two executions in the eager tier (one `plan.op` span per operator, the
join on the Pallas hash join with its host sync). The program's own spans
land in the trace beside the device's ops; the owner map is what
`PlanExecutor.device_op_owners` gave for the capped program. Prints what
`program_spans` makes of it.

`--again`, in a second process, asks for the owner map of the same program
when its executable comes out of the persistent compile cache: the scopes
must have survived the round trip (jax leaves metadata out of the cache
key), and nothing may be compiled for it.
"""
import json
import os
import sys
import time

ROWS, DIM = 1 << 16, 256


def tables(seed: int = 26):
    import jax.numpy as jnp
    import numpy as np
    from chipbench import tpcds
    rng = np.random.default_rng(seed)
    fact = tpcds.table({"k": jnp.asarray(rng.integers(0, DIM, ROWS)),
                        "v": jnp.asarray(rng.integers(1, 100, ROWS))})
    dim = tpcds.table({"dk": jnp.arange(DIM, dtype=jnp.int64),
                       "g": jnp.arange(DIM, dtype=jnp.int64) % 7})
    return fact, dim


def plan():
    from spark_rapids_tpu.plan import PlanBuilder
    b = PlanBuilder()
    fact = b.scan("t", schema=["k", "v"])
    dim = b.scan("d", schema=["dk", "g"])
    return (fact.join(dim, left_on="k", right_on="dk")
            .aggregate(["g"], [("v", "sum", "total")]).build())


def main(out_dir: str, again: bool = False) -> int:
    import jax
    from chipbench import program_spans, spans, trace
    from spark_rapids_tpu.config import place_compile_cache
    from spark_rapids_tpu.plan import PlanExecutor
    from spark_rapids_tpu.runtime.sessionctx import request_scope
    from spark_rapids_tpu.serving import ServingScheduler

    print("compile cache at", place_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    counter = spans.CompileCounter()
    fact, dim = tables()
    inputs = {"t": fact, "d": dim}
    capped = PlanExecutor(mode="capped", degrade="off",
                          caps={"row_cap": ROWS, "key_cap": 64})
    res = capped.execute(plan(), inputs)
    jax.block_until_ready(res.valid)
    print("capped: attempts", res.attempts, "caps", res.caps,
          "compiles so far", counter.snapshot())
    before = counter.snapshot()
    t0 = time.perf_counter()
    owners = capped.device_op_owners(plan(), inputs)
    print(f"device_op_owners: {len(owners)} instructions in "
          f"{time.perf_counter() - t0:.3f} s, lowerings/compiles it cost "
          f"{tuple(b - a for a, b in zip(before, counter.snapshot()))[:2]}")
    print("owners:", sorted(set(owners.values())),
          {k: v for k, v in list(owners.items())[:8]})
    kept = os.path.join(out_dir, "program.owners.json")
    if again:
        with open(kept) as f:
            first = json.load(f)
        same = first == owners
        print("read back from the persistent cache: owner map",
              "unchanged" if same else "DIFFERS", "; backend compile "
              f"seconds this process {counter.snapshot()[2]:.3f}")
        return 0 if same and owners else 1
    os.makedirs(out_dir, exist_ok=True)

    sched = ServingScheduler(capped, workers=1)
    session = sched.open_session("probe")
    eager = PlanExecutor(mode="eager", degrade="off")
    fresh = [tables(seed)[0] for seed in (1, 2, 3)]   # new values: the
    #                                     result cache must not answer
    session.submit(plan(), inputs).result(timeout=600)          # warm both
    jax.block_until_ready(eager.execute(plan(), inputs).table.columns[0].data)
    rec = spans.Recorder()
    trace.start(out_dir)
    rec.sync()
    for t in fresh:
        r = session.submit(plan(), {"t": t, "d": dim}).result(timeout=600)
        jax.block_until_ready(r.valid)
    for i in range(2):
        # a direct execute numbers its requests from 0 as the scheduler
        # does; scoped, the two tiers' requests stay apart in one trace
        with request_scope(100 + i):
            r = eager.execute(plan(), inputs)
        jax.block_until_ready(r.table.columns[0].data)
    rec.sync()
    path = trace.stop(out_dir)
    session.close()
    sched.close()
    print("trace at", path, os.path.getsize(path), "bytes")
    with open(kept, "w") as f:
        json.dump(owners, f, indent=0, sort_keys=True)
    loaded = program_spans.load(path)
    for ops in loaded["devices"]:
        print("device ops:", sorted({(m, n) for m, n, *_ in ops}))
        for m, n, c, s, e, text in ops:
            if c == "custom-call":
                print("custom call", m, n, e - s, "ns",
                      program_spans.hlo_bytes(text), "B", text[:400])
    for owner_map in (owners, None):
        red = program_spans.Reduced(loaded, owner_map)
        print("--- with", "device_op_owners" if owner_map else
              "plan.op containment")
        print("\n".join(red.tables()))
        print("requests", red.requests, "digest_ms",
              red.request_ms("serving.digest"), "named",
              red.named_share(), "join", red.kind_share("HashJoin"),
              "kernels", red.kernels)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], again="--again" in sys.argv[2:]))
