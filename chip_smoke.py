#!/usr/bin/env python3
"""Chip smoke: the one path no cell of the benchmark reaches, once, on four
attached TPU chips — q5 as one SPMD program over a 2x2 mesh.

    python chip_smoke.py --chips 4

Everything one chip does is the benchmark's (`python3 -m chipbench.run
--workload q3.tasks|q3.share|q72.tasks|q1.tasks`, with `correct` beside
every number). This file goes whole when the four-chip cell `q5.shuffle`
(ROADMAP R2) lands.

One process, one import of JAX, no child that needs the chip. The phase
raises on failure; the last line of stdout is the contract's JSON object
and is printed only after it passed. Without four TPU devices the script
exits non-zero before building a table. Wall times printed here are for
the next reader's orientation, not results.

Plan, data (made from `--seed`) and the pandas reference are
examples/nds.py's q5. N_CROSS_CHIP is small because the compile of the
SPMD program, not the chip, is what the call's time limit buys.
"""
import argparse
import json
import sys
import time

N_CROSS_CHIP = 8_192
PLATFORM = "tpu"        # what jax.devices()[0].platform must say


def log(msg: str = "") -> None:
    print(msg, flush=True)


def require_devices(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        print(f"chip_smoke: no {PLATFORM} device — jax.devices()[0] is "
              f"{devs[0].platform}:{devs[0].device_kind}; nothing was run",
              file=sys.stderr)
        sys.exit(2)
    if len(devs) != n_chips:
        print(f"chip_smoke: --chips {n_chips} but jax sees {len(devs)} "
              f"device(s); nothing was run", file=sys.stderr)
        sys.exit(2)
    return devs


def phase_cross_chip(devs, seed: int):
    """SPMD q5 over a 4-device mesh against the pandas reference, with the
    placement of sharded inputs and exchange outputs observed."""
    import pandas as pd
    from examples.nds import (assert_rows_equal, q5_inputs, q5_plan,
                              q5_reference, q5_tables)
    from spark_rapids_tpu.parallel import make_mesh
    from spark_rapids_tpu.plan import PlanExecutor
    from spark_rapids_tpu.plan import distributed as dist

    n_chips = len(devs)
    mesh = make_mesh(n_chips)          # cpu_fallback left False
    seen = {"scan": [], "exchange": []}

    # an observer only: record where the tier put each sharded relation
    # (scans, and the hash-partitioned outputs its exchanges produce —
    # q5's hash exchanges are fused into the aggregates above them)
    exec_node = dist.DistContext.exec_node

    def exec_node_seen(self, node, childs, inputs, schemas, m, metrics):
        out = exec_node(self, node, childs, inputs, schemas, m, metrics)
        if isinstance(out, dist.ShardedRel):
            where = frozenset(d for c in out.table.columns
                              for d in c.data.devices())
            if node.kind == "Scan":
                seen["scan"].append(where)
            elif out.part:
                seen["exchange"].append(where)
        return out

    dist.DistContext.exec_node = exec_node_seen
    try:
        inputs = q5_inputs(*q5_tables(N_CROSS_CHIP, seed))
        t0 = time.perf_counter()
        res = PlanExecutor(mesh=mesh).execute(q5_plan(), inputs)
        got = pd.DataFrame(res.table.to_pydict())
        wall = time.perf_counter() - t0
    finally:
        dist.DistContext.exec_node = exec_node
    if res.degraded is not False:
        raise AssertionError(f"q5 distributed: degraded={res.degraded!r} — "
                             "part of the plan ran on the CPU tier")
    log(res.profile_text())
    assert_rows_equal(got, q5_reference(N_CROSS_CHIP, seed),
                      ["channel", "sales"], "q5 distributed")
    for kind, sets in seen.items():
        if not sets:
            raise AssertionError(f"q5 distributed: no {kind} was observed — "
                                 "the plan did not take the SPMD path")
        for s in sets:
            if len(s) != n_chips or any(d.platform != PLATFORM for d in s):
                raise AssertionError(
                    f"q5 distributed: a {kind} output is spread over "
                    f"{sorted(map(str, s))}, not {n_chips} distinct "
                    f"{PLATFORM} devices")
    observed = {}
    for m in res.metrics.values():
        if m.exchange_how:
            observed[m.exchange_how] = observed.get(m.exchange_how, 0) + 1
    log(f"[q5 distributed] {len(seen['scan'])} sharded scans and "
        f"{len(seen['exchange'])} hash-partitioned outputs, each over {n_chips} "
        f"distinct devices; exchange_bytes "
        f"{sum(m.exchange_bytes for m in res.metrics.values())}, "
        f"exchanges observed {observed}, equal to the pandas reference row "
        f"for row; wall {wall:.3f} s (one run, compiles included)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(4,), default=4,
                    help="the cross-chip path (SPMD q5) needs all four")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    import jax
    devs = require_devices(args.chips)
    from chipbench.spans import CompileCounter
    from spark_rapids_tpu.config import place_compile_cache  # x64 goes on
    log(f"chip_smoke: {len(devs)} x {devs[0].platform}:"
        f"{devs[0].device_kind}, jax {jax.__version__}, compile cache at "
        f"{place_compile_cache()}, seed {args.seed}")
    counter = CompileCounter()
    phase_cross_chip(devs, args.seed)
    log(f"chip_smoke: passed in "
        f"{time.perf_counter() - t_start:.1f} s; {counter.compiles} backend "
        f"compiles took {counter.compile_secs:.1f} s")
    print(json.dumps({"ok": True,
                      "device": {"platform": devs[0].platform,
                                 "kind": devs[0].device_kind,
                                 "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
