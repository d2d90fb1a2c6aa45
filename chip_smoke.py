#!/usr/bin/env python3
"""Chip smoke: the plan engine's main path, once, on the attached TPU.

    python chip_smoke.py             # one chip: batch q3 + q72, serving
    python chip_smoke.py --chips 4   # only the cross-chip path: SPMD q5

One process, one import of JAX, no child that needs the chip. Every phase
raises on failure; the last line of stdout is the contract's JSON object
and is printed only after every phase passed. With no TPU the script exits
non-zero before building a table. Wall times printed here are for the next
reader's orientation, not results (PERF.md starts with the first
benchmark).

Data is made from `--seed`; the reference for every result is pandas on the
host over the same arrays. Sizes, and why two of them are cut:

- eager q3 runs at the bench's own full size (`bench_nds_q3.py` at scale
  1): a 10M-row fact table, all int64, 240 MB of fact columns in HBM. Its
  two fact-scale joins take the Pallas hash join, so nothing large is
  sorted.
- the capped tier (q3 cold + warm, q72, the three serving submits) runs at
  N_CAPPED fact rows, and `--chips 4` at N_CROSS_CHIP (the benches' floor
  size). The cut is forced by the run's 1200 s limit, compilation
  included, not by the chip: XLA's TPU compiler takes minutes per large
  multi-operand int64 `lax.sort` (a 3-operand one: 93 s at 100k rows,
  164 s at 10M, on the chip host), the capped q3 program holds nine sorts
  and q72's more, and at 10M rows the capped q3 program alone does not
  compile in half an hour. Widths, dtypes, key distributions, dimension
  tables and plans are the benches' own at every size.
"""
import argparse
import json
import sys
import time

# bench_nds_q3.main(): n_sales = int(10_000_000 * scale), at scale 1
N_SALES = 10_000_000
# capped tier + serving: what its sorts let a cold process compile in time
N_CAPPED = 100_000
# --chips 4: bench_nds_q5.main()'s floor, max(int(10_000_000 * scale), 8192)
N_CROSS_CHIP = 8_192
PLATFORM = "tpu"        # what jax.devices()[0].platform must say
SERVING_SUBMITS = 3


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


class CompileCounter:
    """Counts jit lowerings (in-memory program-cache misses) and backend
    compiles through jax.monitoring — 'compiled nothing' means both stayed
    put, whatever the persistent cache holds."""
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.lowerings = 0
        self.compiles = 0
        self.compile_secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.LOWER:
            self.lowerings += 1
        elif event == self.COMPILE:
            self.compiles += 1
            self.compile_secs += secs

    def snapshot(self):
        return self.lowerings, self.compiles


def assert_on_device(res, devs, what: str) -> None:
    """Every array of the result lives on the accelerator."""
    allowed = set(devs)
    arrays = [res.valid] if getattr(res, "valid", None) is not None else []
    for c in res.table.columns:
        arrays.append(c.data)
        if c.validity is not None:
            arrays.append(c.validity)
    for a in arrays:
        where = a.devices()
        if not where <= allowed or any(d.platform != PLATFORM for d in where):
            raise AssertionError(f"{what}: a result array lives on {where}, "
                                 f"not on {sorted(map(str, allowed))}")


def result_frame(res):
    import pandas as pd
    t = res.compact() if res.mode == "capped" else res.table
    return pd.DataFrame({n: t[n].to_pylist() for n in t.names})


def assert_rows_equal(got, ref, ordered, what: str) -> None:
    """Row-for-row against the pandas reference: the presentation-sort
    columns `ordered` agree position by position, and the full rows agree
    as multisets (rows tied on the whole sort key may legally swap)."""
    import numpy as np
    if len(got) != len(ref) or len(ref) == 0:
        raise AssertionError(f"{what}: {len(got)} rows, reference has "
                             f"{len(ref)}")
    for c in ordered:
        np.testing.assert_array_equal(got[c].values, ref[c].values,
                                      err_msg=f"{what}: column {c}")
    cols = list(ref.columns)
    if sorted(map(tuple, got[cols].values.tolist())) != \
            sorted(map(tuple, ref[cols].values.tolist())):
        raise AssertionError(f"{what}: rows differ from the reference")


def assert_clean(res, what: str) -> None:
    if res.degraded is not False:
        raise AssertionError(f"{what}: degraded={res.degraded!r} — part of "
                             "the plan ran on the CPU tier")


# ---- references (pandas, host, same seed) -----------------------------------

def q3_reference(n_sales: int, seed: int):
    import pandas as pd
    from benchmarks.bench_nds_q3 import _datagen
    (date_sk, d_year, d_moy, item_sk, i_brand, i_manufact, ss) = \
        _datagen(n_sales, seed)
    ddf = pd.DataFrame({"d_date_sk": date_sk, "d_year": d_year,
                        "d_moy": d_moy})
    idf = pd.DataFrame({"i_item_sk": item_sk, "i_brand": i_brand,
                        "i_manufact": i_manufact})
    j = (pd.DataFrame(ss)
         .merge(ddf[ddf.d_moy == 11], left_on="sold_date_sk",
                right_on="d_date_sk")
         .merge(idf[idf.i_manufact == 42], left_on="item_sk",
                right_on="i_item_sk"))
    return (j.groupby(["d_year", "i_brand"], as_index=False)
             .agg(revenue=("price_cents", "sum"))
             .sort_values(["d_year", "revenue"], ascending=[True, False])
             [["d_year", "i_brand", "revenue"]])


def q72_reference(n_sales: int, seed: int):
    """tests/test_nds_query.py's oracle with the inventory join on the
    composite (item, week) key — the same rows as its item join + week
    filter, without the 104x intermediate."""
    import pandas as pd
    from benchmarks.bench_nds_q72 import _datagen
    cs, inv, items, hd, wh, dates = _datagen(n_sales, seed)
    hddf, ddf = pd.DataFrame(hd), pd.DataFrame(dates)
    j = pd.DataFrame(cs).merge(hddf[hddf.hd_buy_potential == 3],
                               left_on="hd_sk", right_on="hd_demo_sk")
    j = j.merge(pd.DataFrame(items), left_on="item_sk", right_on="i_item_sk")
    j = j.merge(ddf[ddf.d_year == 1], left_on="sold_date_sk",
                right_on="d_date_sk")
    j = j[j.ship_days > 5]
    j = j.merge(pd.DataFrame(inv), left_on=["i_item_sk", "d_week"],
                right_on=["inv_item_sk", "inv_week"])
    j = j[j.inv_qty < j.qty]
    j = j.merge(pd.DataFrame(wh), left_on="inv_wh_sk",
                right_on="w_warehouse_sk")
    return (j.groupby(["i_item_sk", "w_warehouse_sk", "d_week"],
                      as_index=False).size()
             .rename(columns={"size": "cnt"})
             .sort_values(["cnt", "i_item_sk", "w_warehouse_sk", "d_week"],
                          ascending=[False, True, True, True])
             [["i_item_sk", "w_warehouse_sk", "d_week", "cnt"]])


# ---- one-chip phases ---------------------------------------------------------

def q3_caps(n_sales: int) -> dict:
    # bench_nds_q3.main(): the caps its plan-tier configs run under
    return dict(row_cap=max(n_sales // 8, 1024), key_cap=4096)


def load_q3(n_sales: int, seed: int):
    """-> (plan inputs, pandas reference): the q3 tables, resident on the
    device."""
    import jax
    from benchmarks.bench_nds_q3 import build_tables
    from benchmarks.nds_plans import q3_inputs
    t0 = time.perf_counter()
    tables = build_tables(n_sales, seed)
    jax.block_until_ready([c.data for t in tables for c in t.columns])
    resident = sum(c.data.nbytes for c in tables[0].columns)
    log(f"[q3] load: {tables[0].num_rows} fact rows, {resident} B of fact "
        f"columns on {tables[0].columns[0].data.devices()} in "
        f"{time.perf_counter() - t0:.3f} s")
    return q3_inputs(*tables), q3_reference(n_sales, seed)


def run_checked(run, ref, ordered, devs, counter, what: str):
    """One execution (`run()` -> PlanResult), blocked, timed,
    compile-counted and checked: not degraded, on the device, row for row
    equal to the reference. -> (result, whether anything compiled)."""
    import jax
    before = counter.snapshot()
    t0 = time.perf_counter()
    res = run()
    jax.block_until_ready([c.data for c in res.table.columns])
    wall = time.perf_counter() - t0
    after = counter.snapshot()
    log(f"[{what}] wall {wall:.3f} s, lowerings {after[0] - before[0]}, "
        f"backend compiles {after[1] - before[1]}, caps {res.caps}, "
        f"attempts {res.attempts}")
    assert_clean(res, what)
    assert_on_device(res, devs, what)
    assert_rows_equal(result_frame(res), ref, ordered, what)
    return res, after != before


def phase_q3_capped(devs, counter, seed: int):
    """batch query, capped tier: cold, then warm (compiles nothing)."""
    from benchmarks.nds_plans import q3_plan
    from spark_rapids_tpu.plan import PlanExecutor
    inputs, ref = load_q3(N_CAPPED, seed)
    ex = PlanExecutor(mode="capped", degrade="off", caps=q3_caps(N_CAPPED))
    for label in ("cold", "warm"):
        res, compiled = run_checked(
            lambda: ex.execute(q3_plan(), inputs), ref,
            ["d_year", "revenue"], devs, counter, f"q3 capped {label}")
        if label == "warm" and compiled:
            raise AssertionError("q3 capped warm run compiled something")
    log(res.profile_text())
    return ex


def phase_q3_eager(devs, counter, seed: int):
    """load + batch query at the bench's full size, eager tier (operator
    at a time, registry kernels)."""
    from benchmarks.nds_plans import q3_plan
    from spark_rapids_tpu.plan import PlanExecutor
    inputs, ref = load_q3(N_SALES, seed)
    ex = PlanExecutor(mode="eager", degrade="off")
    res, _ = run_checked(lambda: ex.execute(q3_plan(), inputs), ref,
                         ["d_year", "revenue"], devs, counter, "q3 eager")
    log(res.profile_text())


def phase_q72_capped(devs, counter, seed: int):
    """a second plan shape: q72, inventory fan-out join, capped tier."""
    from benchmarks.bench_nds_q72 import build_tables
    from benchmarks.nds_plans import q72_inputs, q72_plan
    from spark_rapids_tpu.plan import PlanExecutor

    tables = build_tables(N_CAPPED, seed)
    n = tables[0].num_rows
    # bench_nds_q72.main(): the caps its plan-tier configs run under
    caps = dict(row_cap=max(n // 2, 2048), key_cap=max(n // 16, 1024))
    ex = PlanExecutor(mode="capped", degrade="off", caps=caps)
    inputs = q72_inputs(*tables)
    res, _ = run_checked(lambda: ex.execute(q72_plan(), inputs),
                         q72_reference(N_CAPPED, seed),
                         ["cnt", "i_item_sk", "w_warehouse_sk", "d_week"],
                         devs, counter, "q72 capped cold")
    log(res.profile_text())


def phase_serving(devs, counter, executor, seed: int):
    """serving: one session, three q3 submits, each over tables drawn from
    its own seed at the same shape — the plan-result cache cannot answer,
    the compiled program must."""
    from benchmarks.bench_nds_q3 import build_tables
    from benchmarks.nds_plans import q3_inputs, q3_plan
    from spark_rapids_tpu.serving import ServingScheduler

    stats = devs[0].memory_stats() or {}
    # the session's device-memory quota: the chip's own limit (the 256 MiB
    # default is sized for many small tenants)
    quota = int(stats.get("bytes_limit", 16 << 30))
    with ServingScheduler(executor) as sched:
        with sched.open_session("chip-smoke", quota_bytes=quota) as session:
            for i in range(SERVING_SUBMITS):
                s = seed + 1 + i
                what = f"serving submit {i + 1} (seed {s})"
                inputs = q3_inputs(*build_tables(N_CAPPED, s))

                def answered():
                    ticket = session.submit(q3_plan(), inputs)
                    res = ticket.result(timeout=900)
                    if ticket.cached:
                        raise AssertionError(
                            f"{what}: answered from the plan-result cache, "
                            "not by an execution")
                    return res

                _, compiled = run_checked(
                    answered, q3_reference(N_CAPPED, s),
                    ["d_year", "revenue"], devs, counter, what)
                if i > 0 and compiled:
                    raise AssertionError(f"{what} compiled something")
        log(f"[serving] metrics {json.dumps(sched.metrics(), default=str)}")


# ---- four-chip phase ---------------------------------------------------------

def phase_cross_chip(devs, seed: int):
    """SPMD q5 over a 4-device mesh against the single-device eager tier
    (benchmarks/nds_plans.run_plan_distributed asserts exact parity), with
    the placement of sharded inputs and exchange outputs observed."""
    from benchmarks.bench_nds_q5 import build_tables
    from benchmarks.nds_plans import (q5_inputs, q5_plan,
                                      run_plan_distributed)
    from spark_rapids_tpu.parallel import make_mesh
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
        tabs, dates = build_tables(N_CROSS_CHIP, seed)
        n_total = sum(t.num_rows + r.num_rows for t, r in tabs.values())
        t0 = time.perf_counter()
        rec, res = run_plan_distributed(
            "chip_smoke_q5_dist", {"num_rows": n_total}, q5_plan(),
            q5_inputs(tabs, dates), n_rows=n_total, iters=1, mesh=mesh)
        wall = time.perf_counter() - t0
    finally:
        dist.DistContext.exec_node = exec_node
    assert_clean(res, "q5 distributed")
    log(res.profile_text())
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
    log(f"[q5 distributed] {len(seen['scan'])} sharded scans and "
        f"{len(seen['exchange'])} hash-partitioned outputs, each over {n_chips} "
        f"distinct devices; exchange_bytes {rec['exchange_bytes']}, "
        f"exchanges observed {rec['exchanges_observed']}, parity with the "
        f"single-device eager tier exact; wall {wall:.3f} s (reference run, "
        "distributed run and one timed repeat, compiles included)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the cross-chip path (SPMD q5)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    import jax
    devs = require_devices(args.chips)
    from spark_rapids_tpu.config import place_compile_cache  # x64 goes on
    log(f"chip_smoke: {len(devs)} x {devs[0].platform}:"
        f"{devs[0].device_kind}, jax {jax.__version__}, compile cache at "
        f"{place_compile_cache()}, seed {args.seed}")
    counter = CompileCounter()
    if args.chips == 4:
        phase_cross_chip(devs, args.seed)
    else:
        phase_q3_eager(devs, counter, args.seed)
        capped = phase_q3_capped(devs, counter, args.seed)
        phase_q72_capped(devs, counter, args.seed)
        phase_serving(devs, counter, capped, args.seed)
    log(f"chip_smoke: all phases passed in "
        f"{time.perf_counter() - t_start:.1f} s; {counter.compiles} backend "
        f"compiles took {counter.compile_secs:.1f} s")
    print(json.dumps({"ok": True,
                      "device": {"platform": devs[0].platform,
                                 "kind": devs[0].device_kind,
                                 "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
