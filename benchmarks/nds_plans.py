"""The four NDS pipelines as physical plans (spark_rapids_tpu.plan).

One source of truth for the plan-engine form of q3/q5/q23/q72, imported by
BOTH the `_plan` bench configs (bench_nds_q*.py) and the parity tests
(tests/test_plan_nds.py) — the same no-drift contract the hand-wired `q3`
has with test_nds_query.py. Each builder returns a validated Plan whose
EAGER execution matches the hand-wired eager pipeline row for row, and
whose CAPPED execution (one XLA program, plan-level cap escalation) agrees
with the eager result after compaction.

Shapes worth noticing:
- q3/q72: star joins as chained HashJoin nodes; q72's inventory join uses
  the COMPOSITE (item, week) key — the physical plan a CBO picks, and the
  shape that keeps the capped tier fan-out-free (see q72_capped).
- q5: per-channel Union → semi-join date window → rollup via a shared
  Union feeding two aggregates (channel subtotals + the const-key grand
  total).
- q23: the two expensive subqueries are SHARED DAG nodes — both sides
  semi-join the same `freq`/`best` objects, so the executor computes each
  once per run (the subquery-reuse that is the whole point of q23); the
  best-customer HAVING uses a scalar-aggregate expression
  (`> 0.95 * scalar_max(rev)`).
"""
import sys

sys.path.insert(0, ".")

from spark_rapids_tpu.plan import (PlanBuilder, col, lit,  # noqa: E402
                                   scalar_max)


def q3_plan():
    b = PlanBuilder()
    sales = b.scan("sales", schema=["sold_date_sk", "item_sk", "price_cents"])
    dates = (b.scan("dates", schema=["d_date_sk", "d_year", "d_moy"])
             .filter(col("d_moy") == 11))
    items = (b.scan("items", schema=["i_item_sk", "i_brand", "i_manufact"])
             .filter(col("i_manufact") == 42))
    j = (sales.join(dates, left_on="sold_date_sk", right_on="d_date_sk")
              .join(items, left_on="item_sk", right_on="i_item_sk"))
    return (j.aggregate(["d_year", "i_brand"],
                        [("price_cents", "sum", "revenue")])
             .sort(["d_year", "revenue"], ascending=[True, False])
             .build())


def q5_plan():
    from benchmarks.bench_nds_q5 import DATE_HI, DATE_LO
    b = PlanBuilder()
    dates = (b.scan("dates", schema=["d_date_sk"])
             .filter((col("d_date_sk") >= DATE_LO) &
                     (col("d_date_sk") < DATE_HI)))
    sums = [("sales", "sum", "sales"), ("returns", "sum", "returns"),
            ("profit", "sum", "profit"), ("loss", "sum", "loss")]
    per = []
    for ci, name in enumerate(("store", "catalog", "web")):
        s = b.scan(f"{name}_sales",
                   schema=["sk", "date_sk", "sales_price", "profit"])
        r = b.scan(f"{name}_returns",
                   schema=["sk", "date_sk", "return_amt", "net_loss"])
        s_rows = s.project([("sk", col("sk")), ("date_sk", col("date_sk")),
                            ("sales", col("sales_price")),
                            ("profit", col("profit")),
                            ("returns", lit(0)), ("loss", lit(0))])
        r_rows = r.project([("sk", col("sk")), ("date_sk", col("date_sk")),
                            ("sales", lit(0)), ("profit", lit(0)),
                            ("returns", col("return_amt")),
                            ("loss", col("net_loss"))])
        u = (s_rows.union(r_rows)
             .join(dates, left_on="date_sk", right_on="d_date_sk",
                   how="left_semi"))
        g = (u.aggregate(["sk"], sums)
              .project([("channel", lit(ci))] +
                       [(n, col(n)) for n in ("sk", "sales", "returns",
                                              "profit", "loss")]))
        per.append(g)
    allch = PlanBuilder.union(per)
    sub = allch.aggregate(["channel"], sums)
    tot = (allch.project([("channel", lit(-1))] +
                         [(n, col(n)) for n in ("sales", "returns",
                                                "profit", "loss")])
                .aggregate(["channel"], sums))
    return (sub.union(tot)
               .sort(["channel", "sales"], ascending=[True, False])
               .build())


def q23_plan():
    from benchmarks.bench_nds_q23 import BEST_FRACTION, FREQ_THRESHOLD
    b = PlanBuilder()
    schema = ["item_sk", "cust_sk", "qty", "price"]
    store = b.scan("store", schema=schema)
    # subquery 1: frequent items — shared by both sides below
    freq = (store.aggregate(["item_sk"], [("qty", "count", "cnt")])
                 .filter(col("cnt") > FREQ_THRESHOLD))
    # subquery 2: best customers, HAVING sum > fraction * MAX(sum) — the
    # scalar-subquery expression evaluates over live groups only
    best = (store.project([("cust_sk", col("cust_sk")),
                           ("rev", col("qty") * col("price"))])
                 .aggregate(["cust_sk"], [("rev", "sum", "rev")])
                 .filter(col("rev") >
                         lit(BEST_FRACTION) * scalar_max(col("rev"))))
    side_totals = []
    for name in ("catalog", "web"):
        side = b.scan(name, schema=schema)
        tot = (side.join(freq, left_on="item_sk", right_on="item_sk",
                         how="left_semi")
                   .join(best, left_on="cust_sk", right_on="cust_sk",
                         how="left_semi")
                   .project([("rev", col("qty") * col("price"))])
                   .aggregate([], [("rev", "sum", "total")]))
        side_totals.append(tot)
    return (side_totals[0].union(side_totals[1])
            .aggregate([], [("total", "sum", "total")])
            .build())


def q72_plan():
    b = PlanBuilder()
    cs = b.scan("cs", schema=["item_sk", "hd_sk", "sold_date_sk",
                              "ship_days", "qty"])
    inv = b.scan("inv", schema=["inv_item_sk", "inv_week", "inv_wh_sk",
                                "inv_qty"])
    items = b.scan("items", schema=["i_item_sk", "i_brand"])
    hd = (b.scan("hd", schema=["hd_demo_sk", "hd_buy_potential"])
          .filter(col("hd_buy_potential") == 3))
    wh = b.scan("wh", schema=["w_warehouse_sk"])
    dates = (b.scan("dates", schema=["d_date_sk", "d_week", "d_year"])
             .filter(col("d_year") == 1))
    j = (cs.join(hd, "hd_sk", "hd_demo_sk")
           .join(items, "item_sk", "i_item_sk")
           .join(dates, "sold_date_sk", "d_date_sk")
           .filter(col("ship_days") > 5)
           # composite (item, week) key: one inventory row per combo, so
           # the join is fan-out-free (same rows as item-join + week filter)
           .join(inv, ["i_item_sk", "d_week"], ["inv_item_sk", "inv_week"])
           .filter(col("inv_qty") < col("qty"))
           .join(wh, "inv_wh_sk", "w_warehouse_sk"))
    return (j.aggregate(["i_item_sk", "w_warehouse_sk", "d_week"],
                        [("qty", "size", "cnt")])
             .sort(["cnt", "i_item_sk", "w_warehouse_sk", "d_week"],
                   ascending=[False, True, True, True])
             .build())


# ---- optimized/unoptimized bench variants -----------------------------------

def _sink_bytes_in(res) -> int:
    """Bytes entering width-sensitive operators (join/aggregate/sort/
    exchange) of the EXECUTED plan — the per-op metric column pruning is
    expected to reduce (dead columns no longer cross the boundary)."""
    from spark_rapids_tpu.plan import (Exchange, HashAggregate, HashJoin,
                                       Sort, TopK)
    total = 0
    for node in res.plan.nodes:
        if isinstance(node, (HashJoin, HashAggregate, Sort, TopK,
                             Exchange)):
            total += sum(res.metrics[c.label].bytes_out
                         for c in node.children)
    return total


def run_plan_variants(bench: str, axes: dict, plan, inputs, *,
                      n_rows: int, iters: int, caps: dict = None):
    """Time the capped plan tier UNOPTIMIZED then OPTIMIZED, assert result
    parity between the two, and record rows/bytes deltas + optimizer
    fields on the JSONL rows (docs/optimizer.md). Shared by the four
    bench_nds_q*.py plan configs and ci/nightly.sh's optimizer-parity
    stage, so the bench numbers and the parity gate can never drift.

    Runs with the stats store SCOPED OFF: this is the STATIC
    optimizer-off-vs-on A/B — with adaptivity live, the "off" variant's
    execution would record observations the "on" variant consumes, and
    the measured rules_fired/bytes deltas would silently describe a warm
    hybrid instead of the static rules (docs/adaptive.md; the adaptive
    cold/warm trajectory has its own gate, benchmarks/adaptive_bench.py).
    The JSONL rows stamp `adaptive: false` accordingly."""
    from spark_rapids_tpu.plan import PlanExecutor
    from spark_rapids_tpu.plan import stats as stats_mod
    from benchmarks.common import run_config

    with stats_mod.scoped_store(None):
        return _plan_variants_static(bench, axes, plan, inputs, n_rows,
                                     iters, caps, PlanExecutor, run_config)


def _plan_variants_static(bench, axes, plan, inputs, n_rows, iters, caps,
                          PlanExecutor, run_config):
    results, totals, recs = {}, {}, []
    for optimized in (False, True):
        label = "on" if optimized else "off"
        ex = PlanExecutor(mode="capped", caps=dict(caps or {}),
                          optimize=optimized)
        res = ex.execute(plan, inputs)          # correctness + metrics run
        results[label] = res.compact().to_pydict()
        totals[label] = {
            "plan_rows_out": sum(m.rows_out for m in res.metrics.values()),
            # the per-op frame sum double-counts zero-copy frames
            # (inserted selects, capped-tier Filters), so also record the
            # bytes ENTERING width-sensitive operators — the traffic that
            # actually crosses a join/aggregate/sort materialization
            # boundary, which is what column pruning shrinks
            "plan_bytes_out": sum(m.bytes_out
                                  for m in res.metrics.values()),
            "plan_sink_bytes_in": _sink_bytes_in(res)}
        extra = dict(totals[label])
        rules = None
        if optimized:
            rules = res.optimizer["rules_fired"]
            extra["pruned_columns"] = res.optimizer["pruned_columns"]
            extra["fell_back"] = res.optimizer["fell_back"]
            if res.optimizer.get("fallback"):
                # the verifier's precise diagnostic (which rule, which
                # node, which invariant) — never a bare fell_back flag
                extra["fallback"] = res.optimizer["fallback"]
            # the win the pruned columns bought, in per-op metric terms
            extra["plan_bytes_saved"] = (totals["off"]["plan_bytes_out"]
                                         - totals["on"]["plan_bytes_out"])
            extra["plan_sink_bytes_saved"] = (
                totals["off"]["plan_sink_bytes_in"]
                - totals["on"]["plan_sink_bytes_in"])
            extra["plan_rows_saved"] = (totals["off"]["plan_rows_out"]
                                        - totals["on"]["plan_rows_out"])

        def prun():
            r = ex.execute(plan, inputs)
            return [c.data for c in r.table.columns], r.valid

        recs.append(run_config(
            bench, dict(axes), prun, (), n_rows=n_rows, iters=iters,
            jit=False, impl="plan_capped", optimizer=label,
            rules_fired=rules, kernels=kernels_of(res), **extra))
    assert results["on"] == results["off"], \
        f"{bench}: optimizer changed the result"
    return recs


# ---- kernel-registry (*_kernels) variants -----------------------------------

def kernels_of(res) -> dict:
    """op -> kernel name(s) an executed plan actually dispatched, from the
    per-op OperatorMetrics.kernel stamps (docs/kernels.md). Multiple nodes
    of one op kind may resolve differently (signature declines), so values
    are comma-joined sorted sets."""
    chosen = {}
    for m in res.metrics.values():
        if m.kernel:
            name, _, op = m.kernel.partition(":")
            # "hash_join/unique": a capped sort join says which tail ran
            chosen.setdefault(op.partition("/")[0], set()).add(name)
    return {op: ",".join(sorted(names))
            for op, names in sorted(chosen.items())}


def run_plan_kernels(bench: str, axes: dict, plan, inputs, *,
                     n_rows: int, iters: int, caps: dict = None):
    """Time the capped plan tier with the kernel registry LIVE and with
    every op forced to its universal fallback
    (SPARK_RAPIDS_TPU_KERNELS=op=fallback,...), assert EXACT result parity
    between the two, and stamp the per-op kernel choices / the "fallback"
    marker on the JSONL rows. These are the named configs behind
    ci/nightly.sh's kernel_bench stage and its capped-tier speedup gate
    (docs/kernels.md). Returns [registry-on record, forced-fallback
    record]."""
    import os
    from spark_rapids_tpu.plan import PlanExecutor
    from spark_rapids_tpu.ops.registry import REGISTRY
    from benchmarks.common import run_config

    fallback_spec = ",".join(
        f"{op}={next(k.name for k in REGISTRY.kernels(op) if k.fallback)}"
        for op in REGISTRY.ops())
    prev = os.environ.get("SPARK_RAPIDS_TPU_KERNELS")
    results, recs = {}, []
    try:
        for label, spec in (("on", prev), ("fallback", fallback_spec)):
            if spec is None:
                os.environ.pop("SPARK_RAPIDS_TPU_KERNELS", None)
            else:
                os.environ["SPARK_RAPIDS_TPU_KERNELS"] = spec
            ex = PlanExecutor(mode="capped", caps=dict(caps or {}))
            res = ex.execute(plan, inputs)      # correctness + stamps run
            results[label] = res.compact().to_pydict()
            kern = kernels_of(res) if label == "on" else "fallback"

            def prun():
                r = ex.execute(plan, inputs)
                return [c.data for c in r.table.columns], r.valid

            recs.append(run_config(
                bench, dict(axes), prun, (), n_rows=n_rows, iters=iters,
                jit=False, impl="plan_capped", kernels=kern))
    finally:
        if prev is None:
            os.environ.pop("SPARK_RAPIDS_TPU_KERNELS", None)
        else:
            os.environ["SPARK_RAPIDS_TPU_KERNELS"] = prev
    assert results["on"] == results["fallback"], \
        f"{bench}: kernel selection changed the result"
    return recs


# ---- distributed (*_dist) variants ------------------------------------------

def dist_mesh(n_devices: int = 4, axis: str = "data"):
    """A small simulated-CPU mesh for the `*_dist` plan variants, or None
    when the process doesn't have enough devices (benches print a skip
    note instead of failing — the driver must set
    XLA_FLAGS=--xla_force_host_platform_device_count before jax init)."""
    import jax
    from spark_rapids_tpu.parallel import make_mesh
    if len(jax.devices()) < n_devices:
        return None
    return make_mesh(n_devices, axis=axis)


def run_plan_distributed(bench: str, axes: dict, plan, inputs, *,
                         n_rows: int, iters: int, mesh,
                         mesh_axis: str = "data"):
    """Time the full-plan SPMD distributed tier (docs/distributed.md)
    against the single-device eager tier, asserting EXACT result parity,
    and record the distribution facts on the JSONL row: `n_devices`/
    `mesh_axis`/`exchange_bytes` plus the optimizer's exchange selection
    (planned kinds, elisions) and the observed gather count. Shared by
    the bench_nds_q5/q72 `*_dist` configs and ci/nightly.sh's
    distributed-parity stage. Returns (record, PlanResult)."""
    from spark_rapids_tpu.plan import PlanExecutor
    from benchmarks.common import run_config

    ref = PlanExecutor(mode="eager").execute(plan, inputs)
    ex = PlanExecutor(mesh=mesh, mesh_axis=mesh_axis)
    res = ex.execute(plan, inputs)          # correctness + metrics run
    assert not res.degraded, f"{bench}: distributed run degraded to CPU"
    assert res.table.to_pydict() == ref.table.to_pydict(), \
        f"{bench}: distributed result differs from the single-device tier"
    observed = {}
    for m in res.metrics.values():
        if m.exchange_how:
            observed[m.exchange_how] = observed.get(m.exchange_how, 0) + 1
    opt = res.optimizer or {}

    def prun():
        r = ex.execute(plan, inputs)
        return [c.data for c in r.table.columns]

    wire = sum(m.exchange_bytes for m in res.metrics.values())
    rec = run_config(
        bench, dict(axes), prun, (), n_rows=n_rows, iters=iters,
        jit=False, impl="plan_distributed", mesh_axis=mesh_axis,
        kernels=kernels_of(res),
        exchange_bytes=wire,
        exchange_bytes_wire=wire,
        exchange_bytes_logical=sum(m.exchange_bytes_logical
                                   for m in res.metrics.values()),
        exchange_overlap_ms=sum(m.exchange_overlap_ms
                                for m in res.metrics.values()),
        mesh_devices=int(mesh.shape[mesh_axis]),
        exchanges_planned=opt.get("exchanges", {}),
        exchanges_elided=opt.get("exchanges_elided", 0),
        exchanges_observed=observed,
        gathers=observed.get("gather", 0))
    return rec, res


# ---- input bindings ---------------------------------------------------------

def q3_inputs(sales, dates, items):
    return {"sales": sales, "dates": dates, "items": items}


def q5_inputs(tabs, dates):
    out = {"dates": dates}
    for name, (s, r) in tabs.items():
        out[f"{name}_sales"] = s
        out[f"{name}_returns"] = r
    return out


def q23_inputs(store, sides):
    return {"store": store, **sides}


def q72_inputs(cs, inv, items, hd, wh, dates):
    return {"cs": cs, "inv": inv, "items": items, "hd": hd, "wh": wh,
            "dates": dates}
