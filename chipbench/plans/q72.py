"""TPC-DS query 72 for the plan engine, in the form
`benchmarks/nds_plans.q72_plan` authors (copied): catalog_sales through
household_demographics, item, date_dim, inventory on the composite
(item, week) key, and warehouse, with the ship-delay and short-stock
residuals, counted by (item, warehouse, week).

One request is one shuffle partition's task: the sales of the 1,020 items
hashed to it and the inventory of the 510 of them that carry inventory
(dsdgen keeps inventory for every second item), 261 weekly snapshots in 15
warehouses each. Unlike the repo's stand-in data the inventory join fans
out: fifteen inventory rows match each (item, week).
"""
import numpy as np

from chipbench import tpcds

YEAR = 1999
BUY_POTENTIAL = 4                 # the code of '>10000' among the six values
ORDERED = ["cnt", "i_item_sk", "w_warehouse_sk", "d_week"]
RESULT_COLUMNS = ["i_item_sk", "w_warehouse_sk", "d_week", "cnt"]
# the drawn tables' columns, in the order the plan's scans declare them
COLUMNS = {"cs": ["item_sk", "hd_sk", "sold_date_sk", "ship_days", "qty"],
           "inv": ["inv_item_sk", "inv_week", "inv_wh_sk", "inv_qty"]}
PARTITIONS = 200


def plan():
    from spark_rapids_tpu.plan import PlanBuilder, col
    b = PlanBuilder()
    cs = b.scan("cs", schema=["item_sk", "hd_sk", "sold_date_sk",
                              "ship_days", "qty"])
    inv = b.scan("inv", schema=["inv_item_sk", "inv_week", "inv_wh_sk",
                                "inv_qty"])
    items = b.scan("items", schema=["i_item_sk", "i_brand"])
    hd = (b.scan("hd", schema=["hd_demo_sk", "hd_buy_potential"])
          .filter(col("hd_buy_potential") == BUY_POTENTIAL))
    wh = b.scan("wh", schema=["w_warehouse_sk"])
    dates = (b.scan("dates", schema=["d_date_sk", "d_week", "d_year"])
             .filter(col("d_year") == YEAR))
    j = (cs.join(hd, "hd_sk", "hd_demo_sk")
           .join(items, "item_sk", "i_item_sk")
           .join(dates, "sold_date_sk", "d_date_sk")
           .filter(col("ship_days") > 5)
           .join(inv, ["i_item_sk", "d_week"], ["inv_item_sk", "inv_week"])
           .filter(col("inv_qty") < col("qty"))
           .join(wh, "inv_wh_sk", "w_warehouse_sk"))
    return (j.aggregate(["i_item_sk", "w_warehouse_sk", "d_week"],
                        [("qty", "size", "cnt")])
             .sort(ORDERED, ascending=[False, True, True, True])
             .build())


def caps(batch: dict) -> dict:
    # benchmarks/bench_nds_q72.main(): the caps its plan-tier configs run under
    n = batch["sales_rows"]
    return dict(row_cap=max(n // 2, 2048), key_cap=max(n // 16, 1024))


def fact_rows(batch: dict) -> int:
    return int(batch["sales_rows"])


def _snapshot_weeks(sizes: dict, d: dict):
    """d_week_seq of the weekly inventory snapshots, from 1998-01-01 on."""
    first = int(np.flatnonzero(d["d_year"] == tpcds.SALES_YEARS[0])[0])
    idx = first + 7 * np.arange(sizes["inventory_weeks"])
    return d["d_week_seq"][idx]


def dimensions(sizes: dict) -> dict:
    rng = np.random.default_rng(sizes["dsdgen_seed"])
    d = tpcds.date_dim()
    n_items, n_hd = sizes["item_rows"], sizes["household_demographics_rows"]
    hd_sk = np.arange(1, n_hd + 1, dtype=np.int64)
    return {"dates": {"d_date_sk": d["d_date_sk"], "d_week": d["d_week_seq"],
                      "d_year": d["d_year"]},
            "items": {"i_item_sk": np.arange(1, n_items + 1, dtype=np.int64),
                      "i_brand": rng.integers(0, sizes["brand_ids"],
                                              n_items).astype(np.int64)},
            # the demographics table is a cross product of its attributes;
            # buy potential is one of six, cycling fastest
            "hd": {"hd_demo_sk": hd_sk, "hd_buy_potential": (hd_sk - 1) % 6},
            "wh": {"w_warehouse_sk": np.arange(
                1, sizes["warehouse_rows"] + 1, dtype=np.int64)}}


def batch_generator(sizes: dict, batch: dict):
    """-> jitted gen(keys_key, values_key) -> {"cs": ..., "inv": ...}: one
    partition's task. The task's items are one of every PARTITIONS
    consecutive item keys, drawn per request, half of them (the even keys)
    with inventory."""
    import jax
    import jax.numpy as jnp
    n = int(batch["sales_rows"])
    n_strata = sizes["item_rows"] // 2 // PARTITIONS          # 510
    n_weeks, n_wh = sizes["inventory_weeks"], sizes["warehouse_rows"]
    if n_strata * n_weeks * n_wh != batch["inventory_rows"]:
        raise ValueError("inventory_rows is not items x weeks x warehouses "
                         f"of one task: {n_strata}*{n_weeks}*{n_wh}")
    weeks = _snapshot_weeks(sizes, tpcds.date_dim())
    if not np.array_equal(weeks, weeks[0] + np.arange(n_weeks)):
        raise ValueError("weekly snapshots do not fall in consecutive weeks")
    week0 = int(weeks[0])
    n_hd = int(sizes["household_demographics_rows"])

    def item_of(stratum, stocked, a, b):
        # the task's item in each stratum of PARTITIONS consecutive keys of
        # one parity: arithmetic in (stratum, request), no table to gather
        off = (stratum * (2 * a + 1) + b) % PARTITIONS
        return (2 * (PARTITIONS * stratum + off) + 1 + stocked) \
            .astype(jnp.int64)

    @jax.jit
    def gen(keys_key, values_key):
        k = jax.random.split(keys_key, 8)
        v1, v2 = jax.random.split(values_key)
        ab = jax.random.randint(k[0], (4,), 0, PARTITIONS, dtype=jnp.int32)
        pick = jax.random.randint(k[2], (n,), 0, 2 * n_strata,
                                  dtype=jnp.int32)
        stocked = pick // n_strata          # even item keys carry inventory
        cs = {"item_sk": item_of(pick % n_strata, stocked,
                                 jnp.where(stocked == 1, ab[0], ab[2]),
                                 jnp.where(stocked == 1, ab[1], ab[3])),
              "hd_sk": tpcds.draw(k[3], n, 1, n_hd + 1),
              "sold_date_sk": tpcds.draw_sales_dates(k[4], n),
              "ship_days": tpcds.draw(k[5], n, 2, 91),
              "qty": tpcds.draw(v1, n, 1, 101)}
        m = n_strata * n_weeks * n_wh
        shift = jax.random.randint(k[6], (), 0, m, dtype=jnp.int32)
        r = (jnp.arange(m, dtype=jnp.int32) + shift) % m
        inv = {"inv_item_sk": item_of(r // (n_weeks * n_wh), 1, ab[0], ab[1]),
               "inv_week": (week0 + r // n_wh % n_weeks).astype(jnp.int64),
               "inv_wh_sk": (r % n_wh + 1).astype(jnp.int64),
               "inv_qty": tpcds.draw(v2, m, 0, 1001)}
        return {"cs": (cs, {}), "inv": (inv, {})}
    return gen


def least_bytes(batch: dict, sizes: dict, result_rows: int) -> int:
    """Each scanned column the optimizer keeps, read once, plus the result:
    five sales and four inventory columns, the dimensions' key and
    predicate columns (i_brand is pruned), four result columns."""
    return 8 * (batch["sales_rows"] * 5 + batch["inventory_rows"] * 4
                + sizes["item_rows"] + 2 * sizes["household_demographics_rows"]
                + sizes["warehouse_rows"] + 3 * tpcds.N_DATES
                + result_rows * 4)


def reference(tables: dict, lossy=None):
    """pandas over the same arrays (tests/test_nds_query.py's oracle with
    the inventory join on the composite key). `lossy`, used only by the
    control, is applied to each gathered key column before it is grouped."""
    import pandas as pd
    frame = lambda name: pd.DataFrame(
        {k: np.asarray(v) for k, v in tables[name][0].items()})
    hd, dates = frame("hd"), frame("dates")
    hd = hd[hd.hd_buy_potential == BUY_POTENTIAL]
    dates = dates[dates.d_year == YEAR]
    cs = frame("cs")
    cs = cs[cs.hd_sk.isin(hd.hd_demo_sk) & (cs.ship_days > 5)
            & cs.sold_date_sk.isin(dates.d_date_sk)]
    j = (cs.merge(hd, left_on="hd_sk", right_on="hd_demo_sk")
           .merge(frame("items"), left_on="item_sk", right_on="i_item_sk")
           .merge(dates, left_on="sold_date_sk", right_on="d_date_sk")
           .merge(frame("inv"), left_on=["i_item_sk", "d_week"],
                  right_on=["inv_item_sk", "inv_week"]))
    j = j[j.inv_qty < j.qty].merge(frame("wh"), left_on="inv_wh_sk",
                                   right_on="w_warehouse_sk")
    if lossy is not None:
        j = j.assign(i_item_sk=lossy(j.i_item_sk.values),
                     d_week=lossy(j.d_week.values))
    return (j.groupby(["i_item_sk", "w_warehouse_sk", "d_week"],
                      as_index=False).size()
             .rename(columns={"size": "cnt"})
             .sort_values(ORDERED, ascending=[False, True, True, True])
             [RESULT_COLUMNS].reset_index(drop=True))
