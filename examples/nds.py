"""The four NDS (TPC-DS-shaped) workloads the tests and documents use:
q3, q5, q23 and q72, each as

- `qN_plan()`: the physical plan (spark_rapids_tpu.plan), and
  `qN_inputs(...)`, its input bindings;
- `qN_datagen(n_sales, seed)`: small NumPy data (dimension tables of a few
  thousand rows), and `qN_tables(n_sales, seed)`, the same arrays as
  engine Tables;
- `qN_reference(n_sales, seed)`: the answer by pandas on the host over the
  same arrays. It shares no line with the engine: a plan run through
  `PlanExecutor`, in any tier, is held to it with `assert_rows_equal`
  (tests/test_plan_nds.py, chip_smoke.py).

The benchmark's q3 and q72 at their measured sizes live in `chipbench/`
and import nothing from here.

Shapes worth noticing:
- q3/q72: star joins as chained HashJoin nodes; q72's inventory join uses
  the COMPOSITE (item, week) key, the physical plan a CBO picks and the
  shape that keeps the capped tier fan-out-free. Its reference joins on
  the item alone and filters the week afterwards: same rows, other route.
- q5: per-channel Union → semi-join date window → rollup via a shared
  Union feeding two aggregates (channel subtotals + the const-key grand
  total).
- q23: the two expensive subqueries are SHARED DAG nodes — both sides
  semi-join the same `freq`/`best` objects, so the executor computes each
  once per run (the subquery-reuse that is the whole point of q23); the
  best-customer HAVING uses a scalar-aggregate expression
  (`> 0.95 * scalar_max(rev)`).
"""
from typing import List

import jax.numpy as jnp
import numpy as np
import pandas as pd

from spark_rapids_tpu import Column, Table
from spark_rapids_tpu.columnar.column import make_string_column
from spark_rapids_tpu.plan import PlanBuilder, col, lit, scalar_max


def _tab(d):
    """{name: int64 array} -> Table"""
    return Table.from_pydict({k: Column.from_numpy(v) for k, v in d.items()})


def assert_rows_equal(got, ref, ordered, what: str) -> None:
    """A result frame against a reference frame, row for row: the
    presentation-sort columns `ordered` agree position by position, and
    the full rows agree as multisets (rows tied on the whole sort key may
    legally swap). An empty reference proves nothing and fails."""
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


def strings_column_from_list(strs: List[bytes]):
    """Fast path: build a string Column from a list of byte strings via one
    concat + frombuffer, instead of per-row from_pylist."""
    joined = b"".join(strs)
    chars = np.frombuffer(joined, dtype=np.uint8)
    lens = np.fromiter((len(s) for s in strs), dtype=np.int32, count=len(strs))
    offsets = np.zeros(len(strs) + 1, dtype=np.int32)
    np.cumsum(lens, out=offsets[1:])
    return make_string_column(jnp.asarray(chars), jnp.asarray(offsets))


# ---- q3: star join → group by (year, brand) → order by ----------------------

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


def q3_inputs(sales, dates, items):
    return {"sales": sales, "dates": dates, "items": items}


def q3_datagen(n_sales: int, seed=0):
    rng = np.random.default_rng(seed)
    n_dates, n_items = 365 * 10, 20_000         # 10 years, 20k items
    date_sk = np.arange(n_dates, dtype=np.int64)
    d_year = 1998 + date_sk // 365
    d_moy = (date_sk % 365) // 31 + 1
    item_sk = np.arange(n_items, dtype=np.int64)
    i_brand = rng.integers(0, 1000, n_items).astype(np.int64)
    i_manufact = rng.integers(0, 100, n_items).astype(np.int64)
    ss = {
        "sold_date_sk": rng.integers(0, n_dates, n_sales).astype(np.int64),
        "item_sk": rng.integers(0, n_items, n_sales).astype(np.int64),
        "price_cents": rng.integers(1, 10_000, n_sales).astype(np.int64),
    }
    return (date_sk, d_year, d_moy, item_sk, i_brand, i_manufact, ss)


def q3_tables(n_sales: int, seed=0):
    """-> (sales, dates, items)"""
    (date_sk, d_year, d_moy, item_sk, i_brand, i_manufact, ss) = \
        q3_datagen(n_sales, seed)
    sales = _tab(ss)
    dates = _tab({"d_date_sk": date_sk, "d_year": d_year, "d_moy": d_moy})
    items = _tab({"i_item_sk": item_sk, "i_brand": i_brand,
                  "i_manufact": i_manufact})
    return sales, dates, items


def q3_reference(n_sales: int, seed=0):
    """-> DataFrame (d_year, i_brand, revenue), ordered by year, revenue
    descending; rows tied on both may stand in any order."""
    (date_sk, d_year, d_moy, item_sk, i_brand, i_manufact, ss) = \
        q3_datagen(n_sales, seed)
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


# ---- q5: multi-channel union → date window → rollup -------------------------

DATE_LO, DATE_HI = 700, 714          # the 14-day window of the real q5


def q5_plan():
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


def q5_inputs(tabs, dates):
    out = {"dates": dates}
    for name, (s, r) in tabs.items():
        out[f"{name}_sales"] = s
        out[f"{name}_returns"] = r
    return out


def q5_datagen(n_sales: int, seed=0):
    """Three channels; returns are ~10% of sales volume."""
    rng = np.random.default_rng(seed)
    n_dates = 365 * 5
    chans = {}
    for ci, name in enumerate(("store", "catalog", "web")):
        n_s = n_sales // (ci + 1)           # store biggest, web smallest
        n_r = max(n_s // 10, 1)
        chans[name] = {
            "s_sk": rng.integers(0, 1000, n_s).astype(np.int64),
            "s_date": rng.integers(0, n_dates, n_s).astype(np.int64),
            "s_price": rng.integers(1, 10_000, n_s).astype(np.int64),
            "s_profit": rng.integers(-2_000, 5_000, n_s).astype(np.int64),
            "r_sk": rng.integers(0, 1000, n_r).astype(np.int64),
            "r_date": rng.integers(0, n_dates, n_r).astype(np.int64),
            "r_amt": rng.integers(1, 8_000, n_r).astype(np.int64),
            "r_loss": rng.integers(1, 3_000, n_r).astype(np.int64),
        }
    date_sk = np.arange(n_dates, dtype=np.int64)
    return chans, date_sk


def q5_tables(n_sales: int, seed=0):
    """-> ({channel: (sales, returns)}, dates)"""
    chans, date_sk = q5_datagen(n_sales, seed)
    tabs = {}
    for name, c in chans.items():
        tabs[name] = (
            _tab({"sk": c["s_sk"], "date_sk": c["s_date"],
                  "sales_price": c["s_price"], "profit": c["s_profit"]}),
            _tab({"sk": c["r_sk"], "date_sk": c["r_date"],
                  "return_amt": c["r_amt"], "net_loss": c["r_loss"]}))
    return tabs, _tab({"d_date_sk": date_sk})


def q5_reference(n_sales: int, seed=0):
    """-> DataFrame (channel, sales, returns, profit, loss): the grand
    total as channel -1, then one row per channel."""
    chans, _ = q5_datagen(n_sales, seed)
    measures = dict(sales=("sales", "sum"), returns=("returns", "sum"),
                    profit=("profit", "sum"), loss=("loss", "sum"))
    frames = []
    for ci, c in enumerate(chans.values()):
        s = pd.DataFrame({"sk": c["s_sk"], "date_sk": c["s_date"],
                          "sales": c["s_price"], "profit": c["s_profit"],
                          "returns": 0, "loss": 0})
        r = pd.DataFrame({"sk": c["r_sk"], "date_sk": c["r_date"],
                          "sales": 0, "profit": 0, "returns": c["r_amt"],
                          "loss": c["r_loss"]})
        u = pd.concat([s, r])
        u = u[(u.date_sk >= DATE_LO) & (u.date_sk < DATE_HI)]
        g = u.groupby("sk", as_index=False).agg(**measures)
        g.insert(0, "channel", ci)
        frames.append(g)
    sub = pd.concat(frames).groupby("channel", as_index=False).agg(**measures)
    tot = sub.drop(columns="channel").sum()
    ref = pd.concat([sub, pd.DataFrame([{"channel": -1, **tot}])])
    return (ref.sort_values(["channel", "sales"], ascending=[True, False])
            [["channel", "sales", "returns", "profit", "loss"]])


# ---- q23: two shared HAVING subqueries, semi-joined on both sides -----------

FREQ_THRESHOLD = 4
BEST_FRACTION = 0.95


def q23_plan():
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


def q23_inputs(store, sides):
    return {"store": store, **sides}


def q23_datagen(n_sales: int, seed=0):
    rng = np.random.default_rng(seed)
    n_items, n_cust = 2_000, 5_000
    # zipf-ish skew so HAVING clauses select non-trivial subsets
    items = (rng.zipf(1.3, n_sales) % n_items).astype(np.int64)
    custs = (rng.zipf(1.2, n_sales) % n_cust).astype(np.int64)
    store = {"item_sk": items, "cust_sk": custs,
             "qty": rng.integers(1, 10, n_sales).astype(np.int64),
             "price": rng.integers(1, 1000, n_sales).astype(np.int64)}
    sides = {}
    for name, frac in (("catalog", 2), ("web", 4)):
        m = max(n_sales // frac, 16)
        sides[name] = {
            "item_sk": (rng.zipf(1.3, m) % n_items).astype(np.int64),
            "cust_sk": (rng.zipf(1.2, m) % n_cust).astype(np.int64),
            "qty": rng.integers(1, 10, m).astype(np.int64),
            "price": rng.integers(1, 1000, m).astype(np.int64)}
    return store, sides


def q23_tables(n_sales: int, seed=0):
    """-> (store, {side: table})"""
    store, sides = q23_datagen(n_sales, seed)
    return _tab(store), {k: _tab(v) for k, v in sides.items()}


def q23_reference(n_sales: int, seed=0):
    """-> DataFrame with the one row (total,). A total of 0 would mean the
    HAVING clauses selected nothing: callers assert it is positive."""
    store, sides = q23_datagen(n_sales, seed)
    sdf = pd.DataFrame(store)
    freq = sdf.groupby("item_sk").size()
    freq_items = set(freq[freq > FREQ_THRESHOLD].index)
    sdf["rev"] = sdf.qty * sdf.price
    by_cust = sdf.groupby("cust_sk").rev.sum()
    best = set(by_cust[by_cust > BEST_FRACTION * by_cust.max()].index)
    total = 0
    for side in sides.values():
        df = pd.DataFrame(side)
        df = df[df.item_sk.isin(freq_items) & df.cust_sk.isin(best)]
        total += int((df.qty * df.price).sum())
    return pd.DataFrame({"total": [total]})


# ---- q72: five chained joins, two residuals → group by → order by -----------

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


def q72_inputs(cs, inv, items, hd, wh, dates):
    return {"cs": cs, "inv": inv, "items": items, "hd": hd, "wh": wh,
            "dates": dates}


def q72_datagen(n_sales: int, seed=0):
    rng = np.random.default_rng(seed)
    n_items, n_wh, n_hd, n_dates = 500, 15, 20, 365 * 2
    cs = {"item_sk": rng.integers(0, n_items, n_sales).astype(np.int64),
          "hd_sk": rng.integers(0, n_hd, n_sales).astype(np.int64),
          "sold_date_sk": rng.integers(0, n_dates - 10, n_sales).astype(np.int64),
          "ship_days": rng.integers(0, 14, n_sales).astype(np.int64),
          "qty": rng.integers(1, 20, n_sales).astype(np.int64)}
    # inventory: one row per (item, week) with a quantity on hand
    n_weeks = n_dates // 7
    item_g, week_g = np.meshgrid(np.arange(n_items), np.arange(n_weeks))
    inv = {"inv_item_sk": item_g.ravel().astype(np.int64),
           "inv_week": week_g.ravel().astype(np.int64),
           "inv_wh_sk": rng.integers(0, n_wh, item_g.size).astype(np.int64),
           "inv_qty": rng.integers(0, 25, item_g.size).astype(np.int64)}
    items = {"i_item_sk": np.arange(n_items, dtype=np.int64),
             "i_brand": rng.integers(0, 50, n_items).astype(np.int64)}
    hd = {"hd_demo_sk": np.arange(n_hd, dtype=np.int64),
          "hd_buy_potential": rng.integers(0, 5, n_hd).astype(np.int64)}
    wh = {"w_warehouse_sk": np.arange(n_wh, dtype=np.int64)}
    dates = {"d_date_sk": np.arange(n_dates, dtype=np.int64),
             "d_week": (np.arange(n_dates) // 7).astype(np.int64),
             "d_year": (np.arange(n_dates) // 365).astype(np.int64)}
    return cs, inv, items, hd, wh, dates


def q72_tables(n_sales: int, seed=0):
    """-> (cs, inv, items, hd, wh, dates)"""
    return tuple(_tab(d) for d in q72_datagen(n_sales, seed))


def q72_reference(n_sales: int, seed=0):
    """-> DataFrame (i_item_sk, w_warehouse_sk, d_week, cnt), ordered by
    count descending then the three keys: a total order."""
    cs, inv, items, hd, wh, dates = q72_datagen(n_sales, seed)
    hddf, ddf = pd.DataFrame(hd), pd.DataFrame(dates)
    j = pd.DataFrame(cs).merge(hddf[hddf.hd_buy_potential == 3],
                               left_on="hd_sk", right_on="hd_demo_sk")
    j = j.merge(pd.DataFrame(items), left_on="item_sk", right_on="i_item_sk")
    j = j.merge(ddf[ddf.d_year == 1], left_on="sold_date_sk",
                right_on="d_date_sk")
    j = j[j.ship_days > 5]
    j = j.merge(pd.DataFrame(inv), left_on="i_item_sk",
                right_on="inv_item_sk")
    j = j[(j.inv_week == j.d_week) & (j.inv_qty < j.qty)]
    j = j.merge(pd.DataFrame(wh), left_on="inv_wh_sk",
                right_on="w_warehouse_sk")
    return (j.groupby(["i_item_sk", "w_warehouse_sk", "d_week"],
                      as_index=False).size()
             .rename(columns={"size": "cnt"})
             .sort_values(["cnt", "i_item_sk", "w_warehouse_sk", "d_week"],
                          ascending=[False, True, True, True])
             [["i_item_sk", "w_warehouse_sk", "d_week", "cnt"]])
