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
  (tests/test_plan_nds.py).

The benchmark's q3 and q72 at their measured sizes live in `chipbench/`
and import nothing from here.

Shapes worth noticing:
- q3/q72: star joins as chained HashJoin nodes; q72's inventory join uses
  the COMPOSITE (item, week) key, the physical plan a CBO picks and the
  shape that keeps the capped tier fan-out-free. Its reference joins on
  the item alone and filters the week afterwards: same rows, other route.
- q5: the query template whole: per-channel Union → join to the 15-day
  date window → join to the channel's dimension → sums by business id;
  web returns take their site from `web_sales` by the composite
  (item, order) key; ROLLUP (channel, id) as three aggregates over one
  shared Union, ORDER BY channel, id, LIMIT 100.
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


# ---- q5: the query template's full shape ------------------------------------
#
# TPC-DS query5.tpl: per channel, sales UNION ALL returns (each with the
# other's measures as zeros) joined to the 15-day date window and to the
# channel's dimension, summed by the dimension's BUSINESS id (several
# surrogate keys map to one); a web return takes its site from the sale it
# returns (`web_returns LEFT OUTER JOIN web_sales ON (item, order)`, which
# the `web_site` join above it turns into an inner join: the plan joins
# inner, the reference merges `how="left"` as written); then
# ROLLUP (channel, id), ORDER BY channel, id, LIMIT 100. Channel names are
# codes in the template's alphabetical order and the subtotal rows' NULLs
# are -1, which sorts where Spark puts nulls: first.

Q5_CHANNELS = ("catalog", "store", "web")       # codes 0, 1, 2
Q5_SALES_DATE = np.datetime64("2000-08-23")
Q5_DATE_LO = int(Q5_SALES_DATE.astype("datetime64[D]").astype(np.int64))
Q5_DATE_HI = Q5_DATE_LO + 14                    # BETWEEN is inclusive
Q5_JULIAN = 2440588                             # d_date_sk of 1970-01-01
# channel -> (sales table, its four columns: dimension key, date, price,
# profit; returns table, its four; dimension table, its key and its id)
Q5_SHAPE = {
    "store": ("store_sales", ["ss_store_sk", "ss_sold_date_sk",
                              "ss_ext_sales_price", "ss_net_profit"],
              "store_returns", ["sr_store_sk", "sr_returned_date_sk",
                                "sr_return_amt", "sr_net_loss"],
              "store", "s_store_sk", "s_store_id"),
    "catalog": ("catalog_sales", ["cs_catalog_page_sk", "cs_sold_date_sk",
                                  "cs_ext_sales_price", "cs_net_profit"],
                "catalog_returns", ["cr_catalog_page_sk",
                                    "cr_returned_date_sk",
                                    "cr_return_amount", "cr_net_loss"],
                "catalog_page", "cp_catalog_page_sk", "cp_catalog_page_id"),
    "web": ("web_sales", ["ws_web_site_sk", "ws_sold_date_sk",
                          "ws_ext_sales_price", "ws_net_profit"],
            "web_returns", ["ws_web_site_sk", "wr_returned_date_sk",
                            "wr_return_amt", "wr_net_loss"],
            "web_site", "web_site_sk", "web_site_id"),
}
Q5_ROWS = ["sk", "date_sk", "sales_price", "profit", "return_amt", "net_loss"]
Q5_RESULT = ["channel", "id", "sales", "returns", "profit"]


def q5_plan():
    b = PlanBuilder()
    dates = (b.scan("date_dim", schema=["d_date_sk", "d_date"])
             .filter((col("d_date") >= Q5_DATE_LO) &
                     (col("d_date") <= Q5_DATE_HI)))
    web_sales = b.scan("web_sales", schema=[
        "ws_sold_date_sk", "ws_web_site_sk", "ws_ext_sales_price",
        "ws_net_profit", "ws_item_sk", "ws_order_number"])
    web_returns = b.scan("web_returns", schema=[
        "wr_returned_date_sk", "wr_item_sk", "wr_order_number",
        "wr_return_amt", "wr_net_loss"])
    sums = [("sales_price", "sum", "sales"), ("profit", "sum", "profit"),
            ("return_amt", "sum", "returns"),
            ("net_loss", "sum", "profit_loss")]
    per = []
    for ci, name in enumerate(Q5_CHANNELS):
        s_name, s_cols, r_name, r_cols, d_name, d_sk, d_id = Q5_SHAPE[name]
        if name == "web":
            s, r = web_sales, web_returns.join(
                web_sales, left_on=["wr_item_sk", "wr_order_number"],
                right_on=["ws_item_sk", "ws_order_number"])
        else:
            s = b.scan(s_name, schema=[s_cols[1], s_cols[0], *s_cols[2:]])
            r = b.scan(r_name, schema=[r_cols[1], r_cols[0], *r_cols[2:]])
        s_rows = s.project(
            [(o, col(c)) for o, c in zip(Q5_ROWS[:4], s_cols)]
            + [("return_amt", lit(0)), ("net_loss", lit(0))])
        r_rows = r.project(
            [(o, col(c)) for o, c in zip(Q5_ROWS[:2], r_cols)]
            + [("sales_price", lit(0)), ("profit", lit(0))]
            + [(o, col(c)) for o, c in zip(Q5_ROWS[4:], r_cols[2:])])
        dim = b.scan(d_name, schema=[d_sk, d_id])
        g = (s_rows.union(r_rows)
             .join(dates, left_on="date_sk", right_on="d_date_sk")
             .join(dim, left_on="sk", right_on=d_sk)
             .aggregate([d_id], sums))
        per.append(g.project([("channel", lit(ci)), ("id", col(d_id)),
                              ("sales", col("sales")),
                              ("returns", col("returns")),
                              ("profit", col("profit")
                               - col("profit_loss"))]))
    x = PlanBuilder.union(per)
    measures = [(n, "sum", n) for n in Q5_RESULT[2:]]
    kept = [(n, col(n)) for n in Q5_RESULT[2:]]
    by_id = x.aggregate(["channel", "id"], measures)
    by_channel = (x.aggregate(["channel"], measures)
                  .project([("channel", col("channel")), ("id", lit(-1))]
                           + kept))
    total = (x.aggregate([], measures)
             .project([("channel", lit(-1)), ("id", lit(-1))] + kept))
    return (PlanBuilder.union([by_id, by_channel, total])
            .sort(["channel", "id"]).limit(100).build())


def q5_inputs(facts, dims):
    return {**facts, **dims}


def _q5_business_ids(n_sk: int):
    """Two surrogate keys to one business id for about half the rows (a
    history-keeping dimension), one to one for the rest."""
    pairs = n_sk // 4
    return np.concatenate([np.repeat(np.arange(pairs), 2),
                           np.arange(pairs, n_sk - pairs)]).astype(np.int64)


def q5_datagen(n_sales: int, seed=0, n_pages: int = 300, empty=()):
    """-> {table: {column: int64 array}}. Sale and return dates are drawn
    independently over seven months around the window, so most returns'
    sales lie outside it; every web return has its sale and
    (ws_item_sk, ws_order_number) is unique; the channels named in `empty`
    keep their rows but none of them falls in the window."""
    rng = np.random.default_rng(seed)
    day0 = int(np.datetime64("2000-06-01").astype(np.int64))
    n_days = 214

    def date_sk(n, name):
        d = day0 + rng.integers(0, n_days, n)
        if name in empty:
            d = np.where((d >= Q5_DATE_LO) & (d <= Q5_DATE_HI), day0, d)
        return (d + Q5_JULIAN).astype(np.int64)

    def money(lo, hi, n):
        return rng.integers(lo, hi, n).astype(np.int64)

    n_dim = {"store": 12, "catalog": n_pages, "web": 6}
    out = {}
    for ci, name in enumerate(("store", "catalog", "web")):
        s_name, s_cols, r_name, r_cols, d_name, d_sk, d_id = Q5_SHAPE[name]
        n_s = max(n_sales // (ci + 1), 8)       # store biggest, web smallest
        n_r = max(n_s // 10, 1)
        k = n_dim[name]
        out[d_name] = {d_sk: np.arange(1, k + 1, dtype=np.int64),
                       d_id: (np.arange(k, dtype=np.int64) if name == "catalog"
                              else _q5_business_ids(k))}
        out[s_name] = {s_cols[1]: date_sk(n_s, name),
                       s_cols[0]: money(1, k + 1, n_s),
                       s_cols[2]: money(1, 10_000, n_s),
                       s_cols[3]: money(-2_000, 5_000, n_s)}
        ret = {r_cols[1]: date_sk(n_r, name)}
        if name == "web":
            order = np.arange(n_s, dtype=np.int64) // 4
            item = (rng.integers(1, 50, n_s // 4 + 1)[order]
                    + np.arange(n_s, dtype=np.int64) % 4 * 50)
            out[s_name].update(ws_item_sk=item, ws_order_number=order)
            sold = rng.choice(n_s, n_r, replace=False)
            ret.update(wr_item_sk=item[sold], wr_order_number=order[sold])
        else:
            ret[r_cols[0]] = money(1, k + 1, n_r)
        ret.update({r_cols[2]: money(1, 8_000, n_r),
                    r_cols[3]: money(1, 3_000, n_r)})
        out[r_name] = ret
    d = np.arange(int(np.datetime64("1998-01-01").astype(np.int64)),
                  int(np.datetime64("2003-01-01").astype(np.int64)),
                  dtype=np.int64)
    out["date_dim"] = {"d_date_sk": d + Q5_JULIAN, "d_date": d}
    return out


def q5_tables(n_sales: int, seed=0, **kw):
    """-> (fact tables, dimension tables), each {name: Table}."""
    t = {name: _tab(cols) for name, cols in
         q5_datagen(n_sales, seed, **kw).items()}
    dims = ("date_dim", "store", "catalog_page", "web_site")
    return ({n: v for n, v in t.items() if n not in dims},
            {n: t[n] for n in dims})


def q5_reference(n_sales: int, seed=0, **kw):
    """-> DataFrame (channel, id, sales, returns, profit): the template in
    pandas, the first 100 rows by (channel, id)."""
    t = {name: pd.DataFrame(cols) for name, cols in
         q5_datagen(n_sales, seed, **kw).items()}
    d = t["date_dim"]
    d = d[(d.d_date >= Q5_DATE_LO) & (d.d_date <= Q5_DATE_HI)]
    frames = []
    for ci, name in enumerate(Q5_CHANNELS):
        s_name, s_cols, r_name, r_cols, d_name, d_sk, d_id = Q5_SHAPE[name]
        r = t[r_name]
        if name == "web":
            r = r.merge(t["web_sales"][["ws_item_sk", "ws_order_number",
                                        "ws_web_site_sk"]], how="left",
                        left_on=["wr_item_sk", "wr_order_number"],
                        right_on=["ws_item_sk", "ws_order_number"])
        s = t[s_name][s_cols].set_axis(Q5_ROWS[:4], axis=1) \
            .assign(return_amt=0, net_loss=0)
        r = r[r_cols].set_axis(Q5_ROWS[:2] + Q5_ROWS[4:], axis=1) \
            .assign(sales_price=0, profit=0)
        u = (pd.concat([s, r[Q5_ROWS]])
             .merge(d, left_on="date_sk", right_on="d_date_sk")
             .merge(t[d_name], left_on="sk", right_on=d_sk))
        g = u.groupby(d_id, as_index=False).agg(
            sales=("sales_price", "sum"), profit=("profit", "sum"),
            returns=("return_amt", "sum"), profit_loss=("net_loss", "sum"))
        frames.append(pd.DataFrame({
            "channel": ci, "id": g[d_id].astype(np.int64), "sales": g.sales,
            "returns": g.returns, "profit": g.profit - g.profit_loss}))
    x = pd.concat(frames)
    by_id = x.groupby(["channel", "id"], as_index=False).sum()
    by_channel = (x.drop(columns="id").groupby("channel", as_index=False)
                  .sum().assign(id=-1))
    total = pd.DataFrame([{"channel": -1, "id": -1,
                           **x[Q5_RESULT[2:]].sum().to_dict()}])
    return (pd.concat([by_id, by_channel, total])[Q5_RESULT]
            .astype(np.int64).sort_values(["channel", "id"]).head(100)
            .reset_index(drop=True))


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
