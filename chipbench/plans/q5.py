"""TPC-DS query 5 for the plan engine over a mesh: plan, born-sharded
generator, pandas reference, its controls, and the least bytes the plan
must move.

    per channel (store, catalog, web):
      sales UNION ALL returns (the other's measures as zeros)
        JOIN date_dim (d_date BETWEEN 2000-08-23 AND +14 days)
        JOIN the channel's dimension (store, catalog_page, web_site)
        -> sums by the dimension's BUSINESS id
      a web return takes its site from the sale it returns:
        web_returns LEFT OUTER JOIN web_sales ON (item, order number)
    -> ROLLUP (channel, id) -> ORDER BY channel, id -> LIMIT 100

The plan is Spark's physical plan for the template as the plugin receives
it (`EliminateOuterJoin` has made the returns join inner: the `web_site`
join above it rejects the null side); the reference runs the template as
written, in pandas, with the LEFT OUTER merge. Which joins broadcast and
which exchange is the optimizer's choice from the bound tables. Channel
names are codes in the template's alphabetical order (catalog 0, store 1,
web 2), the char(16) business ids int64 codes, `d_date` days since
1970-01-01, and the subtotal rows' NULLs -1, which sorts where Spark puts
nulls: first (`reduced` in the configuration file).
"""
import numpy as np

from chipbench import tpcds

SALES_DATE = np.datetime64("2000-08-23")
DATE_LO = int(SALES_DATE.astype("datetime64[D]").astype(np.int64))
DATE_HI = DATE_LO + 14                          # BETWEEN is inclusive
D_DATE0 = int(np.datetime64("1900-01-02").astype(np.int64))   # first row
CHANNELS = ("catalog", "store", "web")          # codes 0, 1, 2
ORDERED = ["channel", "id"]                     # the presentation sort
RESULT_COLUMNS = ["channel", "id", "sales", "returns", "profit"]
ITEMS_PER_ORDER = 8
ITEM_ROWS = 204000                              # item at SF100
AXIS = "data"
# the drawn tables' columns, in the order the plan's scans declare them
COLUMNS = {
    "store_sales": ["ss_sold_date_sk", "ss_store_sk", "ss_ext_sales_price",
                    "ss_net_profit"],
    "store_returns": ["sr_returned_date_sk", "sr_store_sk", "sr_return_amt",
                      "sr_net_loss"],
    "catalog_sales": ["cs_sold_date_sk", "cs_catalog_page_sk",
                      "cs_ext_sales_price", "cs_net_profit"],
    "catalog_returns": ["cr_returned_date_sk", "cr_catalog_page_sk",
                        "cr_return_amount", "cr_net_loss"],
    "web_sales": ["ws_sold_date_sk", "ws_web_site_sk", "ws_ext_sales_price",
                  "ws_net_profit", "ws_item_sk", "ws_order_number"],
    "web_returns": ["wr_returned_date_sk", "wr_item_sk", "wr_order_number",
                    "wr_return_amt", "wr_net_loss"],
}
# channel -> (sales table, returns table, dimension, its key, its id, the
# `sizes` entry with its rows); a fact table's columns are (date, dimension
# key, two measures) in COLUMNS' order, web_returns apart
SHAPE = {
    "store": ("store_sales", "store_returns", "store", "s_store_sk",
              "s_store_id", "store_rows"),
    "catalog": ("catalog_sales", "catalog_returns", "catalog_page",
                "cp_catalog_page_sk", "cp_catalog_page_id",
                "catalog_page_rows"),
    "web": ("web_sales", "web_returns", "web_site", "web_site_sk",
            "web_site_id", "web_site_rows"),
}


def plan():
    from spark_rapids_tpu.plan import PlanBuilder, col, lit
    b = PlanBuilder()
    dates = (b.scan("date_dim", schema=["d_date_sk", "d_date"])
             .filter((col("d_date") >= DATE_LO) & (col("d_date") <= DATE_HI)))
    scans = {name: b.scan(name, schema=cols)
             for name, cols in COLUMNS.items()}
    sums = [("sales_price", "sum", "sales"), ("profit", "sum", "profit"),
            ("return_amt", "sum", "returns"),
            ("net_loss", "sum", "profit_loss")]
    per = []
    for ci, name in enumerate(CHANNELS):
        s_name, r_name, d_name, d_sk, d_id, _ = SHAPE[name]
        s, r = scans[s_name], scans[r_name]
        s_cols = COLUMNS[s_name]
        r_cols = COLUMNS[r_name]
        r_from = [r_cols[1], r_cols[0], r_cols[2], r_cols[3]]
        if name == "web":
            r = r.join(s, left_on=["wr_item_sk", "wr_order_number"],
                       right_on=["ws_item_sk", "ws_order_number"])
            r_from = ["ws_web_site_sk", "wr_returned_date_sk",
                      "wr_return_amt", "wr_net_loss"]
        s_rows = s.project(
            [("sk", col(s_cols[1])), ("date_sk", col(s_cols[0])),
             ("sales_price", col(s_cols[2])), ("profit", col(s_cols[3])),
             ("return_amt", lit(0)), ("net_loss", lit(0))])
        r_rows = r.project(
            [("sk", col(r_from[0])), ("date_sk", col(r_from[1])),
             ("sales_price", lit(0)), ("profit", lit(0)),
             ("return_amt", col(r_from[2])), ("net_loss", col(r_from[3]))])
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
    measures = [(n, "sum", n) for n in RESULT_COLUMNS[2:]]
    kept = [(n, col(n)) for n in RESULT_COLUMNS[2:]]
    by_id = x.aggregate(["channel", "id"], measures)
    by_channel = (x.aggregate(["channel"], measures)
                  .project([("channel", col("channel")), ("id", lit(-1))]
                           + kept))
    total = (x.aggregate([], measures)
             .project([("channel", lit(-1)), ("id", lit(-1))] + kept))
    return (PlanBuilder.union([by_id, by_channel, total])
            .sort(["channel", "id"]).limit(100).build())


def fact_rows(batch: dict) -> int:
    return int(sum(batch[name + "_rows"] for name in COLUMNS))


def business_ids(n_sk: int):
    """Two surrogate keys to one business id for about half the rows (a
    history-keeping dimension), one to one for the rest."""
    pairs = n_sk // 4
    return np.concatenate([np.repeat(np.arange(pairs), 2),
                           np.arange(pairs, n_sk - pairs)]).astype(np.int64)


def dimensions(sizes: dict) -> dict:
    """Host arrays of the dimension tables: the real calendar, and surrogate
    keys 1..n with their business ids (dsdgen's dimensions are the same in
    every run of a scale factor: nothing here is drawn)."""
    d = tpcds.date_dim()
    out = {"date_dim": {"d_date_sk": d["d_date_sk"],
                        "d_date": D_DATE0 + np.arange(tpcds.N_DATES,
                                                      dtype=np.int64)}}
    for name, (_, _, d_name, d_sk, d_id, rows) in SHAPE.items():
        n = int(sizes[rows])
        out[d_name] = {d_sk: np.arange(1, n + 1, dtype=np.int64),
                       d_id: (np.arange(n, dtype=np.int64)
                              if name == "catalog" else business_ids(n))}
    return out


def sale_key(g):
    """(ws_item_sk, ws_order_number) of the web sale with global row number
    `g`: eight items to an order, each from its own eighth of `item`, so
    the pair is unique. A return computes its sale's key the same way."""
    order = g // ITEMS_PER_ORDER + 1
    band = ITEM_ROWS // ITEMS_PER_ORDER
    item = (order * 7919) % band + (g % ITEMS_PER_ORDER) * band + 1
    return item, order


def draw_shard(keys_key, values_key, shard, sizes: dict, batch: dict):
    """One shard's rows of the six fact tables (shard `shard` of
    `batch["chips"]`): a pure function of the keys and the shard's number,
    so a mesh draws it in place and one device can draw the same rows.
    Dates and join keys come from `keys_key`, money and the rotation of
    the rows from `values_key`."""
    import jax
    import jax.numpy as jnp
    chips = int(batch["chips"])
    kk = jax.random.fold_in(keys_key, shard)
    vk = jax.random.fold_in(values_key, shard)
    out = {}
    for ci, (name, (s_name, r_name, _, _, _, rows)) in \
            enumerate(SHAPE.items()):
        n_dim = int(sizes[rows])
        for ti, (table, lo) in enumerate(((s_name, -5000), (r_name, 1))):
            n = int(batch[table + "_rows"]) // chips
            k = jax.random.split(jax.random.fold_in(kk, 2 * ci + ti), 3)
            v = jax.random.split(jax.random.fold_in(vk, 2 * ci + ti), 3)
            shift = jax.random.randint(v[2], (), 0, n)
            roll = lambda a: jnp.roll(a, shift)      # noqa: E731
            cols = COLUMNS[table]
            date = roll(tpcds.draw_sales_dates(k[0], n))
            amount = tpcds.draw(v[0], n, 1, 101) * tpcds.draw(v[1], n, 1, 20001)
            second = tpcds.draw(jax.random.fold_in(v[1], 1), n, lo, 20001)
            if table == "web_returns":
                # a return's sale is one of its own ten rows of web_sales
                r = shard.astype(jnp.int64) * n \
                    + jnp.arange(n, dtype=jnp.int64)
                item, order = sale_key(r * 10 + tpcds.draw(k[1], n, 0, 10))
                out[table] = {cols[0]: date, cols[1]: roll(item),
                              cols[2]: roll(order), cols[3]: amount,
                              cols[4]: second}
                continue
            out[table] = {cols[0]: date,
                          cols[1]: roll(tpcds.draw(k[1], n, 1, n_dim + 1)),
                          cols[2]: amount, cols[3]: second}
            if table == "web_sales":
                g = shard.astype(jnp.int64) * n \
                    + jnp.arange(n, dtype=jnp.int64)
                item, order = sale_key(g)
                out[table].update({cols[4]: roll(item), cols[5]: roll(order)})
    return out


def batch_generator(sizes: dict, batch: dict):
    """-> jitted gen(keys_key, values_key) -> {table: (columns, {})}, every
    column a global array carrying `NamedSharding(mesh, P("data"))` over
    the first `batch["chips"]` devices: each chip draws its own quarter,
    and nothing is ever on one chip whole."""
    import jax
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    chips = int(batch["chips"])
    mesh = Mesh(np.asarray(jax.devices()[:chips]), (AXIS,))

    def local(keys_key, values_key):
        return draw_shard(keys_key, values_key, jax.lax.axis_index(AXIS),
                          sizes, batch)

    drawn = shard_map(local, mesh=mesh, in_specs=(P(), P()),
                      out_specs=P(AXIS))

    @jax.jit
    def gen(keys_key, values_key):
        return {name: (cols, {})
                for name, cols in drawn(keys_key, values_key).items()}
    return gen


def least_bytes(batch: dict, sizes: dict, result_rows: int) -> int:
    """Every scanned column the optimizer keeps, read once, plus the
    result: the fact tables' int64 columns, two columns of each
    dimension, five result columns."""
    facts = sum(int(batch[name + "_rows"]) * len(cols) * 8
                for name, cols in COLUMNS.items())
    dims = 2 * 8 * (tpcds.N_DATES + sum(int(sizes[s[5]])
                                        for s in SHAPE.values()))
    return facts + dims + result_rows * len(RESULT_COLUMNS) * 8


def reference(tables: dict, lossy=None, control=None):
    """pandas over the same host arrays, written from the template. Each
    fact table is cut to the window before anything is merged (the date
    join commutes with the rest; a 9.5 GB share must fit the chip host),
    except `web_sales` as the build side of the LEFT OUTER merge, which
    the window does not touch. `lossy` (chipbench.control's bfloat16) is
    applied to every payload the joins gather (the four measures and the
    business id) before it is aggregated. `control`, used only by
    `tests/test_correct_q5.py`: "int32" carries every measure through 32
    bits, "no_returns_join" gives a web return no site (the merge
    dropped)."""
    import pandas as pd

    def frame(name, columns=None, window=None):
        """A table's columns as a frame; `window` names the date column by
        which it is cut to the window's days before the frame is built."""
        cols = tables[name][0]
        keep = slice(None) if window is None else \
            np.isin(np.asarray(cols[window]), d.d_date_sk.values)
        return pd.DataFrame({c: np.asarray(cols[c])[keep]
                             for c in (columns or cols)})

    def narrow(a):
        a = np.asarray(a)
        return a.astype(np.int32).astype(np.int64) \
            if control == "int32" else a

    d = frame("date_dim")
    d = d[(d.d_date >= DATE_LO) & (d.d_date <= DATE_HI)]
    frames = []
    for ci, name in enumerate(CHANNELS):
        s_name, r_name, d_name, d_sk, d_id, _ = SHAPE[name]
        s_cols, r_cols = COLUMNS[s_name][:4], COLUMNS[r_name]
        s = frame(s_name, s_cols, window=s_cols[0])
        r = frame(r_name, window=r_cols[0])
        if name == "web":
            if control == "no_returns_join":
                r = r.assign(ws_web_site_sk=np.int64(-1))
            else:
                r = r.merge(frame("web_sales", ["ws_item_sk",
                                                "ws_order_number",
                                                "ws_web_site_sk"]),
                            how="left",
                            left_on=["wr_item_sk", "wr_order_number"],
                            right_on=["ws_item_sk", "ws_order_number"])
            r_cols = ["wr_returned_date_sk", "ws_web_site_sk",
                      "wr_return_amt", "wr_net_loss"]
        s = pd.DataFrame({"sk": s[s_cols[1]], "date_sk": s[s_cols[0]],
                          "sales_price": s[s_cols[2]],
                          "profit": s[s_cols[3]],
                          "return_amt": 0, "net_loss": 0})
        r = pd.DataFrame({"sk": r[r_cols[1]], "date_sk": r[r_cols[0]],
                          "sales_price": 0, "profit": 0,
                          "return_amt": r[r_cols[2]],
                          "net_loss": r[r_cols[3]]})
        u = (pd.concat([s, r])
             .merge(d, left_on="date_sk", right_on="d_date_sk")
             .merge(frame(d_name), left_on="sk", right_on=d_sk))
        if lossy is not None:
            u = u.assign(**{c: lossy(u[c].values) for c in
                            ("sales_price", "profit", "return_amt",
                             "net_loss", d_id)})
        g = u.groupby(d_id, as_index=False).agg(
            sales=("sales_price", "sum"), profit=("profit", "sum"),
            returns=("return_amt", "sum"), profit_loss=("net_loss", "sum"))
        frames.append(pd.DataFrame({
            "channel": ci, "id": g[d_id].astype(np.int64),
            "sales": narrow(g.sales), "returns": narrow(g.returns),
            "profit": narrow(g.profit - g.profit_loss)}))
    x = pd.concat(frames)
    by_id = x.groupby(["channel", "id"], as_index=False).sum()
    by_channel = (x.drop(columns="id").groupby("channel", as_index=False)
                  .sum().assign(id=-1))
    total = pd.DataFrame([{"channel": -1, "id": -1,
                           **x[RESULT_COLUMNS[2:]].sum().to_dict()}])
    out = (pd.concat([by_id, by_channel, total])[RESULT_COLUMNS]
           .astype(np.int64).sort_values(ORDERED).head(100)
           .reset_index(drop=True))
    for c in RESULT_COLUMNS[2:]:
        out[c] = narrow(out[c].values)
    return out
