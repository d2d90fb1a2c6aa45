"""TPC-DS query 3 for the plan engine: plan, generator, pandas reference,
its lower-precision control, and the least bytes the plan must move.

    store_sales JOIN date_dim (d_moy = 11) JOIN item (i_manufact_id = 128)
      -> sum(ss_ext_sales_price) by (d_year, i_brand_id)
      -> order by d_year, revenue desc

The plan is the form `benchmarks/nds_plans.q3_plan` authors (copied: the
benchmark imports nothing from `benchmarks/`), with the query template's
qualification parameters (MONTH 11, MANUFACT 128).
"""
import numpy as np

from chipbench import tpcds

MANUFACT = 128
MONTH = 11
ORDERED = ["d_year", "revenue"]          # the presentation sort's columns
RESULT_COLUMNS = ["d_year", "i_brand", "revenue"]
# the drawn tables' columns, in the order the plan's scans declare them
COLUMNS = {"sales": ["sold_date_sk", "item_sk", "price_cents"]}


def plan():
    from spark_rapids_tpu.plan import PlanBuilder, col
    b = PlanBuilder()
    sales = b.scan("sales", schema=["sold_date_sk", "item_sk", "price_cents"])
    dates = (b.scan("dates", schema=["d_date_sk", "d_year", "d_moy"])
             .filter(col("d_moy") == MONTH))
    items = (b.scan("items", schema=["i_item_sk", "i_brand", "i_manufact"])
             .filter(col("i_manufact") == MANUFACT))
    j = (sales.join(dates, left_on="sold_date_sk", right_on="d_date_sk")
              .join(items, left_on="item_sk", right_on="i_item_sk"))
    return (j.aggregate(["d_year", "i_brand"],
                        [("price_cents", "sum", "revenue")])
             .sort(["d_year", "revenue"], ascending=[True, False])
             .build())


def caps(batch: dict) -> dict:
    # benchmarks/bench_nds_q3.main(): the caps its plan-tier configs run under
    return dict(row_cap=max(batch["sales_rows"] // 8, 1024), key_cap=4096)


def fact_rows(batch: dict) -> int:
    return int(batch["sales_rows"])


def dimensions(sizes: dict) -> dict:
    """Host arrays of the dimension tables. dsdgen's dimensions are the same
    in every run of a scale factor, so they are drawn from the
    configuration's `dsdgen_seed`, not from --seed."""
    rng = np.random.default_rng(sizes["dsdgen_seed"])
    d = tpcds.date_dim()
    n_items = sizes["item_rows"]
    brand = rng.integers(0, sizes["brand_ids"], n_items)
    # i_brand_id's published form: category, class and brand digits
    brand_id = ((brand // 95 + 1) * 1_000_000 + (brand // 5 % 19 + 1) * 1000
                + brand % 5 + 1).astype(np.int64)
    return {"dates": {"d_date_sk": d["d_date_sk"], "d_year": d["d_year"],
                      "d_moy": d["d_moy"]},
            "items": {"i_item_sk": np.arange(1, n_items + 1, dtype=np.int64),
                      "i_brand": brand_id,
                      "i_manufact": rng.integers(
                          1, sizes["manufact_ids"] + 1,
                          n_items).astype(np.int64)}}


def batch_generator(sizes: dict, batch: dict):
    """-> jitted gen(keys_key, values_key) -> {"sales": (columns, validity)}.
    The join keys (and which of them are null) come from `keys_key`, prices
    and the rotation of the rows from `values_key`: a resident cell fixes the
    first and so sees the same set of rows, in another order and with other
    prices, on every seed."""
    import jax
    import jax.numpy as jnp
    n = int(batch["sales_rows"])
    n_items = int(sizes["item_rows"])
    null_below = int(float(sizes["sold_date_null_share"]) * 65536)

    @jax.jit
    def gen(keys_key, values_key):
        k1, k2, k3 = jax.random.split(keys_key, 3)
        v1, v2, v3 = jax.random.split(values_key, 3)
        shift = jax.random.randint(v3, (), 0, n)
        date_sk = tpcds.draw_sales_dates(k1, n)
        valid = jax.random.bits(k2, (n,), dtype=jnp.uint16) >= null_below
        item = tpcds.draw(k3, n, 1, n_items + 1)
        qty = tpcds.draw(v1, n, 1, 101)
        unit = tpcds.draw(v2, n, 1, 20001)
        roll = lambda a: jnp.roll(a, shift)
        return {"sales": ({"sold_date_sk": roll(date_sk),
                           "item_sk": roll(item),
                           "price_cents": qty * unit},
                          {"sold_date_sk": roll(valid)})}
    return gen


def least_bytes(batch: dict, sizes: dict, result_rows: int) -> int:
    """Each scanned column the optimizer keeps, read once, plus the result:
    three int64 fact columns and one validity byte per row, three columns
    of each dimension, three result columns."""
    return (batch["sales_rows"] * (3 * 8 + 1)
            + tpcds.N_DATES * 3 * 8 + sizes["item_rows"] * 3 * 8
            + result_rows * 3 * 8)


def _frames(tables: dict):
    import pandas as pd
    cols, validity = tables["sales"]
    ss = pd.DataFrame({k: np.asarray(v) for k, v in cols.items()})
    ss = ss[np.asarray(validity["sold_date_sk"])]     # a null key never matches
    ddf = pd.DataFrame(tables["dates"][0])
    idf = pd.DataFrame(tables["items"][0])
    return ss, ddf, idf


def reference(tables: dict, lossy=None):
    """pandas over the same arrays. `lossy`, used only by the control, is
    applied to each gathered payload column before it is aggregated."""
    ss, ddf, idf = _frames(tables)
    ddf = ddf[ddf.d_moy == MONTH]
    idf = idf[idf.i_manufact == MANUFACT]
    ss = ss[ss.sold_date_sk.isin(ddf.d_date_sk) & ss.item_sk.isin(idf.i_item_sk)]
    j = (ss.merge(ddf, left_on="sold_date_sk", right_on="d_date_sk")
           .merge(idf, left_on="item_sk", right_on="i_item_sk"))
    if lossy is not None:
        j = j.assign(price_cents=lossy(j.price_cents.values),
                     i_brand=lossy(j.i_brand.values))
    return (j.groupby(["d_year", "i_brand"], as_index=False)
             .agg(revenue=("price_cents", "sum"))
             .sort_values(["d_year", "revenue"], ascending=[True, False])
             [RESULT_COLUMNS].reset_index(drop=True))
