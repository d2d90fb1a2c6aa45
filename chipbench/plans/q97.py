"""TPC-DS query 97 (query97.tpl, DMS 1200) for the plan engine: plan,
generator, plain reference, and the bytes its full outer join moves.

    with ssci as (select ss_customer_sk customer_sk, ss_item_sk item_sk
                  from store_sales, date_dim
                  where ss_sold_date_sk = d_date_sk
                    and d_month_seq between 1200 and 1200 + 11
                  group by ss_customer_sk, ss_item_sk),
         csci as (select cs_bill_customer_sk customer_sk, cs_item_sk item_sk
                  from catalog_sales, date_dim
                  where cs_sold_date_sk = d_date_sk
                    and d_month_seq between 1200 and 1200 + 11
                  group by cs_bill_customer_sk, cs_item_sk)
    select sum(case when ssci.customer_sk is not null
                     and csci.customer_sk is null then 1 else 0 end) store_only,
           sum(case when ssci.customer_sk is null
                     and csci.customer_sk is not null then 1 else 0 end) catalog_only,
           sum(case when ssci.customer_sk is not null
                     and csci.customer_sk is not null then 1 else 0 end) store_and_catalog
    from ssci full outer join csci on (ssci.customer_sk = csci.customer_sk
                                       and ssci.item_sk = csci.item_sk)
    limit 100

The plan is the query as Spark plans it: the month filter on `date_dim`
(one scan, both joins read it), a broadcast join of each fact table with
those 366 days, a DISTINCT over (customer, item) a side (a `HashAggregate`
with no aggregate function; NULLs group together), the FULL OUTER JOIN on
the two-column key (a null in either column matches nothing, and the row
survives null-extended), a projection of three `CASE WHEN`s over
`IS [NOT] NULL`, a keyless sum, the limit. The join's null-extended rows
ARE the answer.

The reference shares no code with the engine and imports no jax: numpy and
pandas over the same arrays.
"""
import numpy as np

from chipbench import tpcds
from chipbench.plans.tpch_q1 import Frame

DMS = 1200                  # d_month_seq of January 2000: the template's
COLUMNS = {"store_sales": ["ss_sold_date_sk", "ss_customer_sk", "ss_item_sk"],
           "catalog_sales": ["cs_sold_date_sk", "cs_bill_customer_sk",
                             "cs_item_sk"],
           "date_dim": ["d_date_sk", "d_month_seq"]}
RESULT_COLUMNS = ["store_only", "catalog_only", "store_and_catalog"]
ORDERED = RESULT_COLUMNS    # one row: no presentation sort
# the two customer keys the projection reads, and a validity byte for each
JOIN_OUT_BYTES = 2 * 8 + 2
CONTROLS = ("inner", "left_outer", "null_equal", "null_as_value",
            "no_distinct")
# what the generator holds the configuration to, in this order
STATED = ("store_date_rows", "catalog_date_rows", "store_pairs",
          "catalog_pairs", "store_null_customer_groups",
          "catalog_null_customer_groups", "matched_pairs")

# what the last `reference` call counted (`full_join_bytes` and the
# `full_join_bw_share` reader read it after the check)
COUNTS = {}


def plan():
    from spark_rapids_tpu.plan import (PlanBuilder, col, is_not_null,
                                       is_null, when)
    b = PlanBuilder()
    days = (b.scan("date_dim", schema=COLUMNS["date_dim"])
            .filter((col("d_month_seq") >= DMS)
                    & (col("d_month_seq") <= DMS + 11)))
    ssci = (b.scan("store_sales", schema=COLUMNS["store_sales"])
            .join(days, left_on="ss_sold_date_sk", right_on="d_date_sk")
            .distinct(["ss_customer_sk", "ss_item_sk"]))
    csci = (b.scan("catalog_sales", schema=COLUMNS["catalog_sales"])
            .join(days, left_on="cs_sold_date_sk", right_on="d_date_sk")
            .distinct(["cs_bill_customer_sk", "cs_item_sk"]))
    store, catalog = col("ss_customer_sk"), col("cs_bill_customer_sk")
    built = (ssci.join(csci, left_on=["ss_customer_sk", "ss_item_sk"],
                       right_on=["cs_bill_customer_sk", "cs_item_sk"],
                       how="full_outer")
             .project({
                 "store_only": when(
                     is_not_null(store) & is_null(catalog), 1, 0),
                 "catalog_only": when(
                     is_null(store) & is_not_null(catalog), 1, 0),
                 "store_and_catalog": when(
                     is_not_null(store) & is_not_null(catalog), 1, 0)})
             .aggregate([], [(c, "sum", c) for c in RESULT_COLUMNS])
             .limit(100)
             .build())
    # the engine's own verifier before a table is drawn (an engine whose
    # plans cannot say `full_outer` or `is_null` has failed already)
    from spark_rapids_tpu import dtypes
    from spark_rapids_tpu.analysis import verifier
    verifier.verify(built, input_dtypes={
        t: {c: dtypes.INT64 for c in cols} for t, cols in COLUMNS.items()},
    ).raise_if_failed("q97")
    return built


def caps(batch: dict) -> dict:
    # the capped tier (tier-1 tests; the cell runs eager): a date join puts
    # out a sale once at most, the full join's left part a store pair once
    # (the pairs are distinct); a group a sale at most
    rows = int(batch["store_rows"])
    return dict(row_cap=rows, key_cap=rows)


def fact_rows(batch: dict) -> int:
    return int(batch["store_rows"]) + int(batch["catalog_rows"])


def dimensions(sizes: dict) -> dict:
    """`date_dim` whole, with the real calendar: the surrogate key and
    d_month_seq (months since January 1900: 1200 is January 2000)."""
    d = tpcds.date_dim()
    return {"date_dim": {
        "d_date_sk": d["d_date_sk"],
        "d_month_seq": (d["d_year"] - 1900) * 12 + d["d_moy"] - 1}}


def _year_days():
    """(first, last) d_date_sk of the template's twelve months."""
    d = dimensions({})["date_dim"]
    hit = d["d_date_sk"][(d["d_month_seq"] >= DMS)
                         & (d["d_month_seq"] <= DMS + 11)]
    return int(hit[0]), int(hit[-1])


def batch_generator(sizes: dict, batch: dict):
    """-> gen(keys_key, values_key) -> {"store_sales": .., "catalog_sales":
    ..}, each (columns, validity), drawn on the device. Which customer (by
    rank among this chip's) and which item (by rank) each row holds, its
    date, and which of its three keys are null, is one fixed draw of the
    configuration's `dsdgen_seed` through threefry, whose bits are the same
    on every backend: the rows that pass the date joins, the distinct pairs,
    the null-customer groups and the matched pairs do not change with
    --seed, and the batch can state them (the harness's `keys_key` is that
    seed's too; it is not read). From `values_key`: a relabelling of the
    customers and one of the items (the same for both tables), and the
    rotation of each table's rows."""
    import jax
    import jax.numpy as jnp
    n_ss, n_cs = int(batch["store_rows"]), int(batch["catalog_rows"])
    n_cust, n_item = int(sizes["rank_customers"]), int(sizes["items"])
    ranks, rank = int(sizes["ranks"]), int(sizes["rank"])
    null_below = int(float(sizes["null_share"]) * 65536)
    seed = int(sizes["dsdgen_seed"])
    stated = tuple(int(batch[k]) for k in STATED)
    first, last = _year_days()

    def fixed(i):
        return jax.random.fold_in(
            jax.random.key(seed, impl="threefry2x32"), i)

    def side(at: int, n: int):
        """One table's fixed draw: date, customer rank, item rank (int32)
        and the three validity masks."""
        valid = [jax.random.bits(fixed(at + 3 + j), (n,), dtype=jnp.uint16)
                 >= null_below for j in range(3)]
        return (tpcds.draw_sales_dates(fixed(at), n),
                jax.random.randint(fixed(at + 1), (n,), 0, n_cust,
                                   dtype=jnp.int32),
                jax.random.randint(fixed(at + 2), (n,), 0, n_item,
                                   dtype=jnp.int32), valid)

    def pair_keys(date, cust, item, valid, flag: int):
        """A row's (customer, item) pair as one sortable int64, a null as
        rank -1, with the table's `flag` as the lowest bit; a row the date
        join drops sorts past every pair."""
        passes = valid[0] & (date >= first) & (date <= last)
        c = jnp.where(valid[1], cust, -1).astype(jnp.int64) + 1
        i = jnp.where(valid[2], item, -1).astype(jnp.int64) + 1
        key = (c * (n_item + 1) + i) * 2 + flag
        return jnp.where(passes, key, jnp.int64(2 ** 62)), passes

    @jax.jit
    def draw(keys_key, values_key):
        ss, cs = side(0, n_ss), side(8, n_cs)
        # what the draw fixes, counted here so that `gen` can hold the
        # configuration to it: one sort of both tables' pairs
        ks, ps = pair_keys(*ss, 0)
        kc, pc = pair_keys(*cs, 1)
        srt = jnp.sort(jnp.concatenate([ks, kc]))
        live = srt < 2 ** 62
        prev = jnp.concatenate([jnp.full((1,), -1, jnp.int64), srt[:-1]])
        new = live & (srt != prev)              # a pair's first row, a side
        pair, catalog = srt >> 1, (srt & 1) == 1
        null_cust = pair // (n_item + 1) == 0
        null_key = null_cust | (pair % (n_item + 1) == 0)
        both = new & catalog & (prev == srt - 1) & ~null_key
        count = lambda m: jnp.sum(m, dtype=jnp.int64)
        drawn = (count(ps), count(pc), count(new & ~catalog),
                 count(new & catalog), count(new & ~catalog & null_cust),
                 count(new & catalog & null_cust), count(both))
        v1, v2, v3, v4 = jax.random.split(values_key, 4)
        as_cust = jax.random.permutation(v1, n_cust).astype(jnp.int32)
        as_item = jax.random.permutation(v2, n_item).astype(jnp.int32)
        wide = lambda a: a.astype(jnp.int64)

        def table(names, drawn_side, turn):
            date, cust, item, valid = drawn_side
            roll = lambda a: jnp.roll(a, turn)
            cols = (date, wide(as_cust[cust]) * ranks + rank + 1,
                    wide(as_item[item]) + 1)
            return ({n: roll(c) for n, c in zip(names, cols)},
                    {n: roll(v) for n, v in zip(names, valid)})
        return {"store_sales": table(
                    COLUMNS["store_sales"], ss,
                    jax.random.randint(v3, (), 0, n_ss, dtype=jnp.int32)),
                "catalog_sales": table(
                    COLUMNS["catalog_sales"], cs,
                    jax.random.randint(v4, (), 0, n_cs, dtype=jnp.int32))
                }, drawn

    def gen(keys_key, values_key):
        tables, drawn = draw(keys_key, values_key)
        drawn = tuple(int(x) for x in jax.device_get(drawn))
        if drawn != stated:
            raise ValueError(
                f"the draw holds {dict(zip(STATED, drawn))} for {n_ss} "
                f"store_sales and {n_cs} catalog_sales rows of {n_cust} "
                f"customers and {n_item} items; the configuration states "
                f"{dict(zip(STATED, stated))}")
        return tables
    gen.lower = draw.lower
    return gen


def least_bytes(batch: dict, sizes: dict, result_rows: int) -> int:
    """Each scanned column read once with its validity byte (three of each
    fact table, two of date_dim), plus the result: three int64 sums."""
    return (fact_rows(batch) * 3 * (8 + 1) + tpcds.N_DATES * 2 * 8
            + result_rows * 3 * 8)


def full_join_bytes(batch: dict, sizes: dict, counts: dict = None) -> int:
    """What the plan's full outer join must move in one request: both
    sides' two key columns read once, and every output row's two customer
    keys and their two validity bytes (what the projection reads) written
    once. Rows are what the reference counted (`COUNTS`, set by its last
    call); before any call, what the batch states."""
    counts = counts or COUNTS or {
        "left_rows": batch["store_pairs"],
        "right_rows": batch["catalog_pairs"],
        "matched": batch["matched_pairs"],
        "unmatched": batch["store_pairs"] - batch["matched_pairs"],
        "unmatched_right": batch["catalog_pairs"] - batch["matched_pairs"]}
    return ((counts["left_rows"] + counts["right_rows"]) * 2 * 8
            + (counts["matched"] + counts["unmatched"]
               + counts["unmatched_right"]) * JOIN_OUT_BYTES)


# ---- the plain reference -------------------------------------------------------

def _pairs(table, days, names, lossy, distinct: bool):
    """One side's (customer, item) rows after its date join, as a pandas
    frame of two nullable Int64 columns, DISTINCT unless the control says
    rows; and how many rows the date join passed."""
    import pandas as pd
    cols, validity = table
    date, cust, item = (np.asarray(cols[n]).astype(np.int64) for n in names)
    ok_d, ok_c, ok_i = (np.asarray(validity[n]).astype(bool)
                        if n in validity else np.ones(date.size, bool)
                        for n in names)
    keep = ok_d & np.isin(date, days)           # a null date matches no day
    cust, item, ok_c, ok_i = cust[keep], item[keep], ok_c[keep], ok_i[keep]
    if lossy is not None:
        cust, item = lossy(cust), lossy(item)
    frame = pd.DataFrame({
        "customer_sk": pd.array(cust, dtype="Int64"),
        "item_sk": pd.array(item, dtype="Int64")})
    frame.loc[~ok_c, "customer_sk"] = pd.NA
    frame.loc[~ok_i, "item_sk"] = pd.NA
    if distinct:
        # GROUP BY treats NULLs as equal: so does drop_duplicates
        frame = frame.drop_duplicates(ignore_index=True)
    return frame, int(keep.sum())


def reference(tables: dict, lossy=None, control: str = ""):
    """-> the result as a `Frame`: one row of `store_only`, `catalog_only`,
    `store_and_catalog`.

    pandas' `merge` matches NaN / NA keys with each other, which SQL's `=`
    does not: the reference takes the rows with a null in either key column
    out of both sides before the merge and puts them back as unmatched rows
    of their side. `lossy` (chipbench.control's bfloat16) is applied to the
    join keys of both sides before anything is grouped or matched.
    `control` names this cell's own wrong forms
    (tests/test_correct_q97.py): "inner" and "left_outer" join that way
    (`catalog_only`, and under "inner" `store_only` too, are 0);
    "null_equal" leaves the null keys in the merge, where they match each
    other; "null_as_value" evaluates the `CASE WHEN`s over the data under
    a null, as an engine without validity does (no key `is null`: every
    output row counts as both); "no_distinct" joins the rows, not the
    pairs."""
    assert control in ("",) + CONTROLS, control
    dd = tables["date_dim"][0]
    seq = np.asarray(dd["d_month_seq"])
    days = np.asarray(dd["d_date_sk"])[(seq >= DMS) & (seq <= DMS + 11)]
    distinct = control != "no_distinct"
    ssci, store_date_rows = _pairs(tables["store_sales"], days,
                                   COLUMNS["store_sales"], lossy, distinct)
    csci, catalog_date_rows = _pairs(tables["catalog_sales"], days,
                                     COLUMNS["catalog_sales"], lossy,
                                     distinct)
    sides = []
    for frame in (ssci, csci):
        null_key = (frame.customer_sk.isna() | frame.item_sk.isna()).values
        if control == "null_equal":
            null_key = np.zeros(len(frame), bool)
        sides.append((frame[~null_key], frame[null_key]))
    (left, left_null), (right, right_null) = sides
    merged = left.assign(in_store=True).merge(
        right.assign(in_catalog=True), on=["customer_sk", "item_sk"],
        how="outer")
    in_store = merged.in_store.notna().values
    in_catalog = merged.in_catalog.notna().values
    has_customer = merged.customer_sk.notna().values
    matched = int((in_store & in_catalog).sum())
    # unmatched rows of a side: the merge's, and the null-key rows put back
    store_rows = np.concatenate([has_customer[in_store & ~in_catalog],
                                 left_null.customer_sk.notna().values])
    catalog_rows = np.concatenate([has_customer[~in_store & in_catalog],
                                   right_null.customer_sk.notna().values])
    both_rows = has_customer[in_store & in_catalog]
    COUNTS.clear()
    COUNTS.update(
        store_date_rows=store_date_rows, catalog_date_rows=catalog_date_rows,
        left_rows=len(ssci), right_rows=len(csci),
        store_null_customer_groups=int(ssci.customer_sk.isna().sum()),
        catalog_null_customer_groups=int(csci.customer_sk.isna().sum()),
        matched=matched, unmatched=int(store_rows.size),
        unmatched_right=int(catalog_rows.size))
    if control == "null_as_value":
        # no key reads as null: both `is not null` hold on every row
        sums = (0, 0, matched + store_rows.size + catalog_rows.size)
    else:
        # `ssci.customer_sk is not null and csci.customer_sk is null`: an
        # unmatched store row whose own customer is not null, and so on; a
        # NULL customer's row counts in none of the three
        sums = (int(store_rows.sum()), int(catalog_rows.sum()),
                int(both_rows.sum()))
        if control in ("inner", "left_outer"):
            sums = (0 if control == "inner" else sums[0], 0, sums[2])
    return Frame({c: np.asarray([v], np.int64)
                  for c, v in zip(RESULT_COLUMNS, sums)})
