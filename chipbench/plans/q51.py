"""TPC-DS query 51 (query51.tpl, DMS 1200) for the plan engine: plan,
generator, plain reference, and the bytes its windows move.

    with web_v1 as (
      select ws_item_sk item_sk, d_date,
             sum(sum(ws_sales_price)) over (partition by ws_item_sk order by d_date
               rows between unbounded preceding and current row) cume_sales
      from web_sales, date_dim
      where ws_sold_date_sk = d_date_sk and d_month_seq between 1200 and 1200 + 11
        and ws_item_sk is not null
      group by ws_item_sk, d_date),
    store_v1 as (... the same over store_sales ...)
    select * from (
      select item_sk, d_date, web_sales, store_sales,
             max(web_sales) over (partition by item_sk order by d_date
               rows between unbounded preceding and current row) web_cumulative,
             max(store_sales) over (partition by item_sk order by d_date
               rows between unbounded preceding and current row) store_cumulative
      from (select case when web.item_sk is not null then web.item_sk
                        else store.item_sk end item_sk,
                   case when web.d_date is not null then web.d_date
                        else store.d_date end d_date,
                   web.cume_sales web_sales, store.cume_sales store_sales
            from web_v1 web full outer join store_v1 store
                 on (web.item_sk = store.item_sk and web.d_date = store.d_date)) x) y
    where web_cumulative > store_cumulative
    order by item_sk, d_date
    limit 100

The plan is the query as Spark plans it: the month filter on `date_dim`
(one scan, both joins read it), a broadcast join of each fact table with
those 366 days that brings `d_date`, a keyed sum into (item, date) groups a
channel, a running sum over each item's days (a `Window`), the FULL OUTER
JOIN on (item, date), two `CASE WHEN`s, a running max of each channel's
total carried over the days the other channel alone sold (a second
`Window`: a NULL is skipped, and the result is NULL until the item's first
sale in that channel), the comparison that keeps a row only where both
sides have sold (NULL is not TRUE), the sort and the limit. Last, a
presentation `Project`: the benchmark's `check.to_host` refuses a null in
a live result row, so `web_sales` / `store_sales` come out as
`coalesce(x, 0)` beside `web_sales_null` / `store_sales_null` (0 / 1).

The reference shares no code with the engine and imports no jax: numpy and
pandas over the same arrays.
"""
import numpy as np

from chipbench import tpcds
from chipbench.plans.tpch_q1 import Frame

DMS = 1200                  # d_month_seq of January 2000: the template's
COLUMNS = {"store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_sales_price"],
           "web_sales": ["ws_sold_date_sk", "ws_item_sk", "ws_sales_price"],
           "date_dim": ["d_date_sk", "d_date", "d_month_seq"]}
RESULT_COLUMNS = ["item_sk", "d_date", "web_sales", "web_sales_null",
                  "store_sales", "store_sales_null", "web_cumulative",
                  "store_cumulative"]
ORDERED = ["item_sk", "d_date"]     # the query's ORDER BY; (item, date) is
#                                     unique, so the order is total
CONTROLS = ("no_partition", "null_as_zero", "no_carry", "restart_at_null",
            "inner")
# what the generator holds the configuration to, in this order
STATED = ("store_date_rows", "web_date_rows", "store_groups", "web_groups",
          "matched_pairs", "join_rows")
MAX_PRICE = 20000           # cents
_CELL = 8 + 1               # an int64 and its validity byte

# what the last `reference` call counted (`window_bytes` and the
# `window_bw_share` reader read it after the check)
COUNTS = {}


def plan():
    from spark_rapids_tpu.plan import (PlanBuilder, coalesce, col,
                                       is_not_null, is_null, when)
    b = PlanBuilder()
    days = (b.scan("date_dim", schema=COLUMNS["date_dim"])
            .filter((col("d_month_seq") >= DMS)
                    & (col("d_month_seq") <= DMS + 11)))

    def channel(table: str, prefix: str, out: str):
        """One CTE: item, day and the item's running total to that day."""
        date, item, price = COLUMNS[table]
        return (b.scan(table, schema=COLUMNS[table])
                .filter(is_not_null(col(item)))
                .join(days, left_on=date, right_on="d_date_sk")
                .aggregate([item, "d_date"], [(price, "sum", prefix + "_sum")])
                .window([(out + "_sales", "sum", prefix + "_sum")],
                        partition_by=[item], order_by=["d_date"])
                .project({out + "_item_sk": col(item),
                          out + "_date": col("d_date"),
                          out + "_sales": col(out + "_sales")}))

    web = channel("web_sales", "ws", "web")
    store = channel("store_sales", "ss", "store")
    built = (web.join(store, left_on=["web_item_sk", "web_date"],
                      right_on=["store_item_sk", "store_date"],
                      how="full_outer")
             .project({
                 "item_sk": when(is_not_null(col("web_item_sk")),
                                 col("web_item_sk"), col("store_item_sk")),
                 "d_date": when(is_not_null(col("web_date")),
                                col("web_date"), col("store_date")),
                 "web_sales": col("web_sales"),
                 "store_sales": col("store_sales")})
             .window([("web_cumulative", "max", "web_sales"),
                      ("store_cumulative", "max", "store_sales")],
                     partition_by=["item_sk"], order_by=["d_date"])
             .filter(col("web_cumulative") > col("store_cumulative"))
             .sort(["item_sk", "d_date"])
             .limit(100)
             .project({
                 "item_sk": col("item_sk"), "d_date": col("d_date"),
                 "web_sales": coalesce(col("web_sales"), 0),
                 "web_sales_null": when(is_null(col("web_sales")), 1, 0),
                 "store_sales": coalesce(col("store_sales"), 0),
                 "store_sales_null": when(is_null(col("store_sales")), 1, 0),
                 "web_cumulative": col("web_cumulative"),
                 "store_cumulative": col("store_cumulative")})
             .build())
    # the engine's own verifier before a table is drawn (an engine whose
    # plans cannot say `window` has failed already)
    from spark_rapids_tpu import dtypes
    from spark_rapids_tpu.analysis import verifier
    verifier.verify(built, input_dtypes={
        t: {c: dtypes.INT64 for c in cols} for t, cols in COLUMNS.items()},
    ).raise_if_failed("q51")
    return built


def caps(batch: dict) -> dict:
    # the capped tier (tier-1 tests; the cell runs eager): a date join puts
    # out a sale once at most, the full join's left part a web group once
    # (the groups are distinct); a group a sale at most
    rows = int(batch["store_rows"])
    return dict(row_cap=rows, key_cap=rows)


def fact_rows(batch: dict) -> int:
    return int(batch["store_rows"]) + int(batch["web_rows"])


def dimensions(sizes: dict) -> dict:
    """`date_dim` whole, with the real calendar: the surrogate key, d_date
    as int64 days since 1970-01-01 and d_month_seq (months since January
    1900: 1200 is January 2000)."""
    d = tpcds.date_dim()
    first = (np.datetime64("1900-01-02")
             - np.datetime64("1970-01-01")).astype(np.int64)
    return {"date_dim": {
        "d_date_sk": d["d_date_sk"],
        "d_date": first + np.arange(tpcds.N_DATES, dtype=np.int64),
        "d_month_seq": (d["d_year"] - 1900) * 12 + d["d_moy"] - 1}}


def _year_days():
    """(first, last) d_date_sk of the template's twelve months."""
    d = dimensions({})["date_dim"]
    hit = d["d_date_sk"][(d["d_month_seq"] >= DMS)
                         & (d["d_month_seq"] <= DMS + 11)]
    return int(hit[0]), int(hit[-1])


def batch_generator(sizes: dict, batch: dict):
    """-> gen(keys_key, values_key) -> {"store_sales": .., "web_sales":
    ..}, each (columns, validity), drawn on the device. Which item (by rank
    among this chip's) a row holds, its date, its price, and which of its
    date and price are null, is one fixed draw of the configuration's
    `dsdgen_seed` through threefry, whose bits are the same on every
    backend: the rows that pass the date joins, the (item, date) groups of
    each channel, the pairs in both, and (the prices being fixed) every
    sum, running total and comparison after them do not change with --seed,
    and the batch can state them (the harness's `keys_key` is that seed's
    too; it is not read). From `values_key`: a relabelling of the items
    (the same for both tables) and the rotation of each table's rows. An
    item key is never null, as in dsdgen (a part of the primary key); its
    validity mask is there and all true."""
    import jax
    import jax.numpy as jnp
    n_ss, n_ws = int(batch["store_rows"]), int(batch["web_rows"])
    n_item = int(sizes["rank_items"])
    ranks, rank = int(sizes["ranks"]), int(sizes["rank"])
    null_below = int(float(sizes["null_share"]) * 65536)
    seed = int(sizes["dsdgen_seed"])
    stated = tuple(int(batch[k]) for k in STATED)
    first, last = _year_days()
    span_days = last - first + 1
    # a group key is a 32-bit word (one 32-bit sort operand compiles and
    # runs in a fraction of a 64-bit one's time; the draw is set-up)
    past = np.iinfo(np.int32).max
    assert n_item * span_days * 2 < past, (n_item, span_days)

    def fixed(i):
        return jax.random.fold_in(
            jax.random.key(seed, impl="threefry2x32"), i)

    def side(at: int, n: int):
        """One table's fixed draw: date, item rank, price (int32) and the
        validity masks of the date and of the price."""
        valid = [jax.random.bits(fixed(at + 3 + j), (n,), dtype=jnp.uint16)
                 >= null_below for j in range(2)]
        return (tpcds.draw_sales_dates(fixed(at), n),
                jax.random.randint(fixed(at + 1), (n,), 0, n_item,
                                   dtype=jnp.int32),
                jax.random.randint(fixed(at + 2), (n,), 1, MAX_PRICE + 1,
                                   dtype=jnp.int32), valid)

    def group_keys(date, item, price, valid, flag: int):
        """A row's (item, date) group as one sortable int32 with the
        table's `flag` as the lowest bit; a row the date join drops sorts
        past every group."""
        passes = valid[0] & (date >= first) & (date <= last)
        day = jnp.where(passes, date - first, 0).astype(jnp.int32)
        key = (item * span_days + day) * 2 + flag
        return jnp.where(passes, key, jnp.int32(past)), passes

    @jax.jit
    def draw(keys_key, values_key):
        ss, ws = side(0, n_ss), side(8, n_ws)
        # what the draw fixes, counted here so that `gen` can hold the
        # configuration to it: one sort of both tables' group keys
        ks, ps = group_keys(*ss, 0)
        kw, pw = group_keys(*ws, 1)
        srt = jnp.sort(jnp.concatenate([ks, kw]))
        live = srt < past
        prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), srt[:-1]])
        new = live & (srt != prev)              # a group's first row, a side
        web = (srt & 1) == 1
        both = new & web & (prev == srt - 1)    # the store's rows lie before
        count = lambda m: jnp.sum(m, dtype=jnp.int64)
        groups = (count(new & ~web), count(new & web), count(both))
        drawn = (count(ps), count(pw), *groups,
                 groups[0] + groups[1] - groups[2])
        v1, v2, v3 = jax.random.split(values_key, 3)
        as_item = jax.random.permutation(v1, n_item).astype(jnp.int32)
        wide = lambda a: a.astype(jnp.int64)

        def table(names, drawn_side, turn):
            date, item, price, (ok_date, ok_price) = drawn_side
            roll = lambda a: jnp.roll(a, turn)
            cols = (date, wide(as_item[item]) * ranks + rank + 1,
                    wide(price))
            valid = (ok_date, jnp.ones_like(ok_date), ok_price)
            return ({n: roll(c) for n, c in zip(names, cols)},
                    {n: roll(v) for n, v in zip(names, valid)})
        return {"store_sales": table(
                    COLUMNS["store_sales"], ss,
                    jax.random.randint(v2, (), 0, n_ss, dtype=jnp.int32)),
                "web_sales": table(
                    COLUMNS["web_sales"], ws,
                    jax.random.randint(v3, (), 0, n_ws, dtype=jnp.int32))
                }, drawn

    def gen(keys_key, values_key):
        tables, drawn = draw(keys_key, values_key)
        drawn = tuple(int(x) for x in jax.device_get(drawn))
        if drawn != stated:
            raise ValueError(
                f"the draw holds {dict(zip(STATED, drawn))} for {n_ss} "
                f"store_sales and {n_ws} web_sales rows of {n_item} items; "
                f"the configuration states {dict(zip(STATED, stated))}")
        return tables
    gen.lower = draw.lower
    return gen


def least_bytes(batch: dict, sizes: dict, result_rows: int) -> int:
    """Each scanned column read once with its validity byte (three of each
    fact table), date_dim's three, plus the result: eight int64 columns."""
    return (fact_rows(batch) * 3 * _CELL + tpcds.N_DATES * 3 * 8
            + result_rows * len(RESULT_COLUMNS) * 8)


def window_bytes(batch: dict, sizes: dict, counts: dict = None) -> int:
    """What the plan's three windows must move in one request: each
    window's partition, order and value columns and their validity read
    once, each function's column and its validity written once. A
    channel's running sum reads (item, date, sum) and writes one column
    over its groups; the running maxima read (item, date, web_sales,
    store_sales) and write two over the full join's rows. Rows are what
    the reference counted (`COUNTS`, set by its last call); before any
    call, what the batch states."""
    counts = counts or COUNTS or {
        "web_groups": batch["web_groups"],
        "store_groups": batch["store_groups"],
        "join_rows": batch["join_rows"]}
    return ((counts["web_groups"] + counts["store_groups"]) * (3 + 1) * _CELL
            + counts["join_rows"] * (4 + 2) * _CELL)


# ---- the plain reference -------------------------------------------------------

_LOWEST = np.iinfo(np.int64).min


def _running(part, value, valid, op: str, restart_at_null: bool = False):
    """`op(value) OVER (PARTITION BY part ORDER BY <the rows' order> ROWS
    BETWEEN UNBOUNDED PRECEDING AND CURRENT ROW)` over rows that lie in
    (partition, order) order -> (result, its validity). SQL's rule, written
    out: a NULL is SKIPPED (it adds 0, it raises no maximum) and the result
    is NULL only until the partition's first non-null value. pandas' own
    `cumsum` / `cummax` do neither: they leave NaN AT a NaN row and carry
    past it, so a row whose own value is NULL would read NULL where SQL
    reads the total so far; hence the fill, and the running count that
    says from where on a value has been seen. `part` None: one partition.
    `restart_at_null` (a control's): a NULL ends the run: NULL at that
    row, and the rows after it start over."""
    import pandas as pd
    fill = 0 if op == "sum" else _LOWEST
    filled = pd.Series(np.where(valid, value, fill))
    seen = pd.Series(valid.astype(np.int64))
    if restart_at_null:
        # a run ends at every NULL: the NULL row belongs to the run it ends
        run = np.cumsum(~valid) - (~valid)
        part = run if part is None else [part, run]
    if part is None:
        acc = filled.cumsum() if op == "sum" else filled.cummax()
        seen = seen.cumsum()
    else:
        grouped = filled.groupby(part, sort=False)
        acc = grouped.cumsum() if op == "sum" else grouped.cummax()
        seen = seen.groupby(part, sort=False).cumsum()
    ok = seen.values > 0
    if restart_at_null:
        ok = ok & valid
    return np.where(ok, acc.values, 0), ok


def _channel(table, dd, names, lossy, control):
    """One CTE -> (item, d_date, cume_sales, its validity) in (item, date)
    order, and the rows the date join passed."""
    import pandas as pd
    cols, validity = table
    date, item, price = (np.asarray(cols[n]).astype(np.int64) for n in names)
    ok_d, ok_i, ok_p = (np.asarray(validity[n]).astype(bool)
                        if n in validity else np.ones(date.size, bool)
                        for n in names)
    seq = np.asarray(dd["d_month_seq"])
    year = (seq >= DMS) & (seq <= DMS + 11)
    sk, day = np.asarray(dd["d_date_sk"])[year], np.asarray(dd["d_date"])[year]
    # `ws_sold_date_sk = d_date_sk`: a null date matches no day; and
    # `ws_item_sk is not null`
    keep = ok_d & ok_i & np.isin(date, sk)
    if lossy is not None:
        price = lossy(price)
    frame = pd.DataFrame({
        "item_sk": item[keep],
        "d_date": day[np.searchsorted(sk, date[keep])],    # sk ascends
        # sum() skips a NULL price; a group of NULLs alone sums to NULL
        "price": np.where(ok_p[keep], price[keep], 0),
        "priced": ok_p[keep].astype(np.int64)})
    sums = frame.groupby(["item_sk", "d_date"], sort=True).sum().reset_index()
    item_sk, d_date = sums.item_sk.values, sums.d_date.values
    has_sum = sums.priced.values > 0        # `sum(min_count=1)`, written out
    cume, ok = _running(None if control == "no_partition" else item_sk,
                        sums.price.values, has_sum, "sum",
                        restart_at_null=control == "restart_at_null")
    return (item_sk, d_date, cume, ok), int(keep.sum())


def reference(tables: dict, lossy=None, control: str = ""):
    """-> the first 100 rows of the answer as a `Frame` of
    `RESULT_COLUMNS`.

    pandas' `merge` matches NaN / NA keys with each other, which SQL's `=`
    does not: the reference takes the rows with a null in either key
    column out of both sides before the merge and puts them back as
    unmatched rows of their side (the CTEs' keys hold none here: the item
    is filtered `is not null` and the day comes from `date_dim`; the rule
    is written out all the same). `lossy` (chipbench.control's bfloat16) is
    applied to the money before anything is summed. `control` names this
    cell's own wrong forms (tests/test_correct_q51.py): "no_partition"
    runs each channel's sum over the whole table; "null_as_zero" compares
    a NULL side as 0; "no_carry" takes the row's own total for the
    running maximum; "restart_at_null" resets every carry at a NULL;
    "inner" joins the channels as an inner join."""
    import pandas as pd
    assert control in ("",) + CONTROLS, control
    dd = tables["date_dim"][0]
    sides, date_rows = [], []
    for name, out in (("web_sales", "web"), ("store_sales", "store")):
        (item, day, cume, ok), rows = _channel(
            tables[name], dd, COLUMNS[name], lossy, control)
        date_rows.append(rows)
        sides.append(pd.DataFrame({
            "item_sk": pd.array(item, dtype="Int64"),
            "d_date": pd.array(day, dtype="Int64"),
            out + "_sales": cume, out + "_ok": ok, "in_" + out: True}))
    web, store = sides
    parts = []
    for frame in sides:
        null_key = (frame.item_sk.isna() | frame.d_date.isna()).values
        parts.append((frame[~null_key], frame[null_key]))
    (left, left_null), (right, right_null) = parts
    merged = pd.concat([
        left.merge(right, on=["item_sk", "d_date"],
                   how="inner" if control == "inner" else "outer"),
        *(() if control == "inner" else (left_null, right_null))],
        ignore_index=True)
    in_web = merged.in_web.notna().values
    in_store = merged.in_store.notna().values
    matched = int((in_web & in_store).sum())
    # the CASE WHENs: a matched or web-only row holds the web side's keys,
    # a store-only row the store's; `merge(on=)` has done the same
    order = np.lexsort((merged.d_date.values.astype(np.int64),
                        merged.item_sk.values.astype(np.int64)))
    item = merged.item_sk.values.astype(np.int64)[order]
    day = merged.d_date.values.astype(np.int64)[order]
    # a side without the row is NULL, and so is a total that is NULL itself
    sales = {}
    for out, there in (("web", in_web), ("store", in_store)):
        ok = (there & merged[out + "_ok"].fillna(False).values
              .astype(bool))[order]
        value = np.where(ok, merged[out + "_sales"].fillna(0).values
                         .astype(np.int64)[order], 0)
        if control == "no_carry":
            top, seen = value, ok
        else:
            top, seen = _running(item, value, ok, "max",
                                 restart_at_null=control == "restart_at_null")
        sales[out] = (value, ok, top, seen)
    (web_v, web_ok, web_top, web_seen) = sales["web"]
    (store_v, store_ok, store_top, store_seen) = sales["store"]
    if control == "null_as_zero":
        keep = np.where(web_seen, web_top, 0) > np.where(store_seen,
                                                         store_top, 0)
    else:
        # NULL > x and x > NULL are NULL, and a filter keeps TRUE alone
        keep = web_seen & store_seen & (web_top > store_top)
    COUNTS.clear()
    COUNTS.update(
        web_date_rows=date_rows[0], store_date_rows=date_rows[1],
        web_groups=len(web), store_groups=len(store), matched=matched,
        join_rows=len(merged), filter_rows=int(keep.sum()),
        window_rows=len(web) + len(store) + len(merged),
        window_partitions=int(web.item_sk.nunique() + store.item_sk.nunique()
                              + len(np.unique(item))))
    # rows lie in (item, date) order already: the query's ORDER BY
    first = np.flatnonzero(keep)[:100]
    return Frame({
        "item_sk": item[first], "d_date": day[first],
        "web_sales": web_v[first],
        "web_sales_null": (~web_ok[first]).astype(np.int64),
        "store_sales": store_v[first],
        "store_sales_null": (~store_ok[first]).astype(np.int64),
        "web_cumulative": np.where(web_seen, web_top, 0)[first],
        "store_cumulative": np.where(store_seen, store_top, 0)[first]})
