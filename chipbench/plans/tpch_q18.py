"""TPC-H query 18 ("large volume customer", QUANTITY 300) for the plan
engine: plan, generator, plain reference, and the bytes its keyed
aggregates move.

    select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
           sum(l_quantity)
    from customer, orders, lineitem
    where o_orderkey in (select l_orderkey from lineitem
                         group by l_orderkey having sum(l_quantity) > 300)
      and c_custkey = o_custkey and o_orderkey = l_orderkey
    group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    order by o_totalprice desc, o_orderdate limit 100

The plan is the query as Spark plans it: the subquery's aggregate (one
group an order), the `Filter` over its `decimal(25,2)` sum, `orders LEFT
SEMI` the survivors, the joins to `customer` and to `lineitem` (its second
scan), the outer aggregate over five keys, the top 100. The outer sum is
not rewritten out of the subquery's. `l_quantity` and `o_totalprice` are
`decimal(15,2)` in 8 bytes, as Spark stores them; `c_name` is an int64
code equal to the customer key and `o_orderdate` int64 days since the
epoch (the configuration's `reduced`).

The reference shares no code with the engine and imports no jax: numpy
over the same arrays, every sum an exact integer of cents, and it renders
a DECIMAL128 value as its four little-endian uint32 limbs, so
`check.compare` compares exactly.
"""
import numpy as np

# days since the epoch, DECIMAL128 limbs of Python integers and the frame
# `check.compare` reads: the Q1 reference's own (numpy alone)
from chipbench.plans.tpch_q1 import Frame, _days, _limbs

QUANTITY = 300          # the specification's validation value (2.4.18.3)
COLUMNS = {"lineitem": ["l_orderkey", "l_quantity"],
           "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                      "o_totalprice"]}
ORDERED = ["o_totalprice", "o_orderdate"]      # the query's own ORDER BY
RESULT_COLUMNS = ["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                  "o_totalprice", "sum_qty"]
GROUP_KEYS = RESULT_COLUMNS[:5]
LIMIT = 100
ORDER_FIRST, ORDER_LAST = _days("1992-01-01"), _days("1998-08-02")
PRICE_LOW, PRICE_HIGH = 85771, 55528516     # cents: dbgen's o_totalprice range
MAX_LINES = 7

# ---- Spark's types, as (precision, scale) ----
MONEY = (15, 2)                 # dss.ddl: DECIMAL(15,2)
SUM_MONEY = (25, 2)             # Sum: p + 10, s; DECIMAL128
# sum(l_quantity) > 300: the literal is decimal(3,0); the comparison casts
# both sides to the wider common type, scale max(2, 0) = 2 and 23 integral
# digits: decimal(25,2), so the literal is 30000 unscaled
HAVING_TYPE = (25, 2)

# what the last `reference` call counted: rows into and groups out of
# each keyed aggregate (`groupby_bytes` reads it after the check)
COUNTS = {}


def plan(quantity: int = QUANTITY):
    from spark_rapids_tpu import dtypes
    from spark_rapids_tpu.plan import PlanBuilder, col
    money = dtypes.decimal(*MONEY)
    b = PlanBuilder()
    lineitem = b.scan("lineitem", schema=COLUMNS["lineitem"],
                      types={"l_quantity": money})
    orders = b.scan("orders", schema=COLUMNS["orders"],
                    types={"o_totalprice": money})
    customer = b.scan("customer", schema=["c_custkey", "c_name"])
    large = (lineitem.aggregate(["l_orderkey"],
                                [("l_quantity", "sum", "order_qty")])
             .filter(col("order_qty") > quantity)
             .select(["l_orderkey"]))
    built = (orders.join(large, left_on="o_orderkey", right_on="l_orderkey",
                         how="left_semi")
             .join(customer, left_on="o_custkey", right_on="c_custkey")
             .join(lineitem, left_on="o_orderkey", right_on="l_orderkey")
             .aggregate(GROUP_KEYS, [("l_quantity", "sum", "sum_qty")])
             .sort(ORDERED, ascending=[False, True])
             .limit(LIMIT)
             .build())
    # the engine's own verifier, over the buffers' storage types, before a
    # table is drawn: an engine that does not lower the plan (the parent of
    # PR 34: a comparison over DECIMAL128 limbs) fails here, at once, and
    # not after it has compiled a group-by of 60 M rows
    from spark_rapids_tpu.analysis import verifier
    stored = {"lineitem": COLUMNS["lineitem"], "orders": COLUMNS["orders"],
              "customer": ["c_custkey", "c_name"]}
    verifier.verify(built, input_dtypes={
        t: {c: dtypes.INT64 for c in cols} for t, cols in stored.items()},
    ).raise_if_failed("tpch_q18")
    return built


def caps(batch: dict) -> dict:
    # the capped tier (tier-1 tests; the cell runs eager): a group an
    # order, and a join never puts out more rows than lineitem holds
    return dict(row_cap=int(batch["lineitem_rows"]),
                key_cap=int(batch["orders_rows"]))


def fact_rows(batch: dict) -> int:
    """The scan that feeds the subquery; the second scan of the same
    table is not counted again."""
    return int(batch["lineitem_rows"])


def dimensions(sizes: dict) -> dict:
    n = int(sizes["customer_rows"])
    keys = np.arange(1, n + 1, dtype=np.int64)
    # c_name is 'Customer#' and the key in nine digits: the code is the key
    return {"customer": {"c_custkey": keys, "c_name": keys.copy()}}


def order_key(i):
    """dbgen's sparse order keys: 8 used of every 32."""
    return (i >> 3 << 5) + (i & 7) + 1


def batch_generator(sizes: dict, batch: dict):
    """-> gen(keys_key, values_key) -> {"lineitem": .., "orders": ..}, each
    (columns, {}), drawn on the device. Lines an order (1..7), quantities
    and order keys are one fixed draw of the configuration's
    `dsdgen_seed` through threefry, whose bits are the same on every
    backend: rows, groups and the survivors of the `HAVING` do not change
    with --seed, and `batch["lineitem_rows"]` can state the count (the
    harness's `keys_key` is that seed's too; it is not read). `o_custkey`,
    `o_orderdate` and `o_totalprice` come from `values_key`."""
    import jax
    import jax.numpy as jnp
    n_o, n_l = int(batch["orders_rows"]), int(batch["lineitem_rows"])
    customers = int(sizes["customer_rows"])
    seed = int(sizes["dsdgen_seed"])

    def fixed(i):
        return jax.random.fold_in(
            jax.random.key(seed, impl="threefry2x32"), i)

    @jax.jit
    def draw(keys_key, values_key):
        wide = lambda a: a.astype(jnp.int64)
        lines = jax.random.randint(fixed(0), (n_o,), 1,
                                   MAX_LINES + 1, dtype=jnp.int32)
        starts = jnp.cumsum(lines) - lines
        order = jnp.arange(n_o, dtype=jnp.int32)
        # lineitem in order-key order, as dbgen writes it: each order
        # marks its first row, a running maximum carries it to the rest
        owner = jax.lax.cummax(
            jnp.zeros((n_l,), jnp.int32).at[starts].set(order, mode="drop"))
        qty = jax.random.randint(fixed(1), (n_l,), 1, 51,
                                 dtype=jnp.int32)
        v1, v2, v3 = jax.random.split(values_key, 3)
        # the customer keys not divisible by 3: 1, 2, 4, 5, 7, 8, ...
        j = jax.random.randint(v1, (n_o,), 0, customers * 2 // 3,
                               dtype=jnp.int32)
        return {"lineitem": ({"l_orderkey": wide(order_key(owner)),
                              "l_quantity": wide(qty * 100)}, {}),
                "orders": ({"o_orderkey": wide(order_key(order)),
                            "o_custkey": wide(3 * (j // 2) + j % 2 + 1),
                            "o_orderdate": wide(jax.random.randint(
                                v2, (n_o,), ORDER_FIRST, ORDER_LAST + 1,
                                dtype=jnp.int32)),
                            "o_totalprice": wide(jax.random.randint(
                                v3, (n_o,), PRICE_LOW, PRICE_HIGH + 1,
                                dtype=jnp.int32))}, {})}, jnp.sum(lines)

    def gen(keys_key, values_key):
        tables, drawn = draw(keys_key, values_key)
        if int(drawn) != n_l:
            raise ValueError(
                f"the draw holds {int(drawn)} lineitem rows for {n_o} "
                f"orders; the configuration states {n_l}")
        return tables
    gen.lower = draw.lower
    return gen


def least_bytes(batch: dict, sizes: dict, result_rows: int) -> int:
    """Each scanned column read once (lineitem's two, though the plan
    scans them twice; orders' four; customer's two), plus the result:
    five int64 columns and one 16-byte decimal."""
    return (batch["lineitem_rows"] * 2 * 8 + batch["orders_rows"] * 4 * 8
            + sizes["customer_rows"] * 2 * 8 + result_rows * (5 * 8 + 16))


def groupby_bytes(batch: dict, sizes: dict, counts: dict = None) -> int:
    """What the plan's two keyed aggregates must move in one request: the
    int64 key columns and the 8-byte value column of every row read once,
    the keys and the DECIMAL128 sum of every group written once. Rows and
    groups are what the reference counted (`COUNTS`, set by its last
    call); before any call, the subquery's alone, which the batch fixes:
    every lineitem row, and a group an order."""
    counts = counts or COUNTS or {
        "subquery": (batch["lineitem_rows"], batch["orders_rows"])}
    keys = {"subquery": 1, "outer": len(GROUP_KEYS)}
    return sum(rows * (keys[name] * 8 + 8) + groups * (keys[name] * 8 + 16)
               for name, (rows, groups) in counts.items())


# ---- the plain reference -------------------------------------------------------

def _order_sums(keys: np.ndarray, cents: np.ndarray):
    """-> (distinct keys, each one's exact int64 sum of `cents`)."""
    if keys.size == 0:
        return keys, cents
    if not bool((keys[1:] >= keys[:-1]).all()):     # dbgen writes them sorted
        by = np.argsort(keys, kind="stable")
        keys, cents = keys[by], cents[by]
    starts = np.flatnonzero(np.concatenate([[True], keys[1:] != keys[:-1]]))
    return keys[starts], np.add.reduceat(cents, starts)


def reference(tables: dict, lossy=None, control: str = "",
              quantity: int = QUANTITY):
    """-> the result as a `Frame`: five int64 columns and `sum_qty` as
    limbs, in the query's order.

    `lossy` (chipbench.control's bfloat16) is applied to the two money
    columns before anything is computed. `control` names this cell's own
    wrong forms (tests/test_correct_q18.py): "float64" holds money as a
    float64 of units, sums and compares in float64 and casts back to cents
    by truncation, what an engine without decimals does; "having_ge" keeps
    the orders whose sum is at least QUANTITY; "ascending" orders by
    price ascending."""
    li, orders, cust = (tables[n][0] for n in ("lineitem", "orders",
                                               "customer"))
    as64 = lambda a: np.asarray(a).astype(np.int64)
    lkey, qty = as64(li["l_orderkey"]), as64(li["l_quantity"])
    okey, ocust = as64(orders["o_orderkey"]), as64(orders["o_custkey"])
    odate, price = as64(orders["o_orderdate"]), as64(orders["o_totalprice"])
    ckey, cname = as64(cust["c_custkey"]), as64(cust["c_name"])
    if lossy is not None:
        qty, price = lossy(qty), lossy(price)
    assert qty.size < 2 ** 31 and (qty.size == 0 or (
        0 <= qty.min() and qty.max() < 2 ** 32)), "an int64 sum is exact"
    to_cents = lambda a: a
    if control == "float64":
        qty, price = qty / 100.0, price / 100.0
        to_cents = lambda a: (np.asarray(a) * 100.0).astype(np.int64)
    # the subquery: one group an order, HAVING sum(l_quantity) > QUANTITY
    group_keys, group_sums = _order_sums(lkey, qty)
    bound = quantity if control == "float64" else quantity * 100
    keep = group_sums >= bound if control == "having_ge" \
        else group_sums > bound
    large = group_keys[keep]
    # orders LEFT SEMI the large orders, then customer by key
    o_at = np.flatnonzero(np.isin(okey, large))
    c_at = {int(ckey[r]): r for r in
            np.flatnonzero(np.isin(ckey, ocust[o_at])).tolist()}
    by_order = {}
    for i in o_at.tolist():
        c = c_at.get(int(ocust[i]))
        if c is not None:
            by_order[int(okey[i])] = (int(cname[c]), int(ckey[c]),
                                      int(okey[i]), int(odate[i]),
                                      price[i].item())
    # lineitem again, by order key, then the outer aggregate
    l_at = np.flatnonzero(np.isin(lkey, large))
    sums = {}
    for k, q in zip(lkey[l_at].tolist(), qty[l_at].tolist()):
        if k in by_order:
            sums[by_order[k]] = sums.get(by_order[k], 0) + q
    COUNTS.clear()
    COUNTS.update(subquery=(int(lkey.size), int(group_keys.size)),
                  outer=(sum(1 for k in lkey[l_at].tolist() if k in by_order),
                         len(sums)))
    sign = 1 if control == "ascending" else -1
    rows = sorted(sums.items(),
                  key=lambda kv: (sign * kv[0][4], kv[0][3]))[:LIMIT]
    out = {name: np.asarray([k[j] for k, _ in rows], np.int64)
           for j, name in enumerate(GROUP_KEYS[:4])}
    out["o_totalprice"] = np.asarray(
        to_cents([k[4] for k, _ in rows]), np.int64).reshape(-1)
    out["sum_qty"] = _limbs(to_cents([s for _, s in rows]).tolist()
                            if control == "float64"
                            else [s for _, s in rows])
    return Frame(out)
