"""TPC-H query 13 ("customer distribution", WORD1 special, WORD2 requests)
for the plan engine: plan, generator, plain reference, and the bytes its
outer join moves.

    select c_count, count(*) as custdist
    from (select c_custkey, count(o_orderkey) as c_count
          from customer left outer join orders
               on c_custkey = o_custkey
              and o_comment not like '%special%requests%'
          group by c_custkey) c_orders
    group by c_count
    order by custdist desc, c_count desc

The plan is the query as Spark plans it: the conjunct of the `ON` clause
that reads `orders` alone becomes a `Filter` below the join's
null-supplying side, `customer LEFT OUTER` the surviving orders, the count
of non-null `o_orderkey` a customer, the count of customers a `c_count`,
the sort. The join's semantics are the answer: the row `c_count = 0` holds
the customers without a surviving order, and exists only because an
unmatched left row comes out null-extended and `count` skips the null.
`o_comment like '%special%requests%'` is an int64 0/1 column drawn at load,
`o_special` (the configuration's `reduced.strings`).

The reference shares no code with the engine and imports no jax: numpy over
the same arrays.
"""
import numpy as np

from chipbench.plans.tpch_q1 import Frame
from chipbench.plans.tpch_q18 import order_key

COLUMNS = {"customer": ["c_custkey"],
           "orders": ["o_orderkey", "o_custkey", "o_special"]}
ORDERED = ["custdist", "c_count"]              # the query's own ORDER BY
RESULT_COLUMNS = ["c_count", "custdist"]
SPECIAL_PER_MILLE = 11      # comments that match '%special%requests%'
# the join's output: c_custkey, o_custkey, o_orderkey, and a validity byte
# for each of the two columns of the null-supplying side
JOIN_OUT_BYTES = 3 * 8 + 2

# what the last `reference` call counted (`outer_join_bytes` and the
# `outer_join_bw_share` reader read it after the check)
COUNTS = {}


def plan():
    from spark_rapids_tpu.plan import PlanBuilder, col
    b = PlanBuilder()
    customer = b.scan("customer", schema=COLUMNS["customer"])
    orders = b.scan("orders", schema=COLUMNS["orders"])
    built = (customer.join(orders.filter(col("o_special") == 0),
                           left_on="c_custkey", right_on="o_custkey",
                           how="left_outer")
             .aggregate(["c_custkey"], [("o_orderkey", "count", "c_count")])
             .aggregate(["c_count"], [("c_custkey", "size", "custdist")])
             .sort(ORDERED, ascending=[False, False])
             .build())
    # the engine's own verifier before a table is drawn (an engine whose
    # plans cannot say `left_outer` has failed already, in `join`)
    from spark_rapids_tpu import dtypes
    from spark_rapids_tpu.analysis import verifier
    verifier.verify(built, input_dtypes={
        t: {c: dtypes.INT64 for c in cols} for t, cols in COLUMNS.items()},
    ).raise_if_failed("tpch_q13")
    return built


def caps(batch: dict) -> dict:
    # the capped tier (tier-1 tests; the cell runs eager): every customer
    # comes out once at least and an order once at most; a group a customer
    return dict(row_cap=int(batch["orders_rows"] + batch["customer_rows"]),
                key_cap=int(batch["customer_rows"]))


def fact_rows(batch: dict) -> int:
    return int(batch["orders_rows"])


def dimensions(sizes: dict) -> dict:
    """None: `customer` is rotated by --seed and drawn with `orders`."""
    return {}


def customer_key(j):
    """The j-th customer key that dbgen gives orders to: those not
    divisible by 3 (1, 2, 4, 5, 7, 8, ...)."""
    return 3 * (j // 2) + j % 2 + 1


def batch_generator(sizes: dict, batch: dict):
    """-> gen(keys_key, values_key) -> {"customer": .., "orders": ..}, each
    (columns, {}), drawn on the device. Which ordering customer (by rank
    among the keys not divisible by 3) places each order, and which
    comments match the pattern, is one fixed draw of the configuration's
    `dsdgen_seed` through threefry, whose bits are the same on every
    backend: matched pairs, null-extended rows and the groups of both
    aggregates do not change with --seed, and the batch can state them (the
    harness's `keys_key` is that seed's too; it is not read). From
    `values_key`: a relabelling of the ordering customers' keys among
    themselves, the rotation of both tables' rows, and the rotation of the
    order keys over the rows."""
    import jax
    import jax.numpy as jnp
    n_c, n_o = int(batch["customer_rows"]), int(batch["orders_rows"])
    n_have = n_c * 2 // 3           # customers that order at all
    seed = int(sizes["dsdgen_seed"])
    stated = tuple(int(batch[k]) for k in (
        "matched_pairs", "unmatched_customers", "count_groups"))

    def fixed(i):
        return jax.random.fold_in(
            jax.random.key(seed, impl="threefry2x32"), i)

    @jax.jit
    def draw(keys_key, values_key):
        wide = lambda a: a.astype(jnp.int64)
        who = jax.random.randint(fixed(0), (n_o,), 0, n_have,
                                 dtype=jnp.int32)
        special = (jax.random.randint(fixed(1), (n_o,), 0, 1000,
                                      dtype=jnp.int32)
                   < SPECIAL_PER_MILLE).astype(jnp.int32)
        # what the draw fixes, counted here so that `gen` can hold the
        # configuration to it: surviving orders a customer, by rank
        survive = jnp.zeros((n_have,), jnp.int32).at[who].add(1 - special)
        hist = jnp.zeros((1024,), jnp.int32).at[
            jnp.minimum(survive, 1023)].add(1)
        unmatched = n_c - n_have + hist[0]
        drawn = (jnp.sum(1 - special), unmatched,
                 jnp.sum(hist[1:] > 0) + (unmatched > 0))
        v1, v2, v3, v4 = jax.random.split(values_key, 4)
        relabel = jax.random.permutation(v1, n_have).astype(jnp.int32)
        turn_c = jax.random.randint(v2, (), 0, n_c, dtype=jnp.int32)
        turn_o = jax.random.randint(v3, (), 0, n_o, dtype=jnp.int32)
        turn_k = jax.random.randint(v4, (), 0, n_o, dtype=jnp.int32)
        row_c = jnp.arange(n_c, dtype=jnp.int32)
        row_o = jnp.arange(n_o, dtype=jnp.int32)
        return {"customer": ({"c_custkey": wide((row_c + turn_c) % n_c + 1)},
                             {}),
                "orders": ({"o_orderkey": wide(order_key(
                                (row_o + turn_k) % n_o)),
                            "o_custkey": wide(customer_key(
                                relabel[jnp.roll(who, turn_o)])),
                            "o_special": wide(jnp.roll(special, turn_o))},
                           {})}, drawn

    def gen(keys_key, values_key):
        tables, drawn = draw(keys_key, values_key)
        drawn = tuple(int(x) for x in jax.device_get(drawn))
        if drawn != stated:
            raise ValueError(
                f"the draw holds {drawn} (matched pairs, null-extended "
                f"customers, c_count groups) for {n_o} orders of {n_c} "
                f"customers; the configuration states {stated}")
        return tables
    gen.lower = draw.lower
    return gen


def least_bytes(batch: dict, sizes: dict, result_rows: int) -> int:
    """Each scanned column read once (customer's one, orders' three), plus
    the result: two int64 columns."""
    return (batch["customer_rows"] * 8 + batch["orders_rows"] * 3 * 8
            + result_rows * 2 * 8)


def outer_join_bytes(batch: dict, sizes: dict, counts: dict = None) -> int:
    """What the plan's outer join must move in one request: both sides' key
    columns read once, and every output row's three int64 columns and the
    two validity bytes of the null-supplying side's written once. Rows are
    what the reference counted (`COUNTS`, set by its last call); before any
    call, what the batch states: every surviving order matches one customer
    (so the right side's rows are the matched pairs)."""
    counts = counts or COUNTS or {
        "left_rows": batch["customer_rows"],
        "right_rows": batch["matched_pairs"],
        "matched": batch["matched_pairs"],
        "unmatched": batch["unmatched_customers"]}
    return ((counts["left_rows"] + counts["right_rows"]) * 8
            + (counts["matched"] + counts["unmatched"]) * JOIN_OUT_BYTES)


# ---- the plain reference -------------------------------------------------------

def reference(tables: dict, lossy=None, control: str = ""):
    """-> the result as a `Frame`: `c_count`, `custdist`, in the query's
    order.

    `lossy` (chipbench.control's bfloat16) is applied to the join keys of
    both sides before anything is matched. `control` names this cell's own
    wrong forms (tests/test_correct_q13.py): "inner" joins inner (the
    customers without a surviving order are gone, and `c_count = 0` with
    them); "count_star" counts rows where the query counts non-null
    `o_orderkey` (a null-extended row counts: those customers land in
    `c_count = 1`); "filter_above" applies the comment's predicate to the
    join's output, what pushing it the wrong way round gives (a
    null-extended row's `o_special` is null and fails it)."""
    as64 = lambda a: np.asarray(a).astype(np.int64)
    ckey = as64(tables["customer"][0]["c_custkey"])
    orders = tables["orders"][0]
    okey, ocust = as64(orders["o_orderkey"]), as64(orders["o_custkey"])
    special = as64(orders["o_special"])
    if lossy is not None:
        ckey, ocust = lossy(ckey), lossy(ocust)
    # the filter below the null-supplying side ("filter_above": after the
    # join, where only matched rows can pass it: the same pairs survive)
    keep = special == 0
    right = ocust[keep]
    assert okey[keep].size == right.size    # o_orderkey is never null
    # merge(how="left") by sorting: each customer's matches are the
    # surviving orders that carry its key
    keys, per_key = np.unique(right, return_counts=True)
    if keys.size:
        at = np.minimum(np.searchsorted(keys, ckey), keys.size - 1)
        found = keys[at] == ckey
        matches = np.where(found, per_key[at], 0).astype(np.int64)
    else:
        found = np.zeros(ckey.size, bool)
        matches = np.zeros(ckey.size, np.int64)
    # one output row a match, and one null-extended row where none
    if control in ("inner", "filter_above"):
        c_custkey, c_count = ckey[found], matches[found]
    elif control == "count_star":
        c_custkey, c_count = ckey, np.maximum(matches, 1)
    else:
        c_custkey, c_count = ckey, matches
    # group by c_custkey (under `lossy` several rows share a key)
    groups, member = np.unique(c_custkey, return_inverse=True)
    c_count = np.bincount(member, weights=c_count,
                          minlength=groups.size).astype(np.int64)
    values, custdist = np.unique(c_count, return_counts=True)
    COUNTS.clear()
    COUNTS.update(left_rows=int(ckey.size), right_rows=int(right.size),
                  matched=int(matches.sum()),
                  unmatched=int((~found).sum()),
                  groups=(int(groups.size), int(values.size)))
    order = np.lexsort((-values, -custdist))
    return Frame({"c_count": values[order].astype(np.int64),
                  "custdist": custdist[order].astype(np.int64)})
