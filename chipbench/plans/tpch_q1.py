"""TPC-H query 1 ("pricing summary report", DELTA 90) for the plan engine:
plan, generator, plain reference, and the bytes its decimal kernels move.

    select l_returnflag, l_linestatus, sum(l_quantity), sum(l_extendedprice),
           sum(l_extendedprice * (1 - l_discount)),
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
           avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
    from lineitem where l_shipdate <= date '1998-12-01' - interval '90' day
    group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus

The four money columns are `decimal(15,2)` in 8 bytes, as Spark stores
them; the plan declares that type over the int64 buffers of unscaled
values and everything downstream has Spark's types (below). The two
`char(1)` keys are int64 ASCII codes and the date is int64 days since the
epoch (the configuration's `reduced`).

The reference shares no code with `spark_rapids_tpu/ops/decimal*`: exact
integer arithmetic on the unscaled values, numpy int64 where the
configuration's value ranges prove it fits (each bound asserted), Python
integers for the group sums, the HALF_UP divisions and the checks against
a type's precision. Its result types are the constants below, each beside
its derivation, and it renders a DECIMAL128 value as its four
little-endian uint32 limbs: `check.compare` then compares exactly.
"""
import threading

import numpy as np

from chipbench import tpcds

COLUMNS = {"lineitem": ["l_quantity", "l_extendedprice", "l_discount",
                        "l_tax", "l_returnflag", "l_linestatus",
                        "l_shipdate"]}
DECIMAL_COLUMNS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")
ORDERED = ["l_returnflag", "l_linestatus"]     # the query's own ORDER BY
RESULT_COLUMNS = ["l_returnflag", "l_linestatus", "sum_qty",
                  "sum_base_price", "sum_disc_price", "sum_charge",
                  "avg_qty", "avg_price", "avg_disc", "count_order"]


def _days(date: str) -> int:
    return int((np.datetime64(date) - np.datetime64("1970-01-01")).astype(int))


CUTOFF = _days("1998-09-02")            # 1998-12-01 less 90 days
ORDER_FIRST, ORDER_LAST = _days("1992-01-01"), _days("1998-08-02")
CURRENT = _days("1995-06-17")           # dbgen's CURRENTDATE
R, A, N, O, F = (ord(c) for c in "RANOF")

# ---- Spark's types (allowPrecisionLoss=true; the configuration's `assumed`
# has the versions), as (precision, scale) ----
MONEY = (15, 2)                 # dss.ddl: DECIMAL(15,2)
# 1 - l_discount, 1 + l_tax: the literal 1 is decimal(1,0); add/sub gives
# scale max(0,2) = 2, precision max(1-0, 15-2) + 2 + 1 = 16
ONE_MINUS = (16, 2)
# l_extendedprice * (1 - l_discount): 15 + 16 + 1 = 32, 2 + 2 = 4
DISC_PRICE = (32, 4)
# ... * (1 + l_tax): 32 + 16 + 1 = 49, 4 + 2 = 6; over 38, so adjusted:
# 43 integral digits are kept, the scale gives way to max(38 - 43, min(6, 6))
CHARGE = (38, 6)
SUM_MONEY = (25, 2)             # Sum: p + 10, s
SUM_DISC_PRICE = (38, 4)        # 32 + 10 bounded to 38
SUM_CHARGE = (38, 6)
# Average: the sum as decimal(25,2) over the count as decimal(20,0) by the
# divide rule: scale max(6, 2 + 20 + 1) = 23, precision 25 - 2 + 0 + 23 =
# 46, adjusted to (38, max(38 - 23, 6)) ...
AVG_QUOTIENT = (38, 15)
AVG = (19, 6)                   # ... then cast HALF_UP to (p + 4, s + 4)


def plan():
    from spark_rapids_tpu import dtypes
    from spark_rapids_tpu.plan import PlanBuilder, col
    money = dtypes.decimal(*MONEY)
    b = PlanBuilder()
    li = b.scan("lineitem", schema=COLUMNS["lineitem"],
                types={c: money for c in DECIMAL_COLUMNS})
    keys = [(c, col(c)) for c in ("l_returnflag", "l_linestatus",
                                  "l_quantity", "l_extendedprice",
                                  "l_discount")]
    return (li.filter(col("l_shipdate") <= CUTOFF)
              .project(keys + [
                  ("l_tax", col("l_tax")),
                  ("disc_price",
                   col("l_extendedprice") * (1 - col("l_discount")))])
              .project(keys + [
                  ("disc_price", col("disc_price")),
                  ("charge", col("disc_price") * (1 + col("l_tax")))])
              .aggregate(["l_returnflag", "l_linestatus"],
                         [("l_quantity", "sum", "sum_qty"),
                          ("l_extendedprice", "sum", "sum_base_price"),
                          ("disc_price", "sum", "sum_disc_price"),
                          ("charge", "sum", "sum_charge"),
                          ("l_quantity", "mean", "avg_qty"),
                          ("l_extendedprice", "mean", "avg_price"),
                          ("l_discount", "mean", "avg_disc"),
                          ("l_quantity", "size", "count_order")])
              .sort(ORDERED)
              .build())


def caps(batch: dict) -> dict:
    # four groups; a key cap of 8 leaves room and selects the group-by
    # that sorts nothing (ops/aggregate.py DIRECT_KEY_CAP). No join: the
    # row cap is never read
    return dict(row_cap=max(batch["lineitem_rows"] // 8, 1024), key_cap=8)


def fact_rows(batch: dict) -> int:
    return int(batch["lineitem_rows"])


def dimensions(sizes: dict) -> dict:
    return {}


def batch_generator(sizes: dict, batch: dict):
    """-> gen(keys_key, values_key) -> {"lineitem": (columns, {})}:
    one file split of `lineitem` by dbgen's rules (the configuration's
    `assumed`), drawn on the device from four 32-bit words a row, in 32-bit arithmetic. Part
    keys and dates come from `keys_key`, quantities, discounts, taxes and
    flags from `values_key`."""
    import jax
    import jax.numpy as jnp
    n = int(batch["lineitem_rows"])
    parts = int(sizes["part_rows"])

    def below(halves, hi: int):
        """Whole numbers in [0, hi) from uniform 16-bit halves of a word
        by multiply-shift, in 32 bits (64-bit integer arithmetic, division
        above all, is emulated on the chip and would cost more than the
        query). Uniform to within hi / 2**16 (the file's `assumed`)."""
        return (halves.astype(jnp.int32) * hi) >> 16

    @jax.jit
    def draw(keys_key, values_key):
        k1, k2 = jax.random.split(keys_key, 2)
        v1, v2 = jax.random.split(values_key, 2)
        words = lambda k: jax.random.bits(k, (2, n), dtype=jnp.uint16)
        (part_hi, part_lo), (day, offsets) = words(k1), words(k2)
        (money_a, money_b), (flags_a, flags_b) = words(v1), words(v2)
        # partkey - 1 = 1000 x (0..1999) + (0..999): uniform over 2,000,000
        thousands, units = below(part_hi, parts // 1000), below(part_lo, 1000)
        partkey = 1000 * thousands + units + 1
        retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
        qty = 1 + below(money_a, 50)
        ship = ORDER_FIRST + below(day, ORDER_LAST + 1 - ORDER_FIRST) \
            + 1 + below(offsets & 0xFF, 121 * 256)
        receipt = ship + 1 + below(offsets >> 8, 30 * 256)
        returned = jnp.where(flags_b & 1 == 0, R, A)
        wide = lambda a: a.astype(jnp.int64)
        return {"lineitem": ({
            "l_quantity": wide(qty * 100),
            "l_extendedprice": wide(qty * retail),
            "l_discount": wide(below(money_b, 11)),
            "l_tax": wide(below(flags_a, 9)),
            "l_returnflag": wide(jnp.where(receipt <= CURRENT, returned, N)),
            "l_linestatus": wide(jnp.where(ship > CURRENT, O, F)),
            "l_shipdate": wide(ship)}, {})}

    turn = threading.Lock()

    def gen(keys_key, values_key):
        # the splits are drawn one at a time. The device runs the draws one
        # after another whatever the callers do, but a draw's 336 MB are
        # allocated when it is enqueued, and the harness lets go of a
        # caller's old split only once the new one is ready: with four
        # callers enqueueing at will, 10 or 11 splits were alive at a run's
        # peak, by the phase the closed loop fell into (`peak_hbm_gb` 3.40
        # to 3.73 GB over nine runs; PERF.md, PR 28). A caller that waits
        # its turn holds its old split alone: 9 alive at most, and nothing
        # is kept that a request does not use
        with turn:
            return jax.block_until_ready(draw(keys_key, values_key))
    gen.lower = draw.lower          # tests/deviceless.py compiles the draw
    return gen


def least_bytes(batch: dict, sizes: dict, result_rows: int) -> int:
    """The seven int64 columns read once, plus the result: two int64 keys,
    seven 16-byte decimals and the count per group."""
    return batch["lineitem_rows"] * 7 * 8 + result_rows * (2 * 8 + 7 * 16 + 8)


def decimal_bytes(batch: dict, sizes: dict) -> int:
    """What the row-wise decimal kernels of one request must move: the four
    DECIMAL64 inputs read once and the two DECIMAL128 products written
    once, for every row of the batch (the capped tier masks the 2% the
    filter drops, it does not compact them away)."""
    return batch["lineitem_rows"] * (4 * 8 + 2 * 16)


# ---- the plain reference -------------------------------------------------------

def _half_up(num: int, den: int) -> int:
    """num / den rounded half away from zero; den > 0."""
    q, r = divmod(abs(num), den)
    q += 2 * r >= den
    return -q if num < 0 else q


def _fits(value, precision: int):
    """The value, or None (Spark's non-ANSI overflow) past the precision."""
    return value if value is not None and abs(value) < 10 ** precision \
        else None


def _limbs(values) -> np.ndarray:
    """Python integers -> (n, 4) little-endian uint32 limbs, two's
    complement in 128 bits: DECIMAL128's layout."""
    out = np.zeros((len(values), 4), np.uint32)
    for i, v in enumerate(values):
        u = int(v) & ((1 << 128) - 1)
        out[i] = [(u >> (32 * j)) & 0xFFFFFFFF for j in range(4)]
    return out


class Frame:
    """What `check.compare` and `control` read of a reference: `len`,
    and `frame[column].values` as a numpy array."""

    class _Column:
        def __init__(self, values):
            self.values = values

    def __init__(self, columns: dict):
        self._columns = {n: Frame._Column(np.asarray(v))
                         for n, v in columns.items()}
        self._rows = len(next(iter(columns.values())))

    def __getitem__(self, name):
        return self._columns[name]

    def __len__(self):
        return self._rows


def _average(total, count: int, truncate: bool):
    """Spark's Average over decimal(15,2) as Spark 3.3 and earlier (and
    ISSUE 28) have it: sum / count at AVG_QUOTIENT's scale, HALF_UP, then
    the cast to AVG, HALF_UP again (two roundings).
    `truncate`, the control's, drops the digits instead."""
    if total is None or count == 0:
        return None
    up = 10 ** (AVG_QUOTIENT[1] - MONEY[1])
    down = 10 ** (AVG_QUOTIENT[1] - AVG[1])
    if truncate:
        q = abs(total) * up // count // down
        return _fits(-q if total < 0 else q, AVG[0])
    q = _fits(_half_up(total * up, count), AVG_QUOTIENT[0])
    return None if q is None else _fits(_half_up(q, down), AVG[0])


def reference(tables: dict, lossy=None, control: str = ""):
    """-> the result as a `Frame`: int64 keys and count, every decimal
    column as limbs. A null (an overflow) would raise: the cell's data
    gives none, and the system's answer may hold none either.

    `lossy` (chipbench.control's bfloat16) is applied to the four money
    columns before anything is computed. `control` names this cell's own
    lower-precision forms (tests/test_correct_q1.py): "float64" computes
    both products and every sum in float64, what an engine without
    decimals does; "truncate" drops digits where Spark rounds HALF_UP."""
    cols, _ = tables["lineitem"]
    c = {n: np.asarray(cols[n]).astype(np.int64) for n in COLUMNS["lineitem"]}
    if lossy is not None:
        for name in DECIMAL_COLUMNS:
            c[name] = lossy(c[name])
    keep = c["l_shipdate"] <= CUTOFF
    qty, price, disc, tax = (c[n][keep] for n in DECIMAL_COLUMNS)
    rf, ls = c["l_returnflag"][keep], c["l_linestatus"][keep]
    n = int(keep.sum())
    # the configuration's ranges, which make int64 exact below: a price of
    # at most 2 * 10^7 cents (dbgen: 50 x 209,900) times (1 - discount) as
    # at most 100 times (1 + tax) as at most 200 stays under 4 * 10^11,
    # and a sum of n such values under n * 4 * 10^11
    for name, a, hi in (("l_quantity", qty, 10 ** 4),
                        ("l_extendedprice", price, 2 * 10 ** 7),
                        ("l_discount", disc, 100), ("l_tax", tax, 100)):
        assert a.size == 0 or (0 <= a.min() and a.max() <= hi), (name, hi)
    assert n * 4 * 10 ** 11 < 2 ** 63, n
    if control == "float64":
        disc_price = price.astype(np.float64) * (100.0 - disc)
        charge = disc_price * (100.0 + tax)
    else:
        disc_price = price * (100 - disc)          # DISC_PRICE, exact
        charge = disc_price * (100 + tax)          # CHARGE: scale 6, exact
    rows = {name: [] for name in RESULT_COLUMNS}
    for key in sorted(set(zip(rf.tolist(), ls.tolist()))):
        m = (rf == key[0]) & (ls == key[1])
        count = int(m.sum())
        total = {"sum_qty": (qty, SUM_MONEY), "sum_base_price":
                 (price, SUM_MONEY), "sum_disc_price":
                 (disc_price, SUM_DISC_PRICE), "sum_charge":
                 (charge, SUM_CHARGE)}
        sums = {k: _fits(int(a[m].sum()), t[0]) for k, (a, t) in total.items()}
        sums["avg_qty"] = _average(sums["sum_qty"], count,
                                   control == "truncate")
        sums["avg_price"] = _average(sums["sum_base_price"], count,
                                     control == "truncate")
        sums["avg_disc"] = _average(_fits(int(disc[m].sum()), SUM_MONEY[0]),
                                    count, control == "truncate")
        rows["l_returnflag"].append(key[0])
        rows["l_linestatus"].append(key[1])
        rows["count_order"].append(count)
        for k, v in sums.items():
            if v is None:
                raise ValueError(f"{k} of group {key} overflowed its type")
            rows[k].append(v)
    out = {}
    for name in RESULT_COLUMNS:
        ints = name in ("l_returnflag", "l_linestatus", "count_order")
        out[name] = (np.asarray(rows[name], np.int64) if ints
                     else _limbs(rows[name]))
    return Frame(out)
