"""TPC-DS shapes shared by the plan files: the calendar, the seasons of the
sales dates, and the engine's table type built from plain arrays.

Everything here is the benchmark's own. Cardinalities come from the
configuration file; what the specification's text does not fix (the exact
season weights, where week 1 starts) is listed under `assumed` there.
"""
import numpy as np

D_DATE_SK0 = 2415022            # d_date_sk of 1900-01-02, the first row
N_DATES = 73049                 # 1900-01-02 .. 2100-01-01
SALES_YEARS = (1998, 1999, 2000, 2001, 2002)
JAN1_1998_SK = D_DATE_SK0 + int(
    (np.datetime64("1998-01-01") - np.datetime64("1900-01-02")).astype(int))
YEAR_UNITS = 212 * 1 + 92 * 2 + 61 * 3      # season weight units in a year
# dsdgen's sales calendar: January-July low, August-October medium,
# November-December high (relative weights per day; `assumed`)
SEASON_WEIGHT = {1: 1, 2: 1, 3: 1, 4: 1, 5: 1, 6: 1, 7: 1,
                 8: 2, 9: 2, 10: 2, 11: 3, 12: 3}


def date_dim() -> dict:
    """The whole date_dim with the real calendar, as int64 columns."""
    days = np.datetime64("1900-01-02") + np.arange(N_DATES)
    years = days.astype("datetime64[Y]").astype(np.int64) + 1970
    months = days.astype("datetime64[M]").astype(np.int64) % 12 + 1
    return {"d_date_sk": D_DATE_SK0 + np.arange(N_DATES, dtype=np.int64),
            "d_year": years.astype(np.int64),
            "d_moy": months.astype(np.int64),
            # week 1 holds 1900-01-02 .. 1900-01-07; a new week each Monday
            "d_week_seq": (np.arange(N_DATES, dtype=np.int64) + 1) // 7 + 1}


def draw(key, n: int, lo: int, hi: int):
    """n whole numbers in [lo, hi) as int64 (drawn in 32 bits: cheap)."""
    import jax
    import jax.numpy as jnp
    return jax.random.randint(key, (n,), lo, hi, dtype=jnp.int32) \
        .astype(jnp.int64)


def draw_sales_dates(key, n: int):
    """n sale dates (d_date_sk) on the device over the five sales years,
    each day weighed by its season. Arithmetic only: a table of days would
    cost a gather per row, and the draw must stay cheap beside the query.
    A year is 579 weight units: 212 days of 1, 92 of 2, 61 of 3. The leap
    day of 2000 gets no sales."""
    import jax
    import jax.numpy as jnp
    low, mid = 212, 212 + 2 * 92
    r = jax.random.randint(key, (n,), 0, len(SALES_YEARS) * YEAR_UNITS,
                           dtype=jnp.int32)
    y, u = r // YEAR_UNITS, r % YEAR_UNITS
    d = jnp.where(u < low, u, jnp.where(u < mid, low + (u - low) // 2,
                                        304 + (u - mid) // 3))
    leap = (y > 2).astype(jnp.int32) + ((y == 2) & (d >= 59))
    return (JAN1_1998_SK + 365 * y + d + leap).astype(jnp.int64)


def run_key(seed: int, stream: int):
    """A PRNG key from --seed (any whole number, above 2**31 too) and the
    number of the stream drawn from it (a request, or the cell's tables)."""
    import jax
    seed = int(seed)
    # the accelerator's own bit generator: the draw plays the scan's part
    # and must stay a small share of the device's time
    k = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, int(stream) & 0xFFFFFFFF)


def table(cols: dict, validity: dict = None, order=None):
    """The engine's Table over int64 arrays (device or host), its columns
    in `order` (a jitted generator hands its dict back sorted by name)."""
    import jax.numpy as jnp
    from spark_rapids_tpu import Column, Table, dtypes
    validity = validity or {}
    return Table([Column(dtype=dtypes.INT64, length=int(a.shape[0]),
                         data=jnp.asarray(a), validity=validity.get(n))
                  for n, a in ((n, cols[n]) for n in (order or cols))],
                 names=list(order or cols))
