"""Proleptic-Gregorian ↔ Julian calendar rebase for DATE / TIMESTAMP columns.

TPU-native re-design of the reference's datetime_rebase kernels
(src/main/cpp/src/datetime_rebase.cu): matches Spark's
`localRebaseGregorianToJulianDays` / `localRebaseJulianToGregorianDays` /
`rebaseGregorianToJulianMicros` / `rebaseJulianToGregorianMicros` (UTC).

The per-row chrono arithmetic (Howard Hinnant's civil/julian day algorithms,
datetime_rebase.cu:39-51,:107-125) is entirely branch-free integer math, so
each conversion is one fused elementwise XLA kernel over the column — no
scalar loops.

Key facts (datetime_rebase.cu):
- Gregorian start day = 1582-10-15 = day -141427 since epoch; values at/after
  it are unchanged.
- Dates in the 1582-10-05..14 gap (exist in neither calendar) rebase as if
  they were the gregorian start local date (→ -141427).
- Micros variants decompose into (days, time-of-day) with floor semantics for
  negative values, rebase the day, and reassemble (:228-:291).
"""
from __future__ import annotations

import jax.numpy as jnp

from ..columnar import Column
from ..dtypes import Kind

GREGORIAN_START_DAYS = -141427                    # 1582-10-15
LAST_SWITCH_GREGORIAN_MICROS = -12219292800000000  # 1582-10-15T00:00:00Z
MICROS_PER_SECOND = 1_000_000
SECONDS_PER_DAY = 86_400


def _civil_from_days(days):
    """days since 1970-01-01 -> (year, month, day) proleptic Gregorian."""
    z = days.astype(jnp.int64) + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = (z - era * 146097).astype(jnp.int64)                     # [0, 146096]
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365  # [0, 399]
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)                # [0, 365]
    mp = (5 * doy + 2) // 153                                      # [0, 11]
    d = doy - (153 * mp + 2) // 5 + 1                              # [1, 31]
    m = jnp.where(mp < 10, mp + 3, mp - 9)                         # [1, 12]
    return y + (m <= 2), m, d


def _days_from_civil(y, m, d):
    """(year, month, day) proleptic Gregorian -> days since 1970-01-01."""
    y = y - (m <= 2)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = (y - era * 400).astype(jnp.int64)                        # [0, 399]
    doy = (153 * jnp.where(m > 2, m - 3, m + 9) + 2) // 5 + d - 1  # [0, 365]
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy                  # [0, 146096]
    return era * 146097 + doe - 719468


def _days_from_julian(y, m, d):
    """(year, month, day) Julian calendar -> days since 1970-01-01
    (datetime_rebase.cu:39-51)."""
    year = y - (m <= 2)
    era = jnp.where(year >= 0, year, year - 3) // 4
    yoe = (year - era * 4).astype(jnp.int64)                       # [0, 3]
    doy = (153 * jnp.where(m > 2, m - 3, m + 9) + 2) // 5 + d - 1  # [0, 365]
    doe = yoe * 365 + doy                                          # [0, 1460]
    return era * 1461 + doe - 719470


def _julian_from_days(days):
    """days since epoch -> (year, month, day) Julian calendar
    (datetime_rebase.cu:107-125)."""
    z = days.astype(jnp.int64) + 719470
    era = jnp.where(z >= 0, z, z - 1460) // 1461
    doe = (z - era * 1461).astype(jnp.int64)                       # [0, 1460]
    yoe = (doe - doe // 1460) // 365                               # [0, 3]
    y = yoe + era * 4
    doy = doe - 365 * yoe                                          # [0, 365]
    mp = (5 * doy + 2) // 153                                      # [0, 11]
    d = doy - (153 * mp + 2) // 5 + 1                              # [1, 31]
    m = jnp.where(mp < 10, mp + 3, mp - 9)                         # [1, 12]
    return y + (m <= 2), m, d


def _in_calendar_gap(y, m, d):
    """True for local dates in 1582-10-05..14 (exist in neither calendar)."""
    return (y == 1582) & (m == 10) & (d >= 5) & (d <= 14)


def _greg_to_julian_days(days):
    y, m, d = _civil_from_days(days)
    rebased = jnp.where(_in_calendar_gap(y, m, d),
                        jnp.int64(GREGORIAN_START_DAYS),
                        _days_from_julian(y, m, d))
    return jnp.where(days >= GREGORIAN_START_DAYS, days.astype(jnp.int64), rebased)


def _julian_to_greg_days(days):
    y, m, d = _julian_from_days(days)
    rebased = _days_from_civil(y, m, d)
    return jnp.where(days >= GREGORIAN_START_DAYS, days.astype(jnp.int64), rebased)


def _split_micros(micros):
    """micros -> (days floor, micros-of-day) with negative-value floor
    semantics (datetime_rebase.cu get_time_components)."""
    micros = micros.astype(jnp.int64)
    day_us = jnp.int64(SECONDS_PER_DAY * MICROS_PER_SECOND)
    days = jnp.floor_divide(micros, day_us)
    tod = micros - days * day_us                                   # [0, day_us)
    return days, tod


def _rebase_micros(micros, day_fn):
    days, tod = _split_micros(micros)
    new_days = day_fn(days.astype(jnp.int32))
    out = new_days * jnp.int64(SECONDS_PER_DAY * MICROS_PER_SECOND) + tod
    return jnp.where(micros >= LAST_SWITCH_GREGORIAN_MICROS, micros, out)


def rebase_gregorian_to_julian(col: Column) -> Column:
    """Spark localRebaseGregorianToJulianDays / rebaseGregorianToJulianMicros
    (datetime_rebase.cu:345-358)."""
    if col.dtype.kind == Kind.DATE32:
        out = _greg_to_julian_days(col.data.astype(jnp.int32)).astype(jnp.int32)
    elif col.dtype.kind == Kind.TIMESTAMP_US:
        out = _rebase_micros(col.data, _greg_to_julian_days)
    else:
        raise TypeError(
            "The input must be either day or microsecond timestamps to rebase.")
    return Column(dtype=col.dtype, length=col.length, data=out,
                  validity=col.validity)


def rebase_julian_to_gregorian(col: Column) -> Column:
    """Spark localRebaseJulianToGregorianDays / rebaseJulianToGregorianMicros
    (datetime_rebase.cu:360-373)."""
    if col.dtype.kind == Kind.DATE32:
        out = _julian_to_greg_days(col.data.astype(jnp.int32)).astype(jnp.int32)
    elif col.dtype.kind == Kind.TIMESTAMP_US:
        out = _rebase_micros(col.data, _julian_to_greg_days)
    else:
        raise TypeError(
            "The input must be either day or microsecond timestamps to rebase.")
    return Column(dtype=col.dtype, length=col.length, data=out,
                  validity=col.validity)
