"""Timestamp <-> UTC timezone conversion (GpuTimeZoneDB equivalent).

Reference: /root/reference/src/main/java/com/nvidia/spark/rapids/jni/
GpuTimeZoneDB.java (transition-table construction, loadData :261-335; cached
singleton with async load :88-202; supported = fixed-offset or no recurring
DST rules :236-248; Spark zone-id normalization :251-258) and
/root/reference/src/main/cpp/src/timezones.cu (per-row upper_bound over the
zone's transition span, convert_timestamp_tz_functor :50-90).

TPU-native design: the host half parses TZif files (RFC 8536) directly from
the system tzdata — the role java.time.ZoneRules plays in the reference —
and builds, per supported zone, three dense arrays:

    utc_instants  int64 seconds   (search key when converting UTC -> zone)
    tz_instants   int64 seconds   (search key when converting zone -> UTC)
    offsets       int32 seconds   (offset *after* each transition)

Row 0 is the (INT64_MIN, INT64_MIN, first-standard-offset) sentinel exactly
like GpuTimeZoneDB.java:284-295.  Gap transitions store
(instant, instant + offsetAfter, offsetAfter); overlaps store
(instant, instant + offsetBefore, offsetAfter) — the Spark disambiguation
rule documented at GpuTimeZoneDB.java:296-318.

The device half is one fused XLA kernel: truncate the timestamp to epoch
seconds (duration_cast semantics, timezones.cu:74-76), vectorized
`jnp.searchsorted(side="right")` over the zone's span, gather the offset,
add/subtract.  Zone spans are padded to power-of-two buckets (INT64_MAX
sentinel) so jit recompiles stay bounded.
"""
from __future__ import annotations

import dataclasses
import os
import re
import struct
import threading
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes
from ..columnar.column import Column, _round_bucket

INT64_MIN = -(1 << 63)
INT64_MAX = (1 << 63) - 1

# java.time.ZoneId.SHORT_IDS — applied by the reference's getZoneId
# (GpuTimeZoneDB.java:257 passes ZoneId.SHORT_IDS).
SHORT_IDS = {
    "ACT": "Australia/Darwin", "AET": "Australia/Sydney",
    "AGT": "America/Argentina/Buenos_Aires", "ART": "Africa/Cairo",
    "AST": "America/Anchorage", "BET": "America/Sao_Paulo",
    "BST": "Asia/Dhaka", "CAT": "Africa/Harare", "CNT": "America/St_Johns",
    "CST": "America/Chicago", "CTT": "Asia/Shanghai",
    "EAT": "Africa/Addis_Ababa", "ECT": "Europe/Paris",
    "IET": "America/Indiana/Indianapolis", "IST": "Asia/Kolkata",
    "JST": "Asia/Tokyo", "MIT": "Pacific/Apia", "NET": "Asia/Yerevan",
    "NST": "Pacific/Auckland", "PLT": "Asia/Karachi",
    "PNT": "America/Phoenix", "PRT": "America/Puerto_Rico",
    "PST": "America/Los_Angeles", "SST": "Pacific/Guadalcanal",
    "VST": "Asia/Ho_Chi_Minh",
    "EST": "-05:00", "MST": "-07:00", "HST": "-10:00",
}

_TZPATHS = ("/usr/share/zoneinfo", "/usr/lib/zoneinfo",
            "/usr/share/lib/zoneinfo", "/etc/zoneinfo")


# ---------------------------------------------------------------------------
# TZif parsing (host side; RFC 8536)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _TzifData:
    trans_times: List[int]        # transition instants, UTC seconds
    trans_types: List[int]        # index into utoffs per transition
    utoffs: List[int]             # seconds east of UTC per local time type
    isdsts: List[bool]
    footer: str                   # POSIX TZ string ('' if none / v1)


def _parse_tzif(path: str) -> _TzifData:
    with open(path, "rb") as f:
        raw = f.read()

    def parse_block(buf, off, time_size):
        magic, version = struct.unpack_from(">4sc", buf, off)
        if magic != b"TZif":
            raise ValueError(f"{path}: not a TZif file")
        isutcnt, isstdcnt, leapcnt, timecnt, typecnt, charcnt = \
            struct.unpack_from(">6I", buf, off + 20)
        p = off + 44
        fmt = ">%d%s" % (timecnt, "q" if time_size == 8 else "l")
        trans = list(struct.unpack_from(fmt, buf, p)) if timecnt else []
        p += timecnt * time_size
        types = list(struct.unpack_from(">%dB" % timecnt, buf, p)) if timecnt else []
        p += timecnt
        utoffs, isdsts = [], []
        for i in range(typecnt):
            utoff, isdst, _desig = struct.unpack_from(">lBB", buf, p + 6 * i)
            utoffs.append(utoff)
            isdsts.append(bool(isdst))
        p += 6 * typecnt + charcnt
        p += leapcnt * (time_size + 4) + isstdcnt + isutcnt
        return version, trans, types, utoffs, isdsts, p

    version, trans, types, utoffs, isdsts, end = parse_block(raw, 0, 4)
    footer = ""
    if version != b"\x00":
        # v2+: a second, 64-bit data block follows, then the footer TZ string.
        _, trans, types, utoffs, isdsts, end = parse_block(raw, end, 8)
        nl1 = raw.index(b"\n", end)
        nl2 = raw.index(b"\n", nl1 + 1)
        footer = raw[nl1 + 1:nl2].decode("ascii", errors="replace")
    return _TzifData(trans, types, utoffs, isdsts, footer)


def _zone_is_supported(tz: _TzifData) -> bool:
    """Reference supported-set rule (GpuTimeZoneDB.java:236-240): fixed
    offset, or rules with no *recurring* transition rule.  A TZif footer with
    a ',' carries a recurring DST rule; without one the zone is frozen."""
    return "," not in tz.footer


def _build_transition_rows(tz: _TzifData) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Build (utc_instants, tz_instants, offsets) per GpuTimeZoneDB.loadData."""
    utc, loc, off = [INT64_MIN], [INT64_MIN], []
    if not tz.trans_times:
        # fixed-offset zone: single sentinel row with the lone offset
        # (GpuTimeZoneDB.java:284-288)
        off.append(tz.utoffs[0] if tz.utoffs else 0)
    else:
        # Offset in force before the first transition: first standard
        # (non-DST) type, falling back to type 0 — the tzfile(5) convention,
        # which matches java.time's initial standard offset.
        before = next((u for u, d in zip(tz.utoffs, tz.isdsts) if not d),
                      tz.utoffs[0])
        off.append(before)
        for t, ty in zip(tz.trans_times, tz.trans_types):
            after = tz.utoffs[ty]
            if after > before:   # gap (clocks jump forward) — java isGap()
                utc.append(t)
                loc.append(t + after)
            else:                # overlap: compare against instant+offsetBefore
                utc.append(t)
                loc.append(t + before)
            off.append(after)
            before = after
    return (np.array(utc, dtype=np.int64), np.array(loc, dtype=np.int64),
            np.array(off, dtype=np.int32))


# ---------------------------------------------------------------------------
# Zone-id resolution (Spark/java.time surface)
# ---------------------------------------------------------------------------

_OFFSET_RE = re.compile(
    r"^(?P<sign>[+-])(?P<h>\d{1,2})(?::?(?P<m>\d{2})(?::?(?P<s>\d{2}))?)?$")


def normalize_zone_id(tz_str: str) -> str:
    """Spark's pre-3.0 zone-id fixups (GpuTimeZoneDB.getZoneId :251-258)."""
    tz_str = re.sub(r"(\+|\-)(\d):", r"\g<1>0\g<2>:", tz_str, count=1)
    tz_str = re.sub(r"(\+|\-)(\d\d):(\d)$", r"\g<1>\g<2>:0\g<3>", tz_str, count=1)
    return tz_str


def _resolve_zone(tz_str: str):
    """Return ('fixed', offset_seconds) or ('region', canonical_path_id)."""
    s = normalize_zone_id(tz_str.strip())
    s = SHORT_IDS.get(s, s)
    if s in ("Z", "UTC", "GMT", "UT", "Etc/UTC", "Etc/GMT"):
        return ("fixed", 0)
    for prefix in ("UTC", "GMT", "UT"):
        if s.startswith(prefix) and len(s) > len(prefix):
            s = s[len(prefix):]
            break
    m = _OFFSET_RE.match(s)
    if m:
        mins, secs = int(m.group("m") or 0), int(m.group("s") or 0)
        if mins > 59 or secs > 59:  # ZoneOffset.of rejects +08:99 etc.
            raise ValueError(f"invalid zone offset: {tz_str}")
        total = int(m.group("h")) * 3600 + mins * 60 + secs
        if total > 18 * 3600:  # java.time limit: +/-18:00 total
            raise ValueError(f"zone offset out of range: {tz_str}")
        return ("fixed", -total if m.group("sign") == "-" else total)
    for root in _TZPATHS:
        path = os.path.join(root, s)
        if os.path.isfile(path):
            return ("region", s)
    raise ValueError(f"unknown time zone: {tz_str}")


# ---------------------------------------------------------------------------
# The database singleton
# ---------------------------------------------------------------------------

class TimeZoneDB:
    """Cached transition database (reference's GpuTimeZoneDB singleton,
    GpuTimeZoneDB.java:60-202: idempotent cache, async load, shutdown)."""

    _instance: Optional["TimeZoneDB"] = None
    _lock = threading.Lock()

    def __init__(self):
        # zone id -> (utc_instants, tz_instants, offsets) numpy triple
        self._tables: Dict[str, Tuple[np.ndarray, np.ndarray, np.ndarray]] = {}
        # per-zone device-resident padded arrays, keyed by resolved id
        self._device: Dict[str, Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]] = {}
        self._table_lock = threading.Lock()

    # -- lifecycle ----------------------------------------------------------
    @classmethod
    def instance(cls) -> "TimeZoneDB":
        with cls._lock:
            if cls._instance is None:
                cls._instance = TimeZoneDB()
            return cls._instance

    @classmethod
    def cache_database(cls) -> "TimeZoneDB":
        return cls.instance()

    @classmethod
    def cache_database_async(cls) -> threading.Thread:
        t = threading.Thread(target=cls.cache_database, daemon=True,
                             name="tpu-tzdb-loader")
        t.start()
        return t

    @classmethod
    def shutdown(cls):
        """Drop the cached database; a later cache_database() reloads it
        (reference shutdown/restart protocol, GpuTimeZoneDB.java:161-176)."""
        with cls._lock:
            cls._instance = None

    # -- table access -------------------------------------------------------
    def _table_for(self, tz_str: str):
        kind, key = _resolve_zone(tz_str)
        cache_key = f"fixed:{key}" if kind == "fixed" else key
        with self._table_lock:
            if cache_key in self._tables:
                return cache_key, self._tables[cache_key]
            if kind == "fixed":
                rows = (np.array([INT64_MIN], np.int64),
                        np.array([INT64_MIN], np.int64),
                        np.array([key], np.int32))
            else:
                path = next(os.path.join(r, key) for r in _TZPATHS
                            if os.path.isfile(os.path.join(r, key)))
                tz = _parse_tzif(path)
                if not _zone_is_supported(tz):
                    raise ValueError(f"Unsupported timezone: {tz_str}")
                rows = _build_transition_rows(tz)
            self._tables[cache_key] = rows
            return cache_key, rows

    def _device_table_for(self, tz_str: str):
        key, (utc, loc, off) = self._table_for(tz_str)
        with self._table_lock:
            if key not in self._device:
                # pad to power-of-two bucket so jit shapes are bounded
                pad = _round_bucket(len(off)) - len(off)
                utc_p = np.concatenate([utc, np.full(pad, INT64_MAX, np.int64)])
                loc_p = np.concatenate([loc, np.full(pad, INT64_MAX, np.int64)])
                off_p = np.concatenate([off, np.full(pad, off[-1], np.int32)])
                self._device[key] = (jnp.asarray(utc_p), jnp.asarray(loc_p),
                                     jnp.asarray(off_p))
            return self._device[key]


def is_supported_time_zone(tz_str: str) -> bool:
    try:
        TimeZoneDB.instance()._table_for(tz_str)
        return True
    except ValueError:
        return False


# ---------------------------------------------------------------------------
# Device kernel
# ---------------------------------------------------------------------------

_SCALES = {
    dtypes.Kind.TIMESTAMP_S: 1,
    dtypes.Kind.TIMESTAMP_MS: 1_000,
    dtypes.Kind.TIMESTAMP_US: 1_000_000,
}


@partial(jax.jit, static_argnames=("to_utc", "scale"))
def _convert_kernel(ts, trans_times, offsets, *, to_utc: bool, scale: int):
    ts = ts.astype(jnp.int64)
    # epoch seconds with C++ duration_cast truncation-toward-zero
    # (timezones.cu:74-76)
    q = ts // scale
    r = ts - q * scale
    epoch_s = q + jnp.where((ts < 0) & (r != 0), jnp.int64(1), jnp.int64(0))
    idx = jnp.searchsorted(trans_times, epoch_s, side="right")
    off = offsets[idx - 1].astype(jnp.int64) * scale
    return ts - off if to_utc else ts + off


def _convert(column: Column, tz_str: str, to_utc: bool) -> Column:
    if column.dtype.kind not in _SCALES:
        raise TypeError(f"expected a timestamp column, got {column.dtype}")
    db = TimeZoneDB.cache_database()
    utc_i, tz_i, offs = db._device_table_for(tz_str)
    keys = tz_i if to_utc else utc_i
    out = _convert_kernel(column.data, keys, offs, to_utc=to_utc,
                          scale=_SCALES[column.dtype.kind])
    return Column(dtype=column.dtype, length=column.length, data=out,
                  validity=column.validity)


def from_timestamp_to_utc_timestamp(column: Column, tz_str: str) -> Column:
    """Interpret `column` as wall-clock time in `tz_str`; return UTC instants
    (GpuTimeZoneDB.fromTimestampToUtcTimestamp :204-217)."""
    return _convert(column, tz_str, to_utc=True)


def from_utc_timestamp_to_timestamp(column: Column, tz_str: str) -> Column:
    """Convert UTC instants to wall-clock time in `tz_str`
    (GpuTimeZoneDB.fromUtcTimestampToTimestamp :219-232)."""
    return _convert(column, tz_str, to_utc=False)
