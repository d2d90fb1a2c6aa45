"""Exchange transport layer: packed columnar wire format for the
distributed tier (docs/distributed.md#transport).

Every exchange used to ship raw per-column device arrays — one buffer per
column plus one full bool plane per nullable column — so shuffle cost
scaled with the relation's logical width rather than its information
content. This module packs each exchange payload into dense typed planes
with lightweight per-column encodings, chosen by cheap inspection and
with a STRICT pass-through whenever encoding would not pay (Thallus'
RDMA columnar batches and "Accelerating Presto with GPUs", PAPERS.md,
both ground the dense-batch + cheap-encoding design):

- **frame-of-reference (``for8/16/32``)** — an integer column whose live
  value range fits a narrower unsigned width ships as ``value - lo``
  in that width plus one static reference; exact for every live value.
  Static-shape, so it rides INSIDE the SPMD collectives (hash/range
  all-to-alls, sharded broadcasts).
- **bit-packed validity (``bitpack``)** — the nullable columns' bool
  planes (one byte per row each) collapse into one validity bit-word
  plane per 8 columns (one byte per row total). Also static-shape.
- **dictionary (``dict8/16``)** — a column with few distinct values
  ships as narrow codes plus a value table; **run-length (``rle``)** —
  a sorted/low-cardinality column ships as (values, run lengths). Both
  are dynamic-size, so they apply only where the payload is already
  host-materialized: the local build side of a broadcast join
  (`pack_host`), never inside a jitted collective.

Two accounting truths ride every packed edge (`plan/metrics.py`):
``exchange_bytes_logical`` — the unpacked per-column payload bytes the
edge represents (data itemsize + one validity byte per nullable column,
live rows only, each edge counted once) — and ``exchange_bytes`` (the
wire form): the packed bytes actually shipped. Exchange METADATA (live
masks, bucket counts, FOR references, dictionary/run side tables small
enough to ride the program) is not counted in either, the same
convention as the shuffle's `sent` counts. The static certifier's
per-edge payload bounds (analysis/footprint.py) are proven against the
wire form, so `wire <= certified hi` is a checkable inequality
(`footprint.check_observed`).

Knobs (config.py, read by the distributed tier at execution setup):
SPARK_RAPIDS_TPU_EXCHANGE_PACK (on/off), _EXCHANGE_CODECS
(auto/none/csv subset of for,dict,rle,bitpack), _EXCHANGE_ASYNC
(overlap exchange pack+transfer with downstream compute — see
plan/distributed.py). Pack off restores the byte-identical legacy
payload layout.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .. import dtypes
from ..columnar import Column

ALL_CODECS = frozenset({"for", "dict", "rle", "bitpack"})

__all__ = ["ALL_CODECS", "DevicePack", "HostPacked", "WordPlan",
           "logical_col_bytes", "logical_row_bytes", "narrow_words",
           "widen_words", "pack_device", "unpack_device",
           "unpack_device_np", "pack_host", "unpack_host",
           "unpack_host_device", "pack_bits_device", "unpack_bits_np"]


# ---- logical (unpacked) accounting ------------------------------------------

def logical_col_bytes(col: Column) -> int:
    """Unpacked payload bytes per row for one fixed-width column: the data
    itemsize plus one bool byte when a validity plane rides along."""
    return col.dtype.itemsize() + (1 if col.validity is not None else 0)


def logical_row_bytes(cols: Sequence[Column]) -> int:
    return sum(logical_col_bytes(c) for c in cols)


# ---- device-side static-shape packing (collective edges) --------------------

@dataclasses.dataclass(frozen=True)
class _ColPlan:
    """Static decode recipe for one packed column."""
    name: str
    dtype: dtypes.DType
    codec: str                  # "raw" | "for8" | "for16" | "for32"
    ref: int                    # frame-of-reference lo (exact python int)
    plane: int                  # data plane index
    vplane: int                 # validity plane index (-1: non-nullable)
    vbit: int                   # bit within a packed validity word
    #                             (-1: the validity plane is a raw bool)


@dataclasses.dataclass
class DevicePack:
    """One packed payload: `planes` are equal-length 1-D device arrays
    that ride a collective (or a host pull) in place of the raw columns;
    `plans` rebuild the columns. Byte fields are PER ROW."""
    plans: Tuple[_ColPlan, ...]
    planes: List
    n_planes: int
    wire_row_bytes: int
    logical_row_bytes: int
    codec_str: str


_FOR_TARGETS = ((8, jnp.uint8), (16, jnp.uint16), (32, jnp.uint32))


def _for_probe(col: Column, live):
    """Cheap inspection for frame-of-reference narrowing: one masked
    min/max reduce (two 8-byte host syncs) decides whether the column's
    LIVE value range fits a narrower unsigned plane. Returns (plane, lo,
    codec) or None (pass-through). Null slots are excluded from the
    range — their data is sentinel garbage no consumer reads."""
    st = np.dtype(col.data.dtype)
    if st.kind not in "iu" or st.itemsize < 2 or col.data.shape[0] == 0:
        return None
    mask = live if col.validity is None else (live & col.validity)
    info = jnp.iinfo(col.data.dtype)
    lo = int(jnp.min(jnp.where(mask, col.data, info.max)))
    hi = int(jnp.max(jnp.where(mask, col.data, info.min)))
    if lo > hi:         # no live rows: nothing to prove a range over
        return None
    if lo < -(1 << 63) or lo >= (1 << 63):
        # the reference must be an exact int64 (unsigned storage can
        # exceed it): pass through rather than wrap
        return None
    span = hi - lo
    for bits, tgt in _FOR_TARGETS:
        if bits // 8 >= st.itemsize:
            break
        if span < (1 << bits):
            plane = (col.data.astype(jnp.int64) - lo).astype(tgt)
            return plane, lo, f"for{bits}"
    return None


def pack_device(cols: Sequence[Column], names: Sequence[str], live,
                codecs: frozenset) -> DevicePack:
    """Pack fixed-width 1-D columns into dense wire planes with the
    static-shape codecs (FOR narrowing + bit-packed validity). `live` is
    the relation's live-row mask (the FOR inspection domain); the planes
    keep the input length — dead slots carry wrapped garbage that decode
    reproduces as garbage (never read). Pure pass-through (all-raw, raw
    bool validity planes) when `codecs` allows nothing."""
    planes: List = []
    plans: List[_ColPlan] = []
    notes: List[str] = []
    wire = 0
    logical = 0
    nullable: List[int] = []        # indices into `plans`
    for name, c in zip(names, cols):
        logical += logical_col_bytes(c)
        plane, ref, codec = c.data, 0, "raw"
        if "for" in codecs:
            probe = _for_probe(c, live)
            if probe is not None:
                plane, ref, codec = probe
                notes.append(f"{name}:{codec}")
        idx = len(planes)
        planes.append(plane)
        wire += np.dtype(plane.dtype).itemsize
        plans.append(_ColPlan(name=name, dtype=c.dtype, codec=codec,
                              ref=ref, plane=idx, vplane=-1, vbit=-1))
        if c.validity is not None:
            nullable.append(len(plans) - 1)
    if nullable and "bitpack" in codecs and len(nullable) >= 2:
        # one uint8 bit-word plane per 8 nullable columns, replacing one
        # full bool plane each
        for chunk0 in range(0, len(nullable), 8):
            chunk = nullable[chunk0:chunk0 + 8]
            word = jnp.zeros(live.shape, jnp.uint8)
            for bit, pi in enumerate(chunk):
                v = cols[pi].validity
                word = word | (v.astype(jnp.uint8) << np.uint8(bit))
                plans[pi] = dataclasses.replace(plans[pi],
                                                vplane=len(planes),
                                                vbit=bit)
            planes.append(word)
            wire += 1
        notes.append("validity:bitpack")
    else:
        for pi in nullable:
            plans[pi] = dataclasses.replace(plans[pi], vplane=len(planes),
                                            vbit=-1)
            planes.append(cols[pi].validity)
            wire += 1
    return DevicePack(plans=tuple(plans), planes=planes,
                      n_planes=len(planes), wire_row_bytes=wire,
                      logical_row_bytes=logical,
                      codec_str=",".join(notes))


def unpack_device(arrays: Sequence, pack: DevicePack) -> List[Column]:
    """Wire planes (post-collective) back to typed columns — the
    receiving shard's decode. Eager jnp elementwise; sharding/replication
    of the input planes propagates."""
    if not pack.plans:          # key-only payload: nothing rode along
        return []
    n = int(arrays[0].shape[0])
    out: List[Column] = []
    for p in pack.plans:
        raw = arrays[p.plane]
        if p.codec.startswith("for"):
            data = (jnp.int64(p.ref) + raw.astype(jnp.int64)).astype(
                p.dtype.storage_dtype())
        else:
            data = raw.astype(p.dtype.storage_dtype())
        validity = None
        if p.vplane >= 0:
            vp = arrays[p.vplane]
            if p.vbit >= 0:
                validity = ((vp >> np.uint8(p.vbit)) & np.uint8(1)) \
                    .astype(jnp.bool_)
            else:
                validity = vp.astype(jnp.bool_)
        out.append(Column(dtype=p.dtype, length=n, data=data,
                          validity=validity))
    return out


def unpack_device_np(arrays: Sequence[np.ndarray], pack: DevicePack
                     ) -> List[Tuple[np.ndarray, Optional[np.ndarray]]]:
    """Numpy mirror of `unpack_device` for host-pulled planes (the packed
    gather): returns [(data, validity-or-None)] full-length arrays."""
    out = []
    for p in pack.plans:
        raw = arrays[p.plane]
        if p.codec.startswith("for"):
            data = (p.ref + raw.astype(np.int64)).astype(
                np.dtype(p.dtype.storage_dtype()))
        else:
            data = raw
        validity = None
        if p.vplane >= 0:
            vp = arrays[p.vplane]
            validity = (((vp >> p.vbit) & 1) if p.vbit >= 0 else vp) \
                .astype(bool)
        out.append((data, validity))
    return out


# ---- key-word narrowing (hash-exchange edges) -------------------------------

@dataclasses.dataclass(frozen=True)
class WordPlan:
    """Static decode recipe for one key-word plane of a hash exchange
    (the 64-bit order-preserving words of parallel/keys.py). `codec` is
    "raw" (the plane ships as its int64 word) or "forN" (it ships as
    `word - ref` in the narrow unsigned width); `ref` is an exact
    Python int. `nbytes` is the plane's wire bytes per row."""
    codec: str
    ref: int
    nbytes: int


def narrow_words(words: Sequence, live
                 ) -> Tuple[List, Tuple[WordPlan, ...], int, str]:
    """FOR-narrow the int64 key-word planes a hash exchange ships.

    Key columns used to ride hash edges at a flat 8 B per word (the
    "never narrowed" remainder of the packed wire format): the words are
    the HASH input, and the Spark-exact murmur must see them at full
    width inside the collective body. Narrowing is still sound because
    the hash input and the wire form need not be the same arrays — the
    exchange widens each narrowed plane back to its exact word
    (`ref + narrow.astype(int64)`) for the hash, then ships the narrow
    plane (parallel/relational.distributed_repartition_keyed). Placement
    is bit-identical; only the wire narrows.

    Same inspection discipline as `_for_probe`: one masked min/max
    reduce per plane over the LIVE rows — eager reduces over sharded
    arrays are global, so every shard derives the same reference — with
    exact reconstruction for every live slot (null-key rows' data words
    are zeroed at encode time, so they sit inside the probed range).
    Dead slots ship wrapped garbage no consumer reads (decode zeroes
    them under the alive mask). Null-flag words (0/1) narrow to one
    byte for free. The certifier keeps pricing key words at 8 B each
    (analysis/footprint.py) — a sound hi-bound the narrowed wire only
    ever undershoots.

    Returns (planes, plans, wire_bytes_per_row, codec_note); an all-raw
    outcome returns the input planes and an empty note."""
    planes: List = []
    plans: List[WordPlan] = []
    notes: List[str] = []
    wire = 0
    info = jnp.iinfo(jnp.int64)
    for i, w in enumerate(words):
        plan = WordPlan("raw", 0, 8)
        plane = w
        if w.shape[0]:
            lo = int(jnp.min(jnp.where(live, w, info.max)))
            hi = int(jnp.max(jnp.where(live, w, info.min)))
            if lo <= hi:                # any live rows at all
                span = hi - lo          # exact (host ints)
                for bits, tgt in _FOR_TARGETS:
                    if span < (1 << bits):
                        plane = (w - jnp.int64(lo)).astype(tgt)
                        plan = WordPlan(f"for{bits}", lo, bits // 8)
                        notes.append(f"key{i}:for{bits}")
                        break
        planes.append(plane)
        plans.append(plan)
        wire += plan.nbytes
    return planes, tuple(plans), wire, ",".join(notes)


def widen_words(planes: Sequence, plans: Sequence[WordPlan]) -> List:
    """Inverse of `narrow_words` for RECEIVED planes (outside the
    collective): each narrowed plane back to its exact int64 word array.
    Dead slots widen to garbage no consumer reads — the relation's
    alive mask owns liveness, and key decode zeroes dead words."""
    return [p if wp.codec == "raw"
            else (jnp.int64(wp.ref) + p.astype(jnp.int64))
            for p, wp in zip(planes, plans)]


def pack_bits_device(mask) -> Tuple[object, int]:
    """Bit-pack a (n,) bool device array column-wise into a uint8 plane of
    ceil(n/8) bytes (the packed gather's live-mask wire form). Returns
    (plane, n)."""
    n = int(mask.shape[0])
    pad = (-n) % 8
    m = mask.astype(jnp.uint8)
    if pad:
        m = jnp.concatenate([m, jnp.zeros((pad,), jnp.uint8)])
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))[None, :]
    return jnp.sum(m.reshape(-1, 8) * weights, axis=1,
                   dtype=jnp.uint8), n


def unpack_bits_np(plane: np.ndarray, n: int) -> np.ndarray:
    bits = np.unpackbits(np.asarray(plane, np.uint8), bitorder="little")
    return bits[:n].astype(bool)


# ---- host-side codecs (materialized edges) ----------------------------------

@dataclasses.dataclass
class _HostColPlan:
    name: str
    dtype: dtypes.DType
    codec: str                        # raw | forN | dictN | rle
    ref: int
    data: Optional[np.ndarray]        # raw/for plane or dict codes
    values: Optional[np.ndarray]      # dict/rle value table
    lengths: Optional[np.ndarray]     # rle run lengths (int32)
    validity: Optional[np.ndarray]    # packbits bitmask or raw bool
    vpacked: bool


@dataclasses.dataclass
class HostPacked:
    """A host-materialized payload in wire form (the broadcast build
    side). `wire_bytes`/`logical_bytes` cover the WHOLE payload once
    (multiply by peers-1 for a broadcast)."""
    n: int
    cols: List[_HostColPlan]
    names: Tuple[str, ...]
    wire_bytes: int
    logical_bytes: int
    codec_str: str


def _host_encode_int(a: np.ndarray, codecs: frozenset):
    """Pick the cheapest host codec for one integer array by exact byte
    comparison; strict pass-through when nothing is smaller than raw.
    Returns (codec, data, values, lengths, ref, wire_bytes)."""
    n = a.shape[0]
    item = a.dtype.itemsize
    raw = n * item
    best = ("raw", a, None, None, 0, raw)
    if n == 0:
        return best
    if "rle" in codecs:
        bounds = np.empty(n, bool)
        bounds[0] = True
        np.not_equal(a[1:], a[:-1], out=bounds[1:])
        starts = np.nonzero(bounds)[0]
        runs = starts.shape[0]
        rle_bytes = runs * (item + 4)
        if rle_bytes < best[5]:
            lengths = np.diff(np.append(starts, n)).astype(np.int32)
            best = ("rle", None, a[starts], lengths, 0, rle_bytes)
    if "dict" in codecs:
        uniq = np.unique(a)
        for bits, ct in ((8, np.uint8), (16, np.uint16)):
            if uniq.shape[0] <= (1 << bits):
                d_bytes = n * (bits // 8) + uniq.nbytes
                if d_bytes < best[5]:
                    codes = np.searchsorted(uniq, a).astype(ct)
                    best = (f"dict{bits}", codes, uniq, None, 0, d_bytes)
                break
    if "for" in codecs and item >= 2:
        lo, hi = int(a.min()), int(a.max())
        span = hi - lo
        for bits, ct in ((8, np.uint8), (16, np.uint16), (32, np.uint32)):
            if bits // 8 >= item:
                break
            if span < (1 << bits):
                f_bytes = n * (bits // 8)
                if f_bytes < best[5]:
                    best = (f"for{bits}",
                            (a.astype(np.int64) - lo).astype(ct),
                            None, None, lo, f_bytes)
                break
    return best


def pack_host(cols: Sequence[Column], names: Sequence[str],
              codecs: frozenset) -> HostPacked:
    """Encode a host-materializable table payload (dynamic-size codecs
    allowed — the payload is concrete). Lossless for every slot,
    including null-slot data (codecs encode the actual values)."""
    out: List[_HostColPlan] = []
    notes: List[str] = []
    wire = 0
    logical = 0
    n = int(cols[0].length) if cols else 0
    for name, c in zip(names, cols):
        logical += logical_col_bytes(c) * n
        a = np.asarray(c.data)
        codec, data, values, lengths, ref = "raw", a, None, None, 0
        if np.dtype(a.dtype).kind in "iu" and c.dtype.kind != dtypes.Kind.BOOL:
            codec, data, values, lengths, ref, _ = \
                _host_encode_int(a, codecs)
        wire += sum(x.nbytes for x in (data, values, lengths)
                    if x is not None)
        if codec != "raw":
            notes.append(f"{name}:{codec}")
        validity, vpacked = None, False
        if c.validity is not None:
            v = np.asarray(c.validity)
            if "bitpack" in codecs:
                validity = np.packbits(v, bitorder="little")
                vpacked = True
            else:
                validity = v
            wire += validity.nbytes
        out.append(_HostColPlan(name=name, dtype=c.dtype, codec=codec,
                                ref=ref, data=data, values=values,
                                lengths=lengths, validity=validity,
                                vpacked=vpacked))
    if any(p.vpacked for p in out):
        notes.append("validity:bitpack")
    return HostPacked(n=n, cols=out, names=tuple(names), wire_bytes=wire,
                      logical_bytes=logical, codec_str=",".join(notes))


def _host_decode_np(p: _HostColPlan) -> np.ndarray:
    if p.codec == "raw":
        return p.data
    if p.codec.startswith("for"):
        return (p.ref + p.data.astype(np.int64)).astype(
            np.dtype(p.dtype.storage_dtype()))
    if p.codec.startswith("dict"):
        return p.values[p.data]
    if p.codec == "rle":
        return np.repeat(p.values, p.lengths)
    raise ValueError(f"unknown host codec {p.codec!r}")


def unpack_host(packed: HostPacked) -> List[Column]:
    """Pure-numpy round trip (tests + host-side consumers)."""
    out = []
    for p in packed.cols:
        data = _host_decode_np(p)
        validity = None
        if p.validity is not None:
            v = unpack_bits_np(p.validity, packed.n) if p.vpacked \
                else p.validity.astype(bool)
            validity = jnp.asarray(v)
        out.append(Column(dtype=p.dtype, length=packed.n,
                          data=jnp.asarray(data), validity=validity))
    return out


def unpack_host_device(packed: HostPacked, put) -> List[Column]:
    """Decode a HostPacked payload ON DEVICE: `put` lifts each wire plane
    (e.g. `jax.device_put(..., replicated)`), and the decode runs as
    eager jnp over the lifted planes, so the decoded columns keep the
    planes' placement — the broadcast's 'unpack on the receiving shard'.
    """
    out = []
    for p in packed.cols:
        st = p.dtype.storage_dtype()
        if p.codec == "raw":
            data = put(jnp.asarray(p.data))
        elif p.codec.startswith("for"):
            data = (jnp.int64(p.ref)
                    + put(jnp.asarray(p.data)).astype(jnp.int64)).astype(st)
        elif p.codec.startswith("dict"):
            data = jnp.take(put(jnp.asarray(p.values)),
                            put(jnp.asarray(p.data)).astype(jnp.int32),
                            axis=0)
        elif p.codec == "rle":
            data = jnp.repeat(put(jnp.asarray(p.values)),
                              put(jnp.asarray(p.lengths)),
                              total_repeat_length=packed.n)
        else:
            raise ValueError(f"unknown host codec {p.codec!r}")
        validity = None
        if p.validity is not None:
            if p.vpacked:
                vp = put(jnp.asarray(p.validity))
                idx = jnp.arange(packed.n, dtype=jnp.int32)
                validity = ((jnp.take(vp, idx >> 3, axis=0)
                             >> (idx & 7).astype(jnp.uint8))
                            & np.uint8(1)).astype(jnp.bool_)
            else:
                validity = put(jnp.asarray(p.validity)).astype(jnp.bool_)
        out.append(Column(dtype=p.dtype, length=packed.n, data=data,
                          validity=validity))
    return out


# ---- codec-set resolution ---------------------------------------------------

def resolve_codecs(spec: str) -> frozenset:
    """Config string -> codec set: 'auto' = all, 'none' = layout-only
    pass-through (no per-column encodings, raw validity planes), else a
    comma list validated against the catalog (strict-typo policy)."""
    if spec == "auto":
        return ALL_CODECS
    if spec == "none":
        return frozenset()
    chosen = frozenset(s.strip() for s in spec.split(",") if s.strip())
    unknown = chosen - ALL_CODECS
    if unknown:
        raise ValueError(
            f"unknown exchange codec(s) {sorted(unknown)} "
            f"(expected a subset of {sorted(ALL_CODECS)}, 'auto', or "
            "'none')")
    return chosen
