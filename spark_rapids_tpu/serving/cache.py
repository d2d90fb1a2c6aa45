"""Plan-result cache for the serving layer (docs/serving.md).

Identical traffic is the cheapest traffic: under multi-tenant load the
same dashboard/report plans arrive over and over against unchanged data,
and every repeat admission re-pays optimize + certify + execute. This
module keys a completed `PlanResult` by

    (canonical plan fingerprint, input-data digest)

— the same `optimizer.plan_fingerprint` canonical structural hash the
compiled-program cache shares (structurally identical plans built
independently hit together), crossed with a digest of the DATA the plan
was bound to. A fingerprint alone must never serve: the same plan over
new rows is a different answer, so the digest covers every input's
content (Table bindings fold each buffer to 128 bits on the device that
holds it and hash names, types, shapes and the folds on the host — no
buffer crosses to the host; parquet-path sources hash the path + size +
mtime_ns identity — re-written files change identity; in-memory byte
sources hash the bytes). Any input the digest cannot prove stable makes
the plan UNCACHEABLE (sound-but-incomplete, the certifier's philosophy)
rather than cached on a guess. Table digests memoize per object
identity (weakref-guarded — Tables are immutable by contract), so
repeat submissions over the same binding fold once, not per submit. The
fold is keyed per process: keys mean nothing outside it and must not
leave it (docs/serving.md#what-the-key-is-made-of).

Only DEVICE-tier results enter the cache (the scheduler guards put):
a degraded result is a transient-condition artifact whose
`degraded=True` stamp would keep reporting CPU-tier completions to
healthy-device traffic for the whole TTL.

Served hits are COPIES (`cached_copy`): `cached=True` stamped on the
result, metrics deep-copied so a profile/bench consumer mutating or
summing per-op wall time can never double-attribute the original run's
numbers (and never mutate the cached entry itself). Eviction is LRU +
TTL; hits/misses/evictions/expirations drain to `stats()` and ride the
soak's JSONL `cache_hit` stamp.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import threading
import time
import weakref
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.hash import _mm_fmix, f64_bits_u64
from ..utils.tracing import span


# ---- the fold: a buffer -> 128 bits, where the buffer lives --------------
#
# Four 32-bit lanes, each a wrap-around sum over the buffer's elements of
# a keyed mix of (element index, element bits). The key is drawn once per
# process: a collision cannot be prepared by anyone who cannot read this
# process's memory, which is why cache keys must never leave it
# (docs/serving.md#what-the-key-is-made-of).
_SEEDS = np.frombuffer(os.urandom(16), dtype=np.uint32)


def _f64_bits(x):
    # XLA:TPU lowers no f64 bitcast (ops/hash.py); every NaN folds as the
    # canonical quiet one there, the CPU sees the bits themselves
    return jnp.where(jnp.isnan(x), jnp.uint64(0x7FF8000000000000),
                     f64_bits_u64(x))


def _words(x) -> Tuple[jnp.ndarray, ...]:
    """The elements of a buffer as flat uint32 word arrays, low word
    first: every bit of every element is in exactly one of them."""
    x = x.reshape(-1)
    if x.dtype == jnp.float64:
        x = jax.lax.platform_dependent(
            x, cpu=lambda v: jax.lax.bitcast_convert_type(v, jnp.uint64),
            default=_f64_bits)
    elif x.dtype == jnp.float32:
        x = jax.lax.bitcast_convert_type(x, jnp.uint32)
    if x.dtype.itemsize == 8:
        x = x.astype(jnp.uint64)
        return x.astype(jnp.uint32), (x >> jnp.uint64(32)).astype(jnp.uint32)
    return (x.astype(jnp.uint32),)


def _mix(seed, words):
    """One lane's term per element: the murmur3 finalizer chained over
    the words, keyed at both ends. A word meets the key only through a
    full finalizer round (murmur3's block round lets a top-bit difference
    in one word cancel against the next whatever the seed), and passes at
    least two rounds before the sum."""
    h = seed
    for w in words:
        h = _mm_fmix(h ^ w)
    return _mm_fmix(h ^ seed)


def _fold(seeds, x):
    """uint32[4] of one buffer. Position counts: the element's index
    (both halves, so an index beyond 2**32 differs too) goes into the mix
    before its value. All of it fuses into the reductions: nothing of the
    buffer's size is allocated (tests/test_chip_compile.py)."""
    words = _words(x)
    idx = jax.lax.iota(jnp.uint64, words[0].shape[0])
    at = (idx.astype(jnp.uint32), (idx >> jnp.uint64(32)).astype(jnp.uint32))
    return jnp.stack([jnp.sum(_mix(seeds[k], at + words), dtype=jnp.uint32)
                      for k in range(4)])


@jax.jit
def _fold_buffers(seeds, *buffers):
    """(len(buffers), 4) uint32: one program per table signature, so a
    digest waits for the device once."""
    return jnp.stack([_fold(seeds, b) for b in buffers])


_digested = threading.local()   # .n: buffer bytes this thread has folded


def bytes_digested() -> int:
    """Running total of Table buffer bytes the calling thread has folded;
    the `serving.digest` span reports the difference across one submit (0
    when every table's digest was memoized)."""
    return getattr(_digested, "n", 0)


def _describe_column(h, col, buffers: List) -> None:
    """Everything of a column but its buffers' contents goes to the host
    hash; the buffers line up in `buffers` for the fold."""
    h.update(repr(col.dtype).encode())
    for a in (col.data, col.validity, col.offsets):
        if a is None:
            h.update(b"\x00none")
            continue
        h.update(f"{a.dtype}{tuple(a.shape)}".encode())
        buffers.append(a)
    for c in col.children:
        _describe_column(h, c, buffers)


# per-Table digest memo: Tables are immutable by contract, so the digest
# is a function of object identity; memoize it keyed by id() with a
# weakref guard (id() reuse after GC must not serve a dead table's
# digest) so repeat submissions over the same binding fold once, not per
# submit.
_table_digests: Dict[int, Tuple[object, str]] = {}
_digest_lock = threading.Lock()


def _table_digest(t) -> str:
    key = id(t)
    with _digest_lock:
        ent = _table_digests.get(key)
        if ent is not None and ent[0]() is t:
            return ent[1]
    h = hashlib.blake2b(digest_size=16)
    h.update(",".join(t.names).encode())
    buffers: List = []
    for c in t.columns:
        _describe_column(h, c, buffers)
    if buffers:
        # the one read-back: 16 bytes a buffer, behind whatever the
        # device's queue holds
        folded = _fold_buffers(_SEEDS, *buffers)
        with span("ops.host_sync", site="digest"):
            h.update(jax.device_get(folded).tobytes())
        _digested.n = bytes_digested() + sum(a.nbytes for a in buffers)
    digest = h.hexdigest()
    try:
        ref = weakref.ref(t, lambda _r, k=key: _evict_digest(k))
    except TypeError:
        return digest            # not weakref-able: correct, un-memoized
    with _digest_lock:
        _table_digests[key] = (ref, digest)
    return digest


def _evict_digest(key: int) -> None:
    with _digest_lock:
        _table_digests.pop(key, None)


def input_digest(inputs: Dict) -> Optional[str]:
    """Content digest of one input binding, or None when any input's
    stability cannot be proven (uncacheable — never guess)."""
    from ..columnar import Table
    h = hashlib.blake2b(digest_size=16)
    for name in sorted(inputs):
        v = inputs[name]
        h.update(name.encode())
        if isinstance(v, Table):
            h.update(b"table")
            h.update(_table_digest(v).encode())
            continue
        src = getattr(v, "source", None)
        if isinstance(src, str):
            # path identity: size + mtime_ns change when the file is
            # rewritten; a torn in-place append between stat and read is
            # the writer's race, same as any mmap consumer's
            try:
                st = os.stat(src)
            except OSError:
                return None
            h.update(b"path")
            h.update(src.encode())
            h.update(f"{st.st_size}:{st.st_mtime_ns}".encode())
        elif isinstance(src, bytes):
            h.update(b"bytes")
            h.update(src)
        else:
            return None         # unknown source kind: uncacheable
    return h.hexdigest()


def cache_key(plan, inputs: Dict) -> Optional[Tuple[str, str]]:
    """(canonical fingerprint, input digest), or None when uncacheable."""
    digest = input_digest(inputs)
    if digest is None:
        return None
    return (plan.fingerprint, digest)


def cached_copy(result):
    """A serve-safe copy of a cached PlanResult: `cached=True`, metrics
    and every mutable container deep-copied — the cache entry and all
    previously served copies stay untouched whatever the consumer does,
    and wall times remain attributed to the ORIGINAL run they measured
    (the cached stamp is how profile/bench consumers know not to count
    them again)."""
    from ..plan.executor import PlanResult
    metrics = {}
    for label, m in result.metrics.items():
        # dataclasses.replace copies every declared field; the
        # _kernel_sig side-channel intentionally does not survive — a
        # cached serve must never re-feed the stats store's timings
        metrics[label] = dataclasses.replace(m)
    copy = PlanResult(
        result.plan, result.table, result.valid, metrics, result.mode,
        result.wall_ms, attempts=result.attempts,
        caps=dict(result.caps) if result.caps else result.caps,
        retries=result.retries, degraded=result.degraded,
        breaker=dict(result.breaker) if result.breaker else result.breaker,
        backoff_ms=result.backoff_ms,
        jit_cache_hits=result.jit_cache_hits)
    copy.optimizer = (dict(result.optimizer)
                      if isinstance(result.optimizer, dict)
                      else result.optimizer)
    copy.cert = result.cert           # immutable bounds, shared by design
    copy.session = result.session
    # the worker stamp survives the copy ON PURPOSE: a hit names the
    # worker that COMPUTED the entry, not the one serving it — the
    # fleet soak's cross-worker cache-locality proof reads exactly this
    copy.worker = result.worker
    copy.cached = True
    return copy


class ResultCache:
    """Bounded LRU + TTL cache of completed PlanResults.

    `get` returns a `cached_copy` (never the entry), refreshes recency,
    and expires entries past the TTL; `put` stores a `cached_copy`-able
    original and evicts least-recently-used entries beyond `entries`.
    `entries=0` disables (get always misses, put drops)."""

    def __init__(self, entries: Optional[int] = None,
                 ttl_s: Optional[float] = None,
                 max_bytes: Optional[int] = None,
                 clock=time.monotonic):
        from .. import config
        self.entries = (config.serving_cache_entries() if entries is None
                        else max(0, int(entries)))
        self.ttl_s = (config.serving_cache_ttl_s() if ttl_s is None
                      else float(ttl_s))
        self.max_bytes = (config.serving_cache_bytes() if max_bytes is None
                          else max(1, int(max_bytes)))
        self._clock = clock
        self._lock = threading.Lock()
        # hand-rolled LRU (not utils/lru.LruDict): eviction here is
        # byte-weighted AND TTL'd, neither of which the shared bounded
        # dict models — entries are (stored_at, nbytes, result)
        self._data: Dict[Tuple[str, str], Tuple[float, int, object]] = {}
        self._resident_bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.expirations = 0
        self.oversize_skips = 0

    def get(self, key: Optional[Tuple[str, str]], *,
            count_miss: bool = True):
        """Serve a copy, refresh recency, expire past-TTL entries.
        `count_miss=False` keeps a re-consult of an already-counted key
        (the scheduler's dispatch-time burst dedup) out of the miss
        counter — stats must reflect traffic, not lookup plumbing."""
        if key is None or self.entries <= 0:
            return None
        with self._lock:
            ent = self._data.get(key)
            if ent is None:
                if count_miss:
                    self.misses += 1
                return None
            stored_at, nbytes, result = ent
            if self.ttl_s > 0 and self._clock() - stored_at > self.ttl_s:
                del self._data[key]
                self._resident_bytes -= nbytes
                self.expirations += 1
                if count_miss:
                    self.misses += 1
                return None
            # refresh recency (dict preserves insertion order)
            del self._data[key]
            self._data[key] = ent
            self.hits += 1
        # copy OUTSIDE the lock: concurrent hits (the burst shape the
        # dispatch-time consult exists for) must not serialize behind
        # one tenant's O(#ops) metric copies — the frozen entry is
        # immutable by contract, so the copy needs no exclusion
        return cached_copy(result)

    def put(self, key: Optional[Tuple[str, str]], result) -> None:
        if key is None or self.entries <= 0:
            return
        # resident-bytes accounting: cached tables are live buffers no
        # session quota charges (quotas cover in-flight execution, not
        # retention), so the cache bounds its own pin — and a single
        # result bigger than the whole budget never caches (a one-entry
        # cache that thrashes the budget serves nobody)
        from ..runtime.admission import operand_nbytes
        nbytes = operand_nbytes(result.table) + operand_nbytes(result.valid)
        if nbytes > self.max_bytes:
            with self._lock:
                self.oversize_skips += 1
            return
        # store a COPY, not the live result: the submitting caller still
        # holds the original and may mutate its metrics after completion
        # — the entry every future serve copies from must be frozen at
        # put time
        entry = cached_copy(result)
        with self._lock:
            self._insert_locked(key, nbytes, entry)

    def _insert_locked(self, key, nbytes: int, entry) -> None:
        old = self._data.pop(key, None)
        if old is not None:
            self._resident_bytes -= old[1]
        self._data[key] = (self._clock(), nbytes, entry)
        self._resident_bytes += nbytes
        while len(self._data) > self.entries or \
                self._resident_bytes > self.max_bytes:
            _, ev_bytes, _ = self._data.pop(next(iter(self._data)))
            self._resident_bytes -= ev_bytes
            self.evictions += 1

    def peek_frozen(self, key: Optional[Tuple[str, str]]):
        """The frozen entry as `(nbytes, result)` for cross-worker
        promotion (serving/fleet.py), or None. TTL-honored, but NO
        hit/miss accounting and no recency refresh — promotion is
        router plumbing, not tenant traffic, and must not skew the
        stats either cache reports."""
        if key is None or self.entries <= 0:
            return None
        with self._lock:
            ent = self._data.get(key)
            if ent is None:
                return None
            stored_at, nbytes, result = ent
            if self.ttl_s > 0 and self._clock() - stored_at > self.ttl_s:
                del self._data[key]
                self._resident_bytes -= nbytes
                self.expirations += 1
                return None
            return (nbytes, result)

    def adopt(self, key: Optional[Tuple[str, str]], nbytes: int,
              entry) -> None:
        """Insert an already-frozen entry promoted from a peer worker's
        cache. The frozen object is SHARED between the caches on
        purpose: entries are immutable by contract and every serve
        copies, so adoption costs a dict slot, not a table copy — and
        the entry keeps its original `worker` stamp, which is how a hit
        served here still names the worker that computed it."""
        if key is None or self.entries <= 0 or entry is None:
            return
        if nbytes > self.max_bytes:
            with self._lock:
                self.oversize_skips += 1
            return
        with self._lock:
            self._insert_locked(key, nbytes, entry)

    def invalidate_fingerprint(self, fingerprint: str,
                               keep_digest: Optional[str] = None) -> int:
        """Drop every entry for this plan fingerprint whose input digest
        differs from `keep_digest` (the fleet invalidation bus,
        serving/fleet.py: a source input changed, so results computed
        over the OLD data must stop serving everywhere — the entry for
        the new digest, if any, is still sound and survives). Returns
        the number of entries dropped; they count as evictions."""
        with self._lock:
            doomed = [k for k in self._data
                      if k[0] == fingerprint and k[1] != keep_digest]
            for k in doomed:
                _, nbytes, _ = self._data.pop(k)
                self._resident_bytes -= nbytes
                self.evictions += 1
            return len(doomed)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"entries": len(self._data), "hits": self.hits,
                    "misses": self.misses, "evictions": self.evictions,
                    "expirations": self.expirations,
                    "resident_bytes": self._resident_bytes,
                    "oversize_skips": self.oversize_skips}
