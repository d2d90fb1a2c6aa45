"""Bounded LRU dict shared by the engine's program/memo caches.

One definition for every cache that must not pin dead plans forever: the
plan executor's compiled-program and caps memos and the optimizer's
rewrite/fingerprint caches all hold per-plan artifacts while executors
live for a whole job and front-ends may hand them a fresh Plan per query.

Semantics (deliberately narrow — the callers use exactly this surface):
- `get(key)` refreshes recency (the hit becomes most-recently-used);
- `d[key] = value` inserts as most-recent (overwriting refreshes) and
  evicts the least-recently-used entries beyond `maxsize`;
- plain `d[key]` reads do NOT refresh (dict semantics, cheap probes);
- `discard(key)` drops an entry its owner knows to be dead.

Thread safety: `get`/`__setitem__`/`discard` are internally locked. The serving
layer (serving/scheduler.py) runs N dispatcher workers through ONE
PlanExecutor, so its memo caches see genuinely concurrent get/insert —
the unlocked pop-then-reinsert recency dance would drop a live entry
(two threads `get` the same key; the second `pop` raises) exactly when
the cache is hottest. Compound read-modify-write sequences ACROSS calls
(get-miss then compute then insert) stay caller-racy by design: both
threads compute equivalent values and last-write-wins is correct for
every cache built on this.
"""
from __future__ import annotations

import threading


class LruDict(dict):
    """Bounded cache: `get` refreshes recency, inserts evict the oldest."""

    def __init__(self, maxsize: int):
        super().__init__()
        self.maxsize = maxsize
        self._lru_lock = threading.Lock()

    def get(self, key, default=None):
        with self._lru_lock:
            if key in self:
                val = super().pop(key)
                super().__setitem__(key, val)   # re-insert = most recent
                return val
            return default

    def discard(self, key) -> None:
        with self._lru_lock:
            super().pop(key, None)

    def __setitem__(self, key, value):
        with self._lru_lock:
            super().pop(key, None)
            super().__setitem__(key, value)
            while len(self) > self.maxsize:
                del self[next(iter(self))]
