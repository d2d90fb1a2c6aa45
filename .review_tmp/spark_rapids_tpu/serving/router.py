"""Consistent-hash routing ring for the fleet serving tier
(docs/serving.md#fleet).

The router's cache-locality promise is that the SAME plan fingerprint
lands on the SAME worker run after run — that worker's result cache,
stats store, and compiled-program caches stay warm for it — and that
promise must survive workers joining and leaving. A modulo assignment
(`hash(fp) % n`) reshuffles nearly every fingerprint when n changes; a
consistent-hash ring moves only the keys that mapped onto the departed
(or newly inserted) worker's arcs — about 1/n of the keyspace — which
is the textbook property the fleet's failover story leans on: killing
one worker re-homes that worker's fingerprints and NOBODY else's, so
the survivors' caches keep serving warm (Karger et al.; the same ring
every memcached/Dynamo-descended router ships).

Each worker owns `replicas` virtual points (blake2b of "name#i") so the
arc lengths even out; lookup is a bisect over the sorted point list —
O(log(workers x replicas)) per route, no per-key state. The ring is
deliberately dumb: membership changes and pressure-aware OVERRIDES of
the ring's answer (session affinity, load spillover) are fleet.py
policy, not ring mechanics.
"""
from __future__ import annotations

import bisect
import hashlib
import threading
from typing import Dict, List, Optional, Tuple

__all__ = ["HashRing"]


def _point(label: str) -> int:
    """Ring coordinate of one virtual node / key: the first 8 bytes of
    blake2b — stable across processes and Python hash randomization
    (`hash()` would re-home every fingerprint on restart)."""
    return int.from_bytes(
        hashlib.blake2b(label.encode(), digest_size=8).digest(), "big")


class HashRing:
    """Consistent-hash ring over named workers.

    `route(key)` returns the owning worker name (clockwise-next virtual
    point); `add`/`remove` change membership, moving only ~1/n of the
    keyspace each. Thread-safe — the fleet routes while membership
    changes under failover."""

    def __init__(self, replicas: Optional[int] = None):
        from .. import config
        self.replicas = (config.fleet_ring_replicas() if replicas is None
                         else max(1, int(replicas)))
        self._lock = threading.Lock()
        self._points: List[int] = []          # sorted virtual points
        self._owner: Dict[int, str] = {}      # point -> worker name
        self._members: Dict[str, List[int]] = {}

    def add(self, name: str) -> None:
        with self._lock:
            if name in self._members:
                return
            pts = []
            for i in range(self.replicas):
                p = _point(f"{name}#{i}")
                # vanishingly rare 64-bit collision: skip the point
                # rather than silently re-home another worker's arc
                if p in self._owner:
                    continue
                self._owner[p] = name
                bisect.insort(self._points, p)
                pts.append(p)
            self._members[name] = pts

    def remove(self, name: str) -> None:
        with self._lock:
            pts = self._members.pop(name, None)
            if not pts:
                return
            doomed = set(pts)
            for p in pts:
                del self._owner[p]
            self._points = [p for p in self._points if p not in doomed]

    def route(self, key: str) -> Optional[str]:
        """Owning worker for `key`, or None on an empty ring."""
        with self._lock:
            if not self._points:
                return None
            i = bisect.bisect_right(self._points, _point(key))
            if i == len(self._points):
                i = 0                          # wrap: the ring is a circle
            return self._owner[self._points[i]]

    def route_multi(self, key: str, n: int) -> List[str]:
        """The first `n` DISTINCT owners clockwise from `key`'s point —
        primary first, then the replica owners warm failover replicates
        hot entries to (serving/fleet.py). Same walk every quorum-style
        ring uses: membership changes re-derive replica sets with
        minimal remap (a join inserts itself into some sets, a leave
        drops itself — surviving members keep their relative order,
        which tests/test_fleet.py pins). Returns fewer than `n` names
        when the ring has fewer members."""
        with self._lock:
            if not self._points or n <= 0:
                return []
            out: List[str] = []
            start = bisect.bisect_right(self._points, _point(key))
            for off in range(len(self._points)):
                owner = self._owner[
                    self._points[(start + off) % len(self._points)]]
                if owner not in out:
                    out.append(owner)
                    if len(out) >= n:
                        break
            return out

    def members(self) -> Tuple[str, ...]:
        with self._lock:
            return tuple(sorted(self._members))

    def __len__(self) -> int:
        with self._lock:
            return len(self._members)

    def __contains__(self, name: str) -> bool:
        with self._lock:
            return name in self._members
