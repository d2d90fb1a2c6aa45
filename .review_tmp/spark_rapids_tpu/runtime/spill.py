"""Spillable device buffers + the MemoryEventHandler that frees them.

The reference's allocator chain has an event-handler adaptor between the
arbiter and the pool (`RmmEventHandlerResourceAdaptor`, SURVEY.md §3.2): on
allocation failure the plugin's handler makes cached buffers spillable/frees
them and returns true so the allocation retries immediately, *before* the
task-level blocking state machine engages. `SpillPool` is that handler made
real for HBM: registered buffers are copied to host numpy and their device
arrays deleted (`jax.Array.delete()` actually drops the HBM buffer), their
reservations returned to the budget.

Restore (`SpillableBuffer.get`) re-admits through the budget, so a restore
under pressure can itself trigger further spills or the retry protocol —
the same recursion the reference guards in `pre_alloc_core`
(SparkResourceAdaptorJni.cpp:1238-1265); the arbiter's recursive-allocation
detection makes it safe here too.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional

import jax
import numpy as np

from .admission import array_nbytes
from .pool import MemoryBudget, MemoryEventHandler, Reservation


class SpillableBuffer:
    """One device array whose residency is budget-backed and revocable."""

    def __init__(self, pool: "SpillPool", array: jax.Array,
                 reservation: Reservation):
        self._pool = pool
        self._device = array
        self._host: Optional[np.ndarray] = None
        self._reservation: Optional[Reservation] = reservation
        self.nbytes = array_nbytes(array)
        self._pinned = False
        self._mu = threading.Lock()

    @property
    def spilled(self) -> bool:
        with self._mu:
            return self._device is None

    @property
    def pinned(self) -> bool:
        with self._mu:
            return self._pinned

    def pin(self) -> None:
        """Exclude this buffer from spilling while it is in active use —
        the reference's spillable-state contract: a batch is spillable
        only while its task is NOT computing on it (RmmSpark.java:402-416
        'make the inputs spillable' happens on rollback, and the retry
        unspills before touching them)."""
        with self._mu:
            self._pinned = True

    def unpin(self) -> None:
        with self._mu:
            self._pinned = False

    def spill(self) -> int:
        """Move to host, delete the device buffer, free the budget.
        Returns bytes freed (0 if already spilled or pinned)."""
        with self._mu:
            if self._device is None or self._pinned:
                return 0
            self._host = np.asarray(self._device)     # D2H copy
            self._device.delete()                     # drop the HBM buffer
            self._device = None
            r, self._reservation = self._reservation, None
        self._pool.budget.release(r)
        return self.nbytes

    def get(self) -> jax.Array:
        """The live device array; restores (re-admitting budget) if spilled.

        Loops: the buffer can be re-spilled between our restore attempt and
        the return (another thread's alloc failure), and a race-lost restore
        must re-read under the lock — never hand out a deleted array."""
        import jax.numpy as jnp
        while True:
            with self._mu:
                if self._device is not None:
                    return self._device
                host = self._host
            # acquire outside our own lock: admission may call back into the
            # pool's on_alloc_failure, which takes other buffers' locks
            r = self._pool.budget.acquire(self.nbytes)
            dev = jnp.asarray(host)
            with self._mu:
                if self._device is None:
                    self._device = dev
                    self._host = None
                    self._reservation = r
                    return dev
            # lost a restore race; give the budget back and re-check
            self._pool.budget.release(r)
            dev.delete()

    def close(self) -> None:
        with self._mu:
            if self._device is not None:
                self._device.delete()
                self._device = None
            self._host = None
            r, self._reservation = self._reservation, None
        if r is not None:
            self._pool.budget.release(r)


class SpillableTable:
    """A Table whose buffers live in a SpillPool — the 'make inputs
    spillable' half of the recovery contract (RmmSpark.java:402-416: catch
    RetryOOM → make inputs spillable → block until ready → retry).

    `protect()` registers every device buffer of the table (first call) and
    marks them spillable — call it on rollback, while the task is NOT
    computing on the table. `get()` restores any spilled buffers through
    budget admission and PINS them (in active use: the pool must not
    delete arrays a running op reads). Use as the `on_rollback` of
    runtime.retry.with_retry:

        st = SpillableTable(pool, table)
        out = with_retry(arbiter, lambda t: op(st.get()), table,
                         on_rollback=st.protect, split=...)
        st.close()
    """

    def __init__(self, pool: "SpillPool", table):
        self._pool = pool
        self._table = table
        self._protected = False
        self._closed = False

    def protect(self) -> None:
        """Register the buffers (first call) and make them spillable:
        the rollback half of the recovery contract."""
        if self._closed:
            raise RuntimeError("SpillableTable is closed")
        if not self._protected:
            self._protected = True
            leaves, self._treedef = jax.tree_util.tree_flatten(self._table)
            self._slots = []
            seen: Dict[int, SpillableBuffer] = {}   # alias-safe: one
            for leaf in leaves:                     # buffer per device array
                if isinstance(leaf, jax.Array):
                    buf = seen.get(id(leaf))
                    if buf is None:
                        buf = self._pool.register(leaf)
                        seen[id(leaf)] = buf
                    self._slots.append(buf)
                else:
                    self._slots.append(leaf)
            self._table = None         # drop the direct strong refs
        for s in self._unique_buffers():
            s.unpin()

    def _unique_buffers(self):
        seen = set()
        for s in self._slots:
            if isinstance(s, SpillableBuffer) and id(s) not in seen:
                seen.add(id(s))
                yield s

    def get(self):
        """The live Table, pinned for use; restores spilled buffers
        (admitted — a restore under pressure can spill OTHER unpinned
        buffers or block through the retry protocol). Balance with
        unpin() (or use()) once the op is done, so idle inputs stay
        spillable for other tasks."""
        if self._closed:
            raise RuntimeError("SpillableTable is closed")
        if not self._protected:
            return self._table
        leaves = []
        for s in self._slots:
            if isinstance(s, SpillableBuffer):
                # pin FIRST: a pinned buffer cannot be spilled, so the
                # array returned by get() below is guaranteed to stay live
                s.pin()
                leaves.append(s.get())
            else:
                leaves.append(s)
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    def unpin(self) -> None:
        """Make the buffers spillable again (op finished with them)."""
        if self._protected and not self._closed:
            for s in self._unique_buffers():
                s.unpin()

    def use(self):
        """Context manager: pinned table inside, spillable again outside.

            with st.use() as t:
                out = op(t)
        """
        import contextlib

        @contextlib.contextmanager
        def cm():
            try:
                yield self.get()
            finally:
                self.unpin()
        return cm()

    def close(self) -> None:
        self._closed = True
        if not self._protected:
            self._table = None
            return
        for s in self._unique_buffers():
            self._pool.unregister(s)
        self._slots = []


class SpillPool(MemoryEventHandler):
    """Registry of spillable buffers; spills oldest-first on alloc failure."""

    def __init__(self):
        self.budget: Optional[MemoryBudget] = None   # set by attach()
        self._mu = threading.Lock()
        self._buffers: Dict[int, SpillableBuffer] = {}
        self._next_id = 0
        self.spill_count = 0
        self.spilled_bytes = 0

    def attach(self, budget: MemoryBudget) -> "SpillPool":
        self.budget = budget
        budget.event_handler = self
        return self

    def register(self, array: jax.Array) -> SpillableBuffer:
        """Admit an already-materialized device array into the pool: its
        bytes are charged to the budget and become revocable."""
        assert self.budget is not None, "attach() a budget first"
        r = self.budget.acquire(array_nbytes(array))
        buf = SpillableBuffer(self, array, r)
        with self._mu:
            buf._id = self._next_id
            self._next_id += 1
            self._buffers[buf._id] = buf
        return buf

    def unregister(self, buf: SpillableBuffer) -> None:
        with self._mu:
            self._buffers.pop(getattr(buf, "_id", -1), None)
        buf.close()

    # -- MemoryEventHandler ---------------------------------------------------
    def on_alloc_failure(self, nbytes: int, retry_count: int) -> bool:
        """Spill buffers oldest-first until `nbytes` are freed. True iff any
        bytes were freed (the RmmEventHandlerResourceAdaptor contract:
        true → retry the allocation immediately). Serialized under the pool
        lock so concurrent alloc failures do not over-spill or race the
        counters; individual spills release budget via each buffer's own
        lock, which is never taken while holding another buffer's."""
        freed = 0
        with self._mu:
            candidates = [b for _, b in sorted(self._buffers.items())
                          if not b.spilled and not b.pinned]
            for b in candidates:
                freed += b.spill()
                if freed >= nbytes:
                    break
            if freed > 0:
                self.spill_count += 1
                self.spilled_bytes += freed
        return freed > 0

    def close(self) -> None:
        with self._mu:
            bufs = list(self._buffers.values())
            self._buffers.clear()
        for b in bufs:
            b.close()
