"""Admission control at the Table-op/IO boundary.

In the reference every device allocation crosses the arbiter because the
allocator itself is wrapped (`spark_resource_adaptor::do_allocate`,
SparkResourceAdaptorJni.cpp:1733). XLA owns its allocator, so the TPU-native
crossing point is *op dispatch*: output and working-set bytes are computable
from input shapes before any device work is launched, and a reservation is
acquired from the active `DeviceSession`'s budget first. The acquire path is
the same state machine — under pressure the thread blocks, deadlocks escalate
to RetryOOM/SplitAndRetryOOM, and `with_retry`/`halve_table` recover exactly
as the reference's recovery contract prescribes (RmmSpark.java:402-416).

Lifetime: after the op completes, the reservation is shrunk to the actual
bytes of the op's outputs and tied to the output objects — when the last
output is garbage-collected the bytes return to the budget and blocked
threads wake, mirroring `do_deallocate` (SparkResourceAdaptorJni.cpp:1756).

With no active session every wrapper is a zero-cost pass-through, so the
engine runs unbudgeted by default (the reference likewise only arbitrates
once RmmSpark.setEventHandler installs the adaptor).

Two session notions compose here (docs/serving.md): a `DeviceSession` is
a MEMORY BUDGET (this module's thread-scoped `active_session`), while a
serving-tenant session is an ACCOUNTING IDENTITY
(`runtime/sessionctx.py`, installed by the serving dispatcher around
every job). Health budgets/sticky windows key on the tenant identity —
per-session, thread fallback — so a DeviceSession shared by all serving
workers still arbitrates one device budget while failure isolation stays
per tenant.
"""
from __future__ import annotations

import threading
import weakref
from typing import Any, Optional

import jax
import numpy as np

from .pool import DeviceSession

_state = threading.local()
_global_session: Optional[DeviceSession] = None
_global_lock = threading.Lock()


def set_active_session(session: Optional[DeviceSession]) -> None:
    """Install `session` process-wide (executor startup: the analogue of
    RmmSpark.setEventHandler). Pass None to uninstall."""
    global _global_session
    with _global_lock:
        old = _global_session
        _global_session = session
    # Drop the displaced session's reference OUTSIDE the lock: its teardown
    # runs weakref finalizers (buffer releases -> arbiter.dealloc under
    # ResourceArbiter._close_lock), and a finalizer that reached back into
    # this module would self-deadlock on the plain Lock above.
    del old


def get_active_session() -> Optional[DeviceSession]:
    override = getattr(_state, "session", None)
    if override is not None:
        return override
    return _global_session


class active_session:
    """Context manager scoping a session to the current thread (tests)."""

    def __init__(self, session: DeviceSession):
        self.session = session

    def __enter__(self):
        self._prev = getattr(_state, "session", None)
        _state.session = self.session
        return self.session

    def __exit__(self, *exc):
        _state.session = self._prev
        return False


# ---- byte accounting --------------------------------------------------------

def array_nbytes(a) -> int:
    """Bytes of one dense buffer, from shape+dtype (works on tracers too)."""
    if a is None:
        return 0
    try:
        return int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
    except Exception:
        return 0


def operand_nbytes(obj: Any) -> int:
    """Total buffer bytes reachable from a Column/Table/array/pytree."""
    # local imports: columnar imports dtypes which must not cycle into runtime
    from ..columnar.column import Column
    from ..columnar.table import Table
    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        return 0
    if isinstance(obj, Column):
        return (array_nbytes(obj.data) + array_nbytes(obj.validity) +
                array_nbytes(obj.offsets) +
                sum(operand_nbytes(c) for c in obj.children))
    if isinstance(obj, Table):
        return sum(operand_nbytes(c) for c in obj.columns)
    if isinstance(obj, (list, tuple)):
        return sum(operand_nbytes(x) for x in obj)
    if isinstance(obj, dict):
        return sum(operand_nbytes(v) for v in obj.values())
    if hasattr(obj, "shape") and hasattr(obj, "dtype"):
        return array_nbytes(obj)
    # generic pytree holders (e.g. BloomFilter wraps a device bits array):
    # count every array leaf so their HBM stays visible to the budget
    try:
        leaves = jax.tree_util.tree_leaves(obj)
    except Exception:
        return 0
    if len(leaves) == 1 and leaves[0] is obj:
        return 0
    return sum(array_nbytes(l) if hasattr(l, "shape") else 0 for l in leaves)


# ---- reservation lifetime ---------------------------------------------------

class _SharedRelease:
    """Releases one reservation when the last of N output objects dies."""

    def __init__(self, budget, reservation, count: int):
        self.budget = budget
        self.reservation = reservation
        self.count = count
        self.lock = threading.Lock()

    def dec(self):
        with self.lock:
            self.count -= 1
            done = self.count == 0
        if done:
            # runs from a weakref finalizer on an arbitrary thread: the
            # release is host-side accounting and must always land — a
            # poisoned-device fail-fast here would leak budget forever and
            # never wake blocked threads
            from .. import faultinj
            with faultinj.suppressed():
                self.budget.release(self.reservation)


def _weakrefable_outputs(out: Any) -> list:
    """Output objects whose lifetime should own the reservation."""
    from ..columnar.column import Column
    from ..columnar.table import Table
    found = []

    def walk(o):
        if isinstance(o, (Column, Table)):
            found.append(o)        # do not descend: the holder is enough
        elif isinstance(o, (list, tuple)):
            for x in o:
                walk(x)
        elif isinstance(o, dict):
            for v in o.values():
                walk(v)
        elif isinstance(o, jax.Array):
            found.append(o)
        elif o is not None and not isinstance(o, (bool, int, float, str, bytes)):
            # pytree holder carrying device arrays (e.g. BloomFilter)
            try:
                leaves = jax.tree_util.tree_leaves(o)
            except Exception:
                return
            if any(l is not o and hasattr(l, "shape") for l in leaves):
                found.append(o)

    walk(out)
    return found


def tie_to_outputs(budget, reservation, out: Any) -> None:
    """Shrink `reservation` to the outputs' true bytes and hand ownership to
    the output objects; falls back to immediate release when the output holds
    no device buffers (e.g. a plain Python scalar)."""
    actual = operand_nbytes(out)
    budget.resize(reservation, actual)
    if actual == 0:
        budget.release(reservation)
        return
    holders = _weakrefable_outputs(out)
    live = []
    for h in holders:
        try:
            weakref.ref(h)
            live.append(h)
        except TypeError:
            pass
    if not live:
        budget.release(reservation)
        return
    shared = _SharedRelease(budget, reservation, len(live))
    for h in live:
        weakref.finalize(h, shared.dec)


# ---- the op wrapper ---------------------------------------------------------

def admitted_op(fn, factor: float = 2.0, min_bytes: int = 0, estimator=None):
    """Wrap a Table-level op with reservation-based admission.

    The working-set estimate is `factor × input buffer bytes` (+min_bytes):
    inputs are already resident, the op materializes outputs plus transient
    fusion buffers of the same order. An explicit `estimator(*args, **kw) →
    bytes` overrides that (IO ops estimate from file size). After the op runs
    the reservation is shrunk to the outputs' actual bytes (concrete
    post-dispatch) and tied to their lifetime.
    """
    import functools

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        session = get_active_session()
        if session is None:
            return fn(*args, **kwargs)
        if estimator is not None:
            est = int(estimator(*args, **kwargs))
        else:
            est = int(factor * (operand_nbytes(args) + operand_nbytes(kwargs)))
        est = max(est, min_bytes)
        if est <= 0:
            return fn(*args, **kwargs)
        reservation = session.device.acquire(est)
        try:
            out = fn(*args, **kwargs)
        except BaseException:
            session.device.release(reservation)
            raise
        tie_to_outputs(session.device, reservation, out)
        return out

    wrapper.__wrapped__ = fn
    wrapper.__admitted__ = True
    return wrapper
