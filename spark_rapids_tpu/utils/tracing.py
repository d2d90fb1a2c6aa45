"""Program spans — the reference's NVTX ranges (`CUDF_FUNC_RANGE()`,
NativeParquetJni.cpp:136), TPU-style.

One mechanism: `span(name, **attrs)` is a `jax.profiler.TraceAnnotation`.
"Tracing on" means "a profiler session is running" (`jax.profiler.
start_trace`, XProf, the benchmark's `--trace 1`) and nothing else: the
profiler keeps the spans in memory and writes them into the same
`.xplane.pb` as the device trace, on its clock, on the `/host:` plane's
line of the calling thread. With no session a span is inert (under a
microsecond). There is no environment knob, no second recorder and no
exporter.

Names are `serving.*`, `plan.*` and `ops.*`; every span carries the
`request=` scoped on its thread (`runtime/sessionctx.request_scope`: the
scheduler scopes a ticket's number around submit and around the worker's
execution, a direct `PlanExecutor.execute` its own counter's), which joins
the submitting thread's spans to the worker's; nesting on a thread gives
the parent. Attributes known
only at the end go in through the span object's `set_metadata(**attrs)`.
Attribute text must hold none of `#`, `,` and `=` (the profiler's own
encoding): `text()` makes a plan label safe. docs/plan.md lists every
span, its attributes and what reads them.

A request's spans are brackets (`plan.execute`, `plan.run`, `plan.attempt`,
`plan.op`, `plan.exchange`, the `serving.*` roots) and leaves that tile
them. Wherever the host blocks on the device the leaf is one of two:
`ops.host_sync` (a number crosses to the host and the next shape depends
on it) or `plan.wait` (a block until outputs are ready, nothing read);
`plan.readback` is the capped tier's one transfer of its row counts.
tests/test_tracing.py walks the request path's modules and holds every
blocking read to one of the three.

What runs with no session: one `jax.monitoring` listener, registered when
this module is imported, adds every jit lowering (an in-memory program
cache miss: a trace and, unless the compile cache has it, a compile) to a
per-thread pair (count, seconds). `bracket()` is a span that reads the
pair when it opens and when it closes and carries the difference as
`lowerings=`, `lowering_ms=` and `lowered=` (the program names); the
executor puts it on `plan.execute`, `plan.attempt` and `plan.op` and copies
the request's pair onto `PlanResult`, so an operator of the real system
sees that a request recompiled without a profiler. It has to run always
because a lowering is not announced in advance: the listener fires only
when something is lowered, and a bracket costs two thread-local reads.
"""
from __future__ import annotations

import collections
import contextlib
import threading

import jax
from jax.profiler import TraceAnnotation

from ..runtime.sessionctx import current_request

# the event `chipbench/spans.py:CompileCounter` counts as a lowering
_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_LOWERED_SHOWN = 4


class _Lowered(threading.local):
    """This thread's lowerings since it started: the pair, and the names
    of the newest few programs."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.names = collections.deque(maxlen=4 * _LOWERED_SHOWN)


_lowered = _Lowered()


def _on_duration(event, secs, fun_name="?", **_):
    if event == _LOWERING:       # on the thread that lowered
        _lowered.count += 1
        _lowered.seconds += secs
        _lowered.names.append(str(fun_name))


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def span(name: str, **attrs) -> TraceAnnotation:
    """A named range on the profiler's timeline, as a context manager,
    stamped with the request scoped on this thread (-1 outside any)."""
    return TraceAnnotation(name, request=current_request(), **attrs)


class bracket:
    """A `span` that also says what its thread lowered while it was open,
    its children's lowerings included: `lowerings=`, `lowering_ms=` and,
    where not zero, `lowered=` (at most four program names, joined by
    `+`)."""

    __slots__ = ("_span", "_count0", "_seconds0")

    def __init__(self, name: str, **attrs):
        self._span = span(name, **attrs)

    def __enter__(self) -> "bracket":
        self._count0, self._seconds0 = _lowered.count, _lowered.seconds
        self._span.__enter__()
        return self

    def set_metadata(self, **attrs) -> None:
        self._span.set_metadata(**attrs)

    def lowered(self):
        """-> (lowerings, lowering_ms) since the bracket opened."""
        return (_lowered.count - self._count0,
                (_lowered.seconds - self._seconds0) * 1e3)

    def __exit__(self, *exc):
        n, ms = self.lowered()
        attrs = {"lowerings": n, "lowering_ms": round(ms, 3)}
        if n:
            names = list(_lowered.names)[-n:][:_LOWERED_SHOWN]
            attrs["lowered"] = text("+".join(names)).replace(",", ";") \
                .replace("=", ":")
        self._span.set_metadata(**attrs)
        return self._span.__exit__(*exc)


class Tally:
    """What the kernels under an operator say they did, for the operator's
    span and metrics: a kernel `note`s an item, and whoever opened
    `collect()` on this thread reads the list (nobody: the note is
    dropped). Per thread, so concurrent requests keep apart."""

    def __init__(self):
        self._local = threading.local()

    @contextlib.contextmanager
    def collect(self):
        prev = getattr(self._local, "items", None)
        self._local.items = items = []
        try:
            yield items
        finally:
            self._local.items = prev

    def note(self, item) -> None:
        items = getattr(self._local, "items", None)
        if items is not None:
            items.append(item)


def text(label: str) -> str:
    """`HashJoin#12` -> `HashJoin:12`: safe as an attribute value."""
    return label.replace("#", ":")
