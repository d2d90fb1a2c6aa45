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
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

from ..runtime.sessionctx import current_request


def span(name: str, **attrs) -> TraceAnnotation:
    """A named range on the profiler's timeline, as a context manager,
    stamped with the request scoped on this thread (-1 outside any)."""
    return TraceAnnotation(name, request=current_request(), **attrs)


def text(label: str) -> str:
    """`HashJoin#12` -> `HashJoin:12`: safe as an attribute value."""
    return label.replace("#", ":")
