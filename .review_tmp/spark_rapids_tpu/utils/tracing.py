"""Tracing hooks — the reference's NVTX integration, TPU-style.

The reference brackets native ops with NVTX ranges (`CUDF_FUNC_RANGE()`,
NativeParquetJni.cpp:136) behind a jar flag (`ai.rapids.cudf.nvtx.enabled`,
pom.xml:87) so nsight can attribute GPU time; its de-facto execution trace is
the arbiter's CSV state log (SURVEY.md §5). The JAX equivalents:

- `func_range` / `range_ctx`: `jax.profiler.TraceAnnotation` ranges that show
  up in the xplane/perfetto trace, gated by SPARK_RAPIDS_TPU_TRACE=1 (zero
  overhead when off, like the nvtx flag).
- `start_trace`/`stop_trace`: wrap `jax.profiler` to capture a device trace
  directory viewable in XProf/TensorBoard (the nsight-systems slot).
- the arbiter CSV state log lives in runtime/adaptor.py (`log_loc=`).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Callable, TypeVar

F = TypeVar("F", bound=Callable)

ENV_FLAG = "SPARK_RAPIDS_TPU_TRACE"


def enabled() -> bool:
    from ..config import trace_enabled
    return trace_enabled()


@contextlib.contextmanager
def range_ctx(name: str):
    """Named range in the profiler timeline (CUDF_FUNC_RANGE analogue)."""
    if not enabled():
        yield
        return
    import jax.profiler
    with jax.profiler.TraceAnnotation(name):
        yield


def func_range(fn: F) -> F:
    """Decorator form: wraps the call in a TraceAnnotation named after the
    function, only when tracing is enabled."""
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if not enabled():
            return fn(*args, **kwargs)
        import jax.profiler
        with jax.profiler.TraceAnnotation(fn.__qualname__):
            return fn(*args, **kwargs)
    return wrapper  # type: ignore[return-value]


def start_trace(log_dir: str) -> None:
    """Begin capturing a device trace (XProf/TensorBoard-viewable)."""
    import jax.profiler
    jax.profiler.start_trace(log_dir)


def stop_trace() -> None:
    import jax.profiler
    jax.profiler.stop_trace()


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a device trace around a block."""
    start_trace(log_dir)
    try:
        yield
    finally:
        stop_trace()
