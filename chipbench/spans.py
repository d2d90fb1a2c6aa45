"""Host-side spans and counters, recorded from the benchmark's own files.

Spans are kept in memory (name, request, thread, start, end on
`time.perf_counter_ns`) and read after the window. `sync()` drops a named
`jax.profiler.TraceAnnotation` whose host-clock time is also kept, so that
the trace reduction can put the spans on the profiler's clock.
`CompileCounter` is a copy of chip_smoke.py's: lowerings and backend
compiles through jax.monitoring.
"""
import json
import threading
import time
from contextlib import contextmanager

SYNC_NAME = "chipbench_sync"


class Recorder:
    def __init__(self):
        self.spans = []          # (name, request, thread, t0_ns, t1_ns)
        self.syncs = []          # host perf_counter_ns inside each sync mark
        self._lock = threading.Lock()

    def add(self, name, request, t0_ns, t1_ns):
        with self._lock:
            self.spans.append((name, request, threading.get_ident(),
                               int(t0_ns), int(t1_ns)))

    @contextmanager
    def span(self, name, request=-1):
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.add(name, request, t0, time.perf_counter_ns())

    def sync(self):
        import jax
        with jax.profiler.TraceAnnotation(SYNC_NAME):
            self.syncs.append(time.perf_counter_ns())
            time.sleep(0.0005)

    def durations_ms(self, name, t_lo=None, t_hi=None):
        return [(t1 - t0) / 1e6 for n, _, _, t0, t1 in self.spans
                if n == name and (t_lo is None or t0 >= t_lo)
                and (t_hi is None or t1 <= t_hi)]

    def dump(self, path):
        with open(path, "w") as f:
            json.dump({"spans": self.spans, "syncs": self.syncs}, f)


class CompileCounter:
    """Counts jit lowerings (in-memory program-cache misses) and backend
    compiles: 'compiled nothing' means both stayed put, whatever the
    persistent cache holds."""
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.lowerings = 0
        self.compiles = 0
        self.compile_secs = 0.0
        self.lowered = {}        # program name -> times lowered
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, fun_name="?", **_):
        if event == self.LOWER:
            self.lowerings += 1
            self.lowered[fun_name] = self.lowered.get(fun_name, 0) + 1
        elif event == self.COMPILE:
            self.compiles += 1
            self.compile_secs += secs

    def snapshot(self):
        return self.lowerings, self.compiles, self.compile_secs
