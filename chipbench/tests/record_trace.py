"""How `data/small.xplane.pb` was recorded (on the chip, PR 25):

    python -m chipbench.tests.record_trace chiprun_out/trace_probe

A few small jitted programs with host spans around them, traced for a
fraction of a second, so that the reduction in `chipbench/trace.py` has a
real device trace to be checked on. Prints the planes and lines it finds.
"""
import os
import sys
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from chipbench import spans, trace

    @jax.jit
    def sort_step(x):
        return jnp.sort(x * 3 + 1)

    @jax.jit
    def add_step(x, y):
        return x + y

    x = jnp.arange(1 << 18, dtype=jnp.int32)[::-1]
    jax.block_until_ready(add_step(sort_step(x), x))        # compile outside
    rec = spans.Recorder()
    trace.start(out_dir)
    rec.sync()
    for i in range(3):
        with rec.span("execute", i):
            y = sort_step(x)
            jax.block_until_ready(y)
        with rec.span("generate", i):
            time.sleep(0.02)                                 # device idle
        with rec.span("execute", i):
            jax.block_until_ready(add_step(y, x))
    rec.sync()
    path = trace.stop(out_dir)
    print("trace at", path, os.path.getsize(path), "bytes")
    rec.dump(os.path.join(os.path.dirname(path), "spans.json"))
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("   line", repr(line.name), len(evs), "events",
                  [(e.name[:40], e.start_ns, e.duration_ns) for e in evs[:4]])
    try:
        print(trace.reduce(path, rec.spans, rec.syncs))
    except RuntimeError as e:
        print("reduce:", e)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
