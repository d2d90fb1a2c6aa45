"""Headline benchmark: Spark-exact row hashing throughput on device.

Hashing (murmur3_32 + xxhash64 over a 2×int64-column table) is the kernel a
Spark plan leans on hardest — every hash partition, hash join and hash
aggregate runs it over the full batch. The reference measures its kernels with
nvbench locally and publishes nothing (SURVEY.md §6), so the baseline here is
the same XLA program on the host CPU: `vs_baseline` = device rows/s ÷ host
rows/s.

The measurement runs in the calling process, on the attached TPU. With no
TPU the script exits non-zero and prints no record: a number from a CPU run
is never written under a device metric's name. One JSON line is printed,
stamped with the device it ran on.

Usage: `python bench.py`.
"""
import json
import os
import sys

N_ROWS = 10_000_000
UNIT = "Mrows/s (murmur3_32+xxhash64, 2xint64, 10M rows)"


def _bench(fn, args, iters):
    """Steady-state seconds/iter on the device `args` live on
    (`benchmarks.common.steady_state_ms`: every iteration blocked)."""
    import jax
    from benchmarks.common import steady_state_ms
    jax.block_until_ready(fn(*args))           # warmup/compile
    return steady_state_ms(fn, args, iters) / 1e3


def measure() -> int:
    """Run the measurement in-process and print the ONE JSON line."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench: no TPU — jax.devices()[0] is {dev.platform}:"
              f"{dev.device_kind}; nothing was measured", file=sys.stderr)
        return 2
    from spark_rapids_tpu.config import place_compile_cache
    place_compile_cache()
    import numpy as np
    import jax.numpy as jnp
    from spark_rapids_tpu import dtypes, Column
    from spark_rapids_tpu.columnar import Table
    from spark_rapids_tpu.ops import murmur_hash3_32, xxhash64

    n = N_ROWS
    rng = np.random.default_rng(0)
    keys_np = rng.integers(-(2**62), 2**62, size=n, dtype=np.int64)
    vals_np = rng.integers(-(2**31), 2**31, size=n, dtype=np.int64)

    def step(keys, vals):
        t = Table([Column(dtype=dtypes.INT64, length=n, data=keys),
                   Column(dtype=dtypes.INT64, length=n, data=vals)])
        h32 = murmur_hash3_32(t, seed=42)
        h64 = xxhash64(t)
        return h32.data, h64.data

    jit_step = jax.jit(step)

    d_args = (jax.device_put(jnp.asarray(keys_np), dev),
              jax.device_put(jnp.asarray(vals_np), dev))
    dev_s = _bench(jit_step, d_args, iters=20)
    dev_rows_per_s = n / dev_s

    # the baseline: the same XLA program on this host's CPU
    cpu = jax.devices("cpu")[0]
    c_args = (jax.device_put(jnp.asarray(keys_np), cpu),
              jax.device_put(jnp.asarray(vals_np), cpu))
    cpu_s = _bench(jit_step, c_args, iters=3)
    vs_baseline = round(dev_rows_per_s / (n / cpu_s), 3)

    # kernel-registry stamp (docs/kernels.md): this bench times the jnp
    # fused-XLA row hash — the universal lowering, registry-free on every
    # backend — so the honest per-run stamp is "fallback" (stamping the
    # registry's would-be summary here would attribute kernels this run
    # never dispatched)
    kernels = "fallback"

    print(json.dumps({
        "metric": "spark_row_hash_throughput",
        "value": round(dev_rows_per_s / 1e6, 3),
        "unit": UNIT,
        "vs_baseline": vs_baseline,
        "backend": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "kernels": kernels,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(measure())
