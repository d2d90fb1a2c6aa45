"""On-chip primitive cost measurement for the relational-core redesign.

The groupby/join kernels are compositions of lax.sort, cumsum,
associative_scan, gather (jnp.take), scatter (.at[].set/.add), jnp.repeat
and searchsorted. docs/architecture.md carries one round of these numbers
(10M rows: sort 38ms, cumsum 16ms, gather 160ms, scatter-add-x64 930ms,
searchsorted 2s); this tool re-measures them with the validated barrier
methodology (benchmarks.common), sweeps the axes that drive the round-3
design decisions, and prints one JSON line per measurement:

- marginal cost of a sort OPERAND (payload-through-sort vs gather-after):
  sort with 1..6 operands, u32 vs emulated-i64 keys;
- gather: random vs monotone indices, 4B vs 8B elements;
- scatter: .at[].set vs .add, random vs sorted+unique indices (the
  indices_are_sorted/unique_indices flags), i32 vs i64;
- scans: cumsum over i32/i64/f32/f64, tuple-carry associative_scan
  (the segmented-reduce workhorse), jnp.repeat expansion;
- MXU calibration: big i8xi8->i32 and bf16 matmul rates (the one-hot
  groupby fast-path budget).

Usage: python tools/tpu_primitives.py [--n 10000000] [--cpu] [--iters 5]
Writes records to stdout and (by default) appends to tools/primitives.jsonl.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=10_000_000)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "primitives.jsonl"))
    ap.add_argument("--only", default=None,
                    help="comma-separated name filter (substring match)")
    args = ap.parse_args(argv)

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    # the package runs under x64 (enabled on import); measure the same regime
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import steady_state_ms

    n = args.n
    platform = jax.default_backend()
    rng = np.random.default_rng(0)
    results = []

    def rec(name, ms, note=""):
        r = {"name": name, "n": n, "ms": round(ms, 3), "backend": platform}
        if note:
            r["note"] = note
        print(json.dumps(r), flush=True)
        results.append(r)

    def bench(name, fn, *arrs, note=""):
        if args.only and not any(s in name for s in args.only.split(",")):
            return
        f = jax.jit(fn)
        try:
            t0 = time.perf_counter()
            out = f(*arrs)
            jax.block_until_ready(out)
            compile_s = time.perf_counter() - t0
            ms = steady_state_ms(f, arrs, args.iters)
            rec(name, ms, note=note or f"compile {compile_s:.1f}s")
        except Exception as e:  # keep sweeping on a single failure
            print(json.dumps({"name": name, "n": n, "error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)

    u32 = jnp.asarray(rng.integers(0, 2**32, size=n, dtype=np.uint32))
    u32b = jnp.asarray(rng.integers(0, 2**32, size=n, dtype=np.uint32))
    i64 = jnp.asarray(rng.integers(-2**62, 2**62, size=n, dtype=np.int64))
    i32 = jnp.asarray(rng.integers(-2**31, 2**31, size=n, dtype=np.int32))
    f32 = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    iota = jnp.arange(n, dtype=jnp.int32)
    perm = jnp.asarray(rng.permutation(n).astype(np.int32))
    sorted_idx = jnp.sort(jnp.asarray(
        rng.integers(0, n, size=n, dtype=np.int32)))

    import jax.lax as lax

    # ---- sort: marginal operand cost ------------------------------------
    bench("sort_k1_u32", lambda a: lax.sort([a], num_keys=1)[0], u32)
    bench("sort_k1_u32_stable",
          lambda a, b: lax.sort([a, b], num_keys=1, is_stable=True)[0],
          u32, iota)
    bench("sort_k1_u32_p1",
          lambda a, b: lax.sort([a, b], num_keys=1)[0], u32, iota)
    bench("sort_k1_u32_p2",
          lambda a, b, c: lax.sort([a, b, c], num_keys=1)[0],
          u32, iota, i32)
    bench("sort_k1_u32_p4",
          lambda a, b, c, d, e: lax.sort([a, b, c, d, e], num_keys=1)[0],
          u32, iota, i32, f32, u32b)
    bench("sort_k1_u32_p4_i64pay",
          lambda a, b, c, d: lax.sort([a, b, c, d], num_keys=1)[0],
          u32, iota, i64, i64)
    bench("sort_k2_u32_p1",
          lambda a, b, c: lax.sort([a, b, c], num_keys=2, is_stable=True)[0],
          u32, u32b, iota)
    bench("sort_k1_i64_p1",
          lambda a, b: lax.sort([a, b], num_keys=1, is_stable=True)[0],
          i64, iota)

    # ---- gather ---------------------------------------------------------
    bench("gather_i32_random", lambda x, ix: jnp.take(x, ix, axis=0),
          i32, perm)
    bench("gather_i32_monotone", lambda x, ix: jnp.take(x, ix, axis=0),
          i32, sorted_idx)
    bench("gather_i64_random", lambda x, ix: jnp.take(x, ix, axis=0),
          i64, perm)
    bench("gather_f32_random", lambda x, ix: jnp.take(x, ix, axis=0),
          f32, perm)

    # ---- scatter --------------------------------------------------------
    bench("scatter_set_i32_random",
          lambda ix, v: jnp.zeros((n,), jnp.int32).at[ix].set(v), perm, i32)
    bench("scatter_set_i32_sorted_unique",
          lambda v: jnp.zeros((n,), jnp.int32).at[iota].set(
              v, indices_are_sorted=True, unique_indices=True), i32)
    bench("scatter_set_i32_monotone",
          lambda ix, v: jnp.zeros((n,), jnp.int32).at[ix].set(
              v, indices_are_sorted=True), sorted_idx, i32)
    bench("scatter_add_i32_random",
          lambda ix, v: jnp.zeros((n,), jnp.int32).at[ix].add(v), perm, i32)
    bench("scatter_add_i64_random",
          lambda ix, v: jnp.zeros((n,), jnp.int64).at[ix].add(v), perm, i64)

    # ---- scans ----------------------------------------------------------
    bench("cumsum_i32", lambda x: jnp.cumsum(x), i32)
    bench("cumsum_i64", lambda x: jnp.cumsum(x.astype(jnp.int64)), i32)
    bench("cumsum_f32", lambda x: jnp.cumsum(x), f32)
    bench("cumsum_f64", lambda x: jnp.cumsum(x.astype(jnp.float64)), f32)

    boundary = jnp.asarray(rng.random(n) < 0.01)

    def segscan_i64(b, v):
        def combine(x, y):
            xb, xv = x
            yb, yv = y
            return xb | yb, jnp.where(yb, yv, xv + yv)
        return lax.associative_scan(combine, (b, v.astype(jnp.int64)))[1]

    bench("segscan_tuple_i64", segscan_i64, boundary, i32)

    def segscan_f64(b, v):
        def combine(x, y):
            xb, xv = x
            yb, yv = y
            return xb | yb, jnp.where(yb, yv, xv + yv)
        return lax.associative_scan(combine, (b, v.astype(jnp.float64)))[1]

    bench("segscan_tuple_f64", segscan_f64, boundary, f32)

    # ---- expansion / search ---------------------------------------------
    counts = jnp.asarray(rng.integers(0, 3, size=n, dtype=np.int32))
    bench("repeat_total_n",
          lambda c: jnp.repeat(iota, c, total_repeat_length=n), counts,
          note="jnp.repeat with static total")
    small = jnp.sort(u32[:4096])
    bench("searchsorted_4096", lambda q: jnp.searchsorted(small, q), u32,
          note="range-partition bucket map")

    # broadcast-compare bucketing: n x 256 compare-reduce (the searchsorted
    # substitute for 256 splitters)
    spl = jnp.sort(u32[:256])
    bench("bucket256_compare",
          lambda q: jnp.sum(q[:, None] >= spl[None, :], axis=1), u32)

    # ---- MXU calibration -------------------------------------------------
    m = 4096
    a8 = jnp.asarray(rng.integers(-127, 127, (m, m), dtype=np.int8))
    b8 = jnp.asarray(rng.integers(-127, 127, (m, m), dtype=np.int8))
    bench("matmul_i8_4096",
          lambda a, b: lax.dot_general(
              a, b, (((1,), (0,)), ((), ())),
              preferred_element_type=jnp.int32), a8, b8,
          note=f"{2 * m**3 / 1e9:.0f} GMAC")
    abf = jnp.asarray(rng.standard_normal((m, m)).astype(np.float32)).astype(jnp.bfloat16)
    bench("matmul_bf16_4096",
          lambda a, b: lax.dot_general(
              a, b, (((1,), (0,)), ((), ())),
              preferred_element_type=jnp.float32), abf, abf)

    if args.out:
        with open(args.out, "a") as f:
            for r in results:
                f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
