"""Chip probe behind the constants of `ops/gather.py`'s row compaction
(`FEW_KEPT`, `RIDE_WORDS`): what a row costs each way of leaving rows out.

    chiprun --chips 1 --timeout 2700 -- python3 -m tools.probe_compaction
    JAX_PLATFORMS=cpu python3 -m tools.probe_compaction --rows 100000   # rehearsal

Prints one line a reading (`ms` is the fastest of three runs after the
compiling one) and writes them all to `chiprun_out/probe_compaction.jsonl`.
PERF.md section 6 (PR 42) holds the table read off it.

`--slots N` (PR 45; `--rows` empty runs it alone) sets the two ways of
putting a permuted side into an outer join's output order beside each
other at N rows (15,334,665: `q13.batch`'s slots): `rows_by_slot`, a
not-stable sort on a unique 32-bit key with 5 and 6 words riding, against
`jnp.take` of an int64 plane through a random map.

    chiprun --chips 1 -- python3 -m tools.probe_compaction --rows --slots 15334665
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.ops import gather
from spark_rapids_tpu.ops.scans import running

OUT = os.path.join("chiprun_out", "probe_compaction.jsonl")


@functools.partial(jax.jit, static_argnames=("total",))
def pack_rows(mask, total: int):
    """What `kept_rows` ran until PR 42: a running count and one scatter of
    the kept rows' numbers."""
    n = mask.shape[0]
    at = running(mask.astype(jnp.int32)) - 1
    return jnp.zeros((total,), jnp.int32).at[
        jnp.where(mask, at, total)].set(jnp.arange(n, dtype=jnp.int32),
                                        mode="drop")


@jax.jit
def take_all(arrays, rows):
    return [jnp.take(a, rows, axis=0) for a in arrays]


@functools.partial(jax.jit, static_argnames=("kept", "key"))
def sort_flagged(mask, arrays, *, kept: int, key: str):
    """What `rows_by_sort` is measured against: a STABLE sort keyed on the
    dropped flag (the chip's compiler adds the row numbers as one more
    operand and compares two keys)."""
    got = jax.lax.sort([(~mask).astype(key)] + list(arrays), num_keys=1,
                       is_stable=True)
    return [p[:kept] for p in got[1:]]


def timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args, **kwargs))
    first = time.perf_counter() - t0
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        best = min(best, time.perf_counter() - t0)
    return first, best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, nargs="*",
                    default=[15_000_000, 60_000_000])
    ap.add_argument("--words", type=int, nargs="*", default=[1, 2, 4, 8, 12])
    ap.add_argument("--slots", type=int, nargs="*", default=[15_334_665])
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args()
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    sink = open(OUT, "a")

    def report(n, what, kept, words, first, best, **more):
        line = dict(device=dev.device_kind, rows=n, what=what, kept=kept,
                    words=words, first_s=round(first, 3),
                    ms=round(best * 1e3, 3),
                    ns_per_row=round(best * 1e9 / n, 3),
                    ns_per_kept=round(best * 1e9 / max(kept, 1), 3), **more)
        print(json.dumps(line), flush=True)
        sink.write(json.dumps(line) + "\n")
        sink.flush()

    for n in args.rows:
        big = n > 20_000_000
        rng = np.random.default_rng(args.seed)
        plane = jnp.asarray(rng.integers(-2**31, 2**31, n, dtype=np.int64)
                            .astype(np.int32))
        wide = jnp.asarray(rng.integers(-2**62, 2**62, n, dtype=np.int64))
        draw = rng.random(n)

        def mask_of(share):
            m = draw < share
            return jnp.asarray(m), int(m.sum())

        most, most_kept = mask_of(0.989)

        # the sort carrying w 32-bit words
        for w in args.words:
            arrays = [plane + jnp.int32(j) for j in range(w)]
            first, best = timed(
                gather.rows_by_sort, most, arrays, kept=most_kept,
                groups=gather.ride_groups((1,) * w, limit=w))
            report(n, "sort", most_kept, w, first, best)
            del arrays
        # the row numbers alone (what `kept_rows` runs past FEW_KEPT)
        first, best = timed(gather.rows_by_sort, most, [], kept=most_kept)
        report(n, "sort.rows", most_kept, 0, first, best)
        # two int64 columns as they are (q13.batch's filter)
        first, best = timed(gather.rows_by_sort, most, [wide, wide + 1],
                            kept=most_kept, groups=((0, 1),))
        report(n, "sort.int64x2", most_kept, 4, first, best)
        if not big:
            # the stable sort on the dropped flag; a validity plane beside
            # a column
            for key in ("int32", "bool"):
                first, best = timed(sort_flagged, most, [wide, wide + 1],
                                    kept=most_kept, key=key)
                report(n, "sort_flagged." + key, most_kept, 4, first, best)
            first, best = timed(gather.rows_by_sort, most, [wide, most],
                                kept=most_kept, groups=((0, 1),))
            report(n, "sort.int64+bool", most_kept, 3, first, best)
        # what the share kept does to the sort, and to the old pack
        for share in (0.989, 0.5, 1 / 8, 1 / 32):
            m, kept = mask_of(share)
            if share == 1 / 8 and not big:
                first, best = timed(gather.rows_by_sort, m, [wide, wide + 1],
                                    kept=kept, groups=((0, 1),))
                report(n, "sort.int64x2", kept, 4, first, best, share=share)
            if share != 0.989:
                first, best = timed(gather.rows_by_sort, m, [], kept=kept)
                report(n, "sort.rows", kept, 0, first, best, share=share)
            first, best = timed(pack_rows, m, kept)
            report(n, "pack_rows", kept, 1, first, best, share=share)
            rows = pack_rows(m, kept)
            first, best = timed(take_all, [wide, wide + 1], rows)
            report(n, "take.int64x2", kept, 4, first, best, share=share)
            del rows
        # positions of few rows, alone and with gathered columns
        for one_in in (10_000, 256, 32, 8):
            m, kept = mask_of(1 / one_in)
            for arrays, w, name in (([], 0, "positions"),
                                    ([wide, wide + 1], 4,
                                     "positions.int64x2")):
                first, best = timed(gather.rows_by_position, m, arrays,
                                    kept=kept)
                report(n, name, kept, w, first, best, one_in=one_in)
        del plane, wide

    for n in args.slots:
        # a permuted side to its slots: one sort on the slot, or a gather
        # through the map a plane
        rng = np.random.default_rng(args.seed)
        where = rng.permutation(n).astype(np.int32)
        slot, none = jnp.asarray(where), jnp.zeros((0,), jnp.int32)
        wide = jnp.asarray(rng.integers(-2**62, 2**62, n, dtype=np.int64))
        plane = jnp.asarray(where) + jnp.int32(7)
        for w, arrays in ((5, [wide, wide + 1, plane]),
                          (6, [wide, wide + 1, plane, plane + 1])):
            first, best = timed(
                gather.rows_by_slot, slot, none, arrays, slots=n,
                groups=gather.ride_groups(gather.plane_words(arrays)))
            report(n, "slot_sort", n, w, first, best)
        first, best = timed(take_all, [wide], slot)
        report(n, "take.int64", n, 2, first, best)
        del where, slot, wide, plane


if __name__ == "__main__":
    main()
