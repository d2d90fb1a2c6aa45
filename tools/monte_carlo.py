"""Randomized task/allocation stress harness for the resource arbiter.

TPU-native equivalent of the reference's RmmSparkMonteCarlo
(/root/reference/src/test/java/com/nvidia/spark/rapids/jni/RmmSparkMonteCarlo.java,
SURVEY.md §4 tier 3): generate random "situations" — tasks issuing skewed
sequences of reserve/release ops, run them on a bounded worker pool (plus a
shuffle thread pool) against a small device budget, and measure completion,
retry/split counts, blocked time and wall clock. `--baseline` runs the same
situations WITHOUT the arbiter (plain bounded budget with timed waits) so the
two can be compared, exactly like the reference's `--baseline` mode.

Run nightly by ci/fuzz-test.sh. Example:

    python tools/monte_carlo.py --tasks 64 --parallelism 8 \
        --gpu-mib 3072 --task-max-mib 2048 --skewed
"""
from __future__ import annotations

import argparse
import json
import random
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import List

sys.path.insert(0, ".")

# This is a host-side state-machine fuzzer: it never launches device work, so
# pin the CPU backend before anything can initialize an accelerator.
import os  # noqa: E402
import jax  # noqa: E402
jax.config.update("jax_platforms", os.environ.get("SRT_MC_PLATFORM", "cpu"))

from spark_rapids_tpu.runtime import (DeviceSession, HardOOM,  # noqa: E402
                                      Reservation, ResourceArbiter, with_retry)

MIB = 1024 * 1024


# ---- situation generation (reference generateSituations) --------------------

@dataclass
class AllocOp:
    size: int          # bytes

@dataclass
class FreeOp:
    index: int         # which live buffer to free (mod len)

@dataclass
class OpSet:
    ops: List[object]
    is_shuffle: bool = False
    sleep_ms: int = 0

@dataclass
class TaskSpec:
    task_id: int
    op_sets: List[OpSet] = field(default_factory=list)


def generate_tasks(rng: random.Random, n_tasks: int, task_max_bytes: int,
                   max_allocs: int, max_sleep_ms: int, skewed: bool,
                   skew_amount: float, shuffle: bool) -> List[TaskSpec]:
    tasks = []
    for t in range(n_tasks):
        # skew: a few tasks allocate close to the whole task budget, most are
        # small (reference --skewed / --skewAmount)
        scale = 1.0
        if skewed and rng.random() < 0.2:
            scale = 1.0 + skew_amount
        spec = TaskSpec(task_id=t)
        for _ in range(rng.randint(1, 4)):
            ops: List[object] = []
            live = 0
            for _ in range(rng.randint(1, max_allocs)):
                if live and rng.random() < 0.4:
                    ops.append(FreeOp(rng.randrange(live)))
                    live -= 1
                else:
                    frac = rng.random() ** 2  # bias small
                    size = max(4096, int(task_max_bytes * frac * scale / max_allocs))
                    ops.append(AllocOp(size))
                    live += 1
            is_shuf = shuffle and rng.random() < 0.25
            ops_sleep = rng.randint(0, max_sleep_ms)
            spec.op_sets.append(OpSet(ops, is_shuffle=is_shuf, sleep_ms=ops_sleep))
        tasks.append(spec)
    return tasks


# ---- arbitrated run ---------------------------------------------------------

@dataclass
class Stats:
    completed: int = 0
    failed: int = 0
    retries: int = 0
    split_retries: int = 0
    blocked_ns: int = 0
    lost_ns: int = 0
    wall_s: float = 0.0

    def as_json(self, mode: str) -> str:
        return json.dumps({"mode": mode, **self.__dict__})


def run_op_set(session: DeviceSession, op_set: OpSet, buffers: List[Reservation],
               split_level: int = 0):
    """Execute one op-set's allocs/frees under the retry protocol."""
    arb = session.arbiter

    def attempt(divisor: int):
        acquired: List[Reservation] = []
        try:
            for op in op_set.ops:
                if isinstance(op, AllocOp):
                    acquired.append(session.device.acquire(max(op.size // divisor, 1)))
                else:
                    pool = buffers if buffers else acquired
                    if pool:
                        session.device.release(pool.pop(op.index % len(pool)))
            if op_set.sleep_ms:
                time.sleep(op_set.sleep_ms / 1e3)
        except BaseException:
            for r in acquired:
                session.device.release(r)
            raise
        return acquired

    def rollback():
        # make state "spillable": free everything this task currently holds
        while buffers:
            session.device.release(buffers.pop())

    # SplitAndRetry = split the op set into two halves, each with every
    # allocation halved (divisor doubles per split level)
    results = with_retry(arb, attempt, 1,
                         split=lambda d: [d * 2, d * 2],
                         on_rollback=rollback)
    for acquired in results:
        buffers.extend(acquired)


def run_arbitrated(tasks: List[TaskSpec], parallelism: int, gpu_bytes: int,
                   shuffle_threads: int, task_retry: int) -> Stats:
    stats = Stats()
    mu = threading.Lock()
    t0 = time.perf_counter()
    with DeviceSession(device_limit_bytes=gpu_bytes) as session:
        arb = session.arbiter
        shuffle_pool = ThreadPoolExecutor(max_workers=max(shuffle_threads, 1))

        def run_task(spec: TaskSpec):
            arb.current_thread_is_dedicated_to_task(spec.task_id)
            buffers: List[Reservation] = []
            ok = False
            try:
                for attempt_no in range(task_retry + 1):
                    try:
                        for op_set in spec.op_sets:
                            if op_set.is_shuffle:
                                def shuf(op_set=op_set):
                                    arb.shuffle_thread_working_on_tasks([spec.task_id])
                                    sbuf: List[Reservation] = []
                                    try:
                                        run_op_set(session, op_set, sbuf)
                                    finally:
                                        while sbuf:
                                            session.device.release(sbuf.pop())
                                        arb.pool_thread_finished_for_tasks([spec.task_id])
                                arb.submitting_to_pool()
                                fut = shuffle_pool.submit(shuf)
                                try:
                                    fut.result()
                                finally:
                                    arb.done_waiting_on_pool()
                            else:
                                run_op_set(session, op_set, buffers)
                        ok = True
                        break
                    except HardOOM:
                        # roll everything back and retry the task from scratch
                        while buffers:
                            session.device.release(buffers.pop())
            finally:
                while buffers:
                    session.device.release(buffers.pop())
                with mu:
                    stats.retries += arb.get_and_reset_num_retry_throw(spec.task_id)
                    stats.split_retries += arb.get_and_reset_num_split_retry_throw(spec.task_id)
                    stats.blocked_ns += arb.get_and_reset_block_time_ns(spec.task_id)
                    stats.lost_ns += arb.get_and_reset_computation_time_lost_ns(spec.task_id)
                    if ok:
                        stats.completed += 1
                    else:
                        stats.failed += 1
                arb.task_done(spec.task_id)

        with ThreadPoolExecutor(max_workers=parallelism) as pool:
            futs = [pool.submit(run_task, spec) for spec in tasks]
            for f in futs:
                f.result()
        shuffle_pool.shutdown(wait=True)
    stats.wall_s = round(time.perf_counter() - t0, 3)
    return stats


# ---- baseline (no arbiter) --------------------------------------------------

class PlainBudget:
    """Bounded budget with timed condition waits — what you get WITHOUT the
    arbiter: no priorities, no deadlock detection, no retry protocol."""

    def __init__(self, limit: int, timeout_s: float = 2.0):
        self.limit = limit
        self.used = 0
        self.cv = threading.Condition()
        self.timeout_s = timeout_s

    def acquire(self, n: int) -> int:
        deadline = time.monotonic() + self.timeout_s
        with self.cv:
            while self.used + n > self.limit:
                left = deadline - time.monotonic()
                if left <= 0 or not self.cv.wait(timeout=left):
                    raise HardOOM("baseline allocation timed out (possible deadlock)")
            self.used += n
        return n

    def release(self, n: int):
        with self.cv:
            self.used -= n
            self.cv.notify_all()


def run_baseline(tasks: List[TaskSpec], parallelism: int, gpu_bytes: int,
                 task_retry: int) -> Stats:
    stats = Stats()
    mu = threading.Lock()
    budget = PlainBudget(gpu_bytes)
    t0 = time.perf_counter()

    def run_task(spec: TaskSpec):
        held: List[int] = []
        ok = False
        try:
            for _ in range(task_retry + 1):
                try:
                    for op_set in spec.op_sets:
                        for op in op_set.ops:
                            if isinstance(op, AllocOp):
                                held.append(budget.acquire(op.size))
                            elif held:
                                budget.release(held.pop(op.index % len(held)))
                        if op_set.sleep_ms:
                            time.sleep(op_set.sleep_ms / 1e3)
                    ok = True
                    break
                except HardOOM:
                    while held:
                        budget.release(held.pop())
        finally:
            while held:
                budget.release(held.pop())
            with mu:
                if ok:
                    stats.completed += 1
                else:
                    stats.failed += 1

    with ThreadPoolExecutor(max_workers=parallelism) as pool:
        futs = [pool.submit(run_task, spec) for spec in tasks]
        for f in futs:
            f.result()
    stats.wall_s = round(time.perf_counter() - t0, 3)
    return stats


# ---- CLI --------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--iterations", type=int, default=1)
    ap.add_argument("--tasks", type=int, default=32)
    ap.add_argument("--parallelism", type=int, default=8)
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--gpu-mib", type=int, default=3072,
                    help="device budget MiB (name kept for reference parity)")
    ap.add_argument("--task-max-mib", type=int, default=2048)
    ap.add_argument("--task-retry", type=int, default=2)
    ap.add_argument("--max-task-allocs", type=int, default=8)
    ap.add_argument("--max-task-sleep", type=int, default=2, help="ms")
    ap.add_argument("--shuffle-threads", type=int, default=2)
    ap.add_argument("--skewed", action="store_true")
    ap.add_argument("--skew-amount", type=float, default=2.0)
    ap.add_argument("--baseline", action="store_true",
                    help="also run without the arbiter and compare")
    args = ap.parse_args(argv)

    seed = args.seed if args.seed is not None else random.randrange(2**31)
    print(json.dumps({"seed": seed, "tasks": args.tasks,
                      "parallelism": args.parallelism,
                      "gpu_mib": args.gpu_mib, "task_max_mib": args.task_max_mib}))
    failures = 0
    for it in range(args.iterations):
        rng = random.Random(seed + it)
        tasks = generate_tasks(rng, args.tasks, args.task_max_mib * MIB,
                               args.max_task_allocs, args.max_task_sleep,
                               args.skewed, args.skew_amount,
                               shuffle=args.shuffle_threads > 0)
        st = run_arbitrated(tasks, args.parallelism, args.gpu_mib * MIB,
                            args.shuffle_threads, args.task_retry)
        print(st.as_json("arbitrated"))
        if st.failed:
            failures += st.failed
        if args.baseline:
            sb = run_baseline(tasks, args.parallelism, args.gpu_mib * MIB,
                              args.task_retry)
            print(sb.as_json("baseline"))
    # the arbitrated run must complete every task; that's the whole point
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
