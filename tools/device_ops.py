"""What a capped cell's device time is spent on, by source line.

    python3 -m chipbench.run --workload q3.tasks --seed 7 --seconds 50 --trace 1
    python3 tools/device_ops.py --workload q3.tasks

The second command reads the trace the first one left under
`.chipbench_trace/<cell>/` (same checkout, same call on the chip: the
compile cache then answers for the program) and prints the largest device
ops with what each is: its plan operator, the primitive that was traced
and the innermost Python line that traced it
(`PlanExecutor.device_op_sources`), then the same seconds summed by
(operator, source line), which is where the hundreds of small ops of an
unrolled scan show. Seconds are self times over the whole trace; "ms/run"
divides by the runs of `jit_capped_plan` in it. No number printed here is
a benchmark metric; PERF.md section 5 is written from it.
"""
import argparse
import bisect
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--top", type=int, default=24)
    ap.add_argument("--trace-dir", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import harness, program_spans, tpcds, trace
    from spark_rapids_tpu.config import place_compile_cache
    from spark_rapids_tpu.plan import PlanExecutor

    cell = harness.Cell(args.workload)
    if cell.traffic["tier"] != "capped":
        raise SystemExit(f"{cell.name} runs the eager tier: its operators "
                         "are separate programs, named in the trace as is")
    harness.require_devices(cell, "tpu")
    place_compile_cache()
    plan_mod = cell.plan
    inputs = {n: tpcds.table(c)
              for n, c in plan_mod.dimensions(cell.sizes).items()}
    gen = plan_mod.batch_generator(cell.sizes, cell.batch)
    for name, (cols, validity) in gen(*harness.batch_keys(cell, 0, 0)).items():
        inputs[name] = tpcds.table(cols, validity, plan_mod.COLUMNS[name])
    executor = PlanExecutor(mode="capped", caps=plan_mod.caps(cell.batch),
                            **cell.config.get("executor", {}))
    # one execution first, as the harness's set-up makes: the program the
    # window ran is the one at the capacities that execution ended on (the
    # cells' starting caps overflow once and double)
    res = executor.execute(plan_mod.plan(), inputs)
    print(f"{cell.name}: the program at caps {res.caps} "
          f"(attempts {res.attempts})")
    sources = executor.device_op_sources(plan_mod.plan(), inputs)

    trace_dir = args.trace_dir or os.path.join(ROOT, ".chipbench_trace",
                                               cell.name)
    from jax.profiler import ProfileData
    seconds, runs = {}, 0
    for plane in ProfileData.from_file(
            program_spans.find_trace(trace_dir)).planes:
        lines = {line.name: line for line in plane.lines}
        if not plane.name.startswith(trace.DEVICE_PREFIX) \
                or trace.OP_LINE not in lines:
            continue
        mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns))
                      for e in lines[trace.MODULE_LINE].events
                      if e.name.startswith(program_spans.CAPPED_MODULE))
        runs += len(mods)
        starts = [m[0] for m in mods]
        mine = []
        for e in lines[trace.OP_LINE].events:
            t0 = int(e.start_ns)
            i = bisect.bisect_right(starts, t0) - 1
            if i >= 0 and t0 < mods[i][1]:
                instr = trace.op_name(e.name).rsplit(":", 1)[0]
                mine.append((instr, t0, t0 + int(e.duration_ns)))
        for instr, own_ns in trace._self_times(mine):
            seconds[instr] = seconds.get(instr, 0.0) + own_ns / 1e9
    total = sum(seconds.values())
    runs = max(runs, 1)
    print(f"{cell.name}: {total:.3f} s of device time in "
          f"{program_spans.CAPPED_MODULE} over {runs} runs, "
          f"{1e3 * total / runs:.2f} ms a run; {len(seconds)} instructions, "
          f"{sum(1 for i in seconds if i not in sources)} without a source")
    unknown = ("", "?", "(compiler-made: no op_name in the text)")
    print(f"largest {args.top} ops:")
    for instr, s in sorted(seconds.items(), key=lambda x: -x[1])[:args.top]:
        owner, prim, where = sources.get(instr, unknown)
        print(f"  {instr:36s} {s:8.4f} s {1e3 * s / runs:7.2f} ms/run "
              f"{100 * s / total:5.1f}%  {owner:16s} {prim:16s} {where}")
    by_line = {}
    for instr, s in seconds.items():
        owner, prim, where = sources.get(instr, unknown)
        row = by_line.setdefault((owner, where), [0.0, 0, set()])
        row[0] += s
        row[1] += 1
        row[2].add(prim)
    print(f"largest {args.top} (operator, source line) sums:")
    for (owner, where), (s, n, prims) in sorted(
            by_line.items(), key=lambda x: -x[1][0])[:args.top]:
        print(f"  {owner:16s} {where:44s} {s:8.4f} s "
              f"{1e3 * s / runs:7.2f} ms/run {100 * s / total:5.1f}%  "
              f"{n:4d} ops  {'/'.join(sorted(prims))[:60]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
