"""One run of one cell, on the accelerator this process is started on.

    python3 -m chipbench.run --workload q3.tasks --seed 7 --seconds 40 --trace 0

Finds its TPU (and its row of `peaks.json`) or exits non-zero before a table
is built; loads, warms only the cell's own shapes, measures, checks against
the pandas reference, and prints as its last line the one JSON object the
contract asks for. There is no fallback to the CPU: `chipbench.rehearse`
drives the same code at a tiny size and prints no metrics.
"""
import time

T_PROCESS = time.perf_counter()      # set-up is counted from here

import argparse                      # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402


def drive(workload: str, seed: int, seconds: float, traced: bool,
          platform: str = "tpu", tiny: bool = False,
          t_process: float = None) -> dict:
    """-> the result line. BENCH_RUN, which the driver sets for its own
    use, is not read."""
    # libtpu would log under /tmp: a run writes inside its checkout only
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import harness
    cell = harness.Cell(workload, tiny=tiny)
    run = harness.Run(cell, seed, seconds, traced, platform,
                      T_PROCESS if t_process is None else t_process)
    run.set_up()
    run.window()
    run.check()
    return run.result_line()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    line = drive(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
