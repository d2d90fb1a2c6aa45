"""CPU rehearsal: the same code path as `chipbench.run`, at the tiny sizes
each configuration file gives under `rehearsal`, on the CPU backend.

    JAX_PLATFORMS=cpu python3 -m chipbench.rehearse --workload q3.tasks

Checks results against the reference, reduces the recorded chip trace in
`tests/data/` as a self-test of the trace reduction, and prints NO metrics
line: a number from a CPU run is never a device number. A `--trace 1`
rehearsal cannot reduce its own trace (the CPU backend has no device
plane), so it is not offered.
"""
import argparse
import json
import os
import sys
import time


def trace_self_test() -> dict:
    """The reduction over the small trace recorded on the chip
    (`tests/record_trace.py`): three sorts and three adds with 20 ms host
    pauses between them."""
    from chipbench import trace
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tests", "data")
    with open(os.path.join(here, "small.spans.json")) as f:
        kept = json.load(f)
    out = trace.reduce(os.path.join(here, "small.xplane.pb"),
                       [tuple(s) for s in kept["spans"]], kept["syncs"])
    sorts = sum(s for n, s in out["op_seconds"].items()
                if trace.opcode(n) == "sort")
    assert out["devices"] == 1, out
    assert 0 < out["busy_s"] < out["window_s"] < 0.2, out
    assert abs(out["busy_s"] - 0.000732757) < 1e-9, out["busy_s"]
    assert sorts / out["busy_s"] > 0.9, sorts
    assert out["device_ops"][0][0] == "jit_sort_step/sort.6:sort", out
    assert out["idle_gaps"][0][0] == "generate", out["idle_gaps"]
    assert 0.019 < out["idle_gaps"][0][1] < 0.03, out["idle_gaps"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 11)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        print("chipbench.rehearse: set JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2
    from chipbench import harness, run
    harness.require_devices = cpu_devices
    trace_self_test()
    print("trace reduction: self-test on tests/data/small.xplane.pb passed")
    line = run.drive(args.workload, args.seed, args.seconds, False,
                     platform="cpu", tiny=True,
                     t_process=time.perf_counter())
    ok = line["correct"] and line["attempted"] > 0
    print(f"rehearsal of {args.workload}: correct {line['correct']}, "
          f"attempted {line['attempted']}, failed {line['failed']} "
          "(no metrics: this was the CPU)")
    return 0 if ok else 1


def cpu_devices(cell, platform):
    """In `harness.require_devices`' place: the rehearsal has no chip and
    no row in peaks.json, so it borrows the v5e's for the readers."""
    import jax
    from chipbench import harness
    peaks = harness.read_json(os.path.join(harness.HERE, "peaks.json"))
    return jax.devices()[:cell.chips], peaks["devices"]["TPU v5 lite"]


if __name__ == "__main__":
    sys.exit(main())
