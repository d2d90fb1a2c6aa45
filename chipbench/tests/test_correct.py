"""The two tests the contract keeps beside the comparison that decides
`correct`, at the rehearsal's tiny sizes on the CPU:

- the control (the reference with its gathered payloads passed through
  bfloat16) comes out as not correct, on three seeds, in every cell;
- a whole run with the timed path broken underneath (one value of an
  answer altered where it is produced) sees `correct` come out false, and
  the same run unbroken sees it true.

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q -p no:cacheprovider
"""
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


@pytest.fixture(autouse=True)
def cpu_devices(monkeypatch):
    from chipbench import harness, rehearse
    monkeypatch.setattr(harness, "require_devices", rehearse.cpu_devices)


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    from chipbench import control
    assert control.main(["--workload", cell, "--seeds", "3,2147483659,77"],
                        platform="cpu", tiny=True) == 0


def _drive(cell):
    import time
    from chipbench import run
    return run.drive(cell, 2**31 + 5, 1.0, False, platform="cpu", tiny=True,
                     t_process=time.perf_counter())


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(cell, monkeypatch):
    from spark_rapids_tpu import Column, Table
    from spark_rapids_tpu.plan import PlanExecutor
    assert _drive(cell)["correct"] is True
    real = PlanExecutor.execute

    def altered(self, *a, **kw):
        res = real(self, *a, **kw)
        t = res.table
        last = t.columns[-1]           # the aggregate: revenue, or cnt
        cols = list(t.columns[:-1]) + [Column(
            dtype=last.dtype, length=last.length,
            data=last.data.at[0].add(1), validity=last.validity)]
        res.table = Table(cols, names=list(t.names))
        return res

    monkeypatch.setattr(PlanExecutor, "execute", altered)
    line = _drive(cell)
    assert line["correct"] is False and line["failed"] > 0


def test_trace_reduction_on_the_recorded_trace():
    from chipbench import rehearse
    rehearse.trace_self_test()
