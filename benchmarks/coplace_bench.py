"""Nightly co-placement gate (ci/nightly.sh, docs/optimizer.md#placement).

Runs NDS q5 and q72 through the eager plan tier with the placement rule
OFF then ON (SPARK_RAPIDS_TPU_PLACEMENT), cold then warm under fresh
per-fingerprint stats stores, asserting the co-placement contract:

- bit-exact result parity: placement on == off, cold and warm (the rule
  may change WHERE a subtree executes, never what it returns);
- `placement_overlap_ms > 0` on >= 1 plan: the host-placed build side
  measurably overlapped device execution rather than serializing at the
  join (q72's hd/dates dimension subtrees are the expected candidates —
  q5's date dimension is DAG-shared across channels, so the rule must
  decline it and q5 doubles as placement-declines-shared coverage);
- warm placed wall <= warm device-only wall on every plan that placed,
  ON A REAL DEVICE BACKEND (not measured on the chip yet): there the host
  threads are genuinely different silicon from the device walk, so an overlap
  that loses wall-clock is a placement-rule regression. Under the CPU
  nightly (JAX_PLATFORMS=cpu) the "device" walk and the host threads
  share the same cores — co-placement cannot win wall-clock by
  construction, so the strict gate would only measure thread-spawn
  overhead; instead the warm-on/warm-off ratio is REPORTED to JSONL
  (the trajectory finally records a co-placement number) and bounded
  loosely (<= 1.5) to catch serialization-class regressions where the
  placed subtree stops overlapping and runs strictly after the walk.

Every row stamps `placement`/`placement_overlap_ms` alongside `backend`
and `session` (tools/lint_metrics.py missing-placement-stamp: an
overlap number is a host-vs-device comparison by construction).
"""
import contextlib
import os
import sys

sys.path.insert(0, ".")

from benchmarks.common import emit_record, parse_args        # noqa: E402
from benchmarks.nds_plans import kernels_of                  # noqa: E402
from benchmarks.nds_plans import (q5_inputs, q5_plan,        # noqa: E402
                                  q72_inputs, q72_plan)


@contextlib.contextmanager
def _placement(on: bool):
    """SPARK_RAPIDS_TPU_PLACEMENT toggle, restored on exit — config
    reads the env at use time, so toggling between runs is the same
    contract the serving layer relies on."""
    key = "SPARK_RAPIDS_TPU_PLACEMENT"
    prev = os.environ.get(key)
    os.environ[key] = "on" if on else "off"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = prev


def _placed_ops(res):
    """Labels the executed plan ran on the host thread (stamped by
    plan/executor.py's co-placement dispatch)."""
    return sorted(label for label, m in res.metrics.items()
                  if m.placement == "host")


def _overlap_ms(res):
    """Total measured host/device overlap across consuming operators."""
    return sum(m.placement_overlap_ms for m in res.metrics.values())


def _run(name, plan, inputs, n_rows):
    import jax
    from spark_rapids_tpu.plan import PlanExecutor
    from spark_rapids_tpu.plan import stats as stats_mod

    results, runs = {}, {}

    def one(mode, phase, store):
        with _placement(mode == "on"), stats_mod.scoped_store(store):
            ex = PlanExecutor(mode="eager", optimize=True)
            res = ex.execute(plan, inputs)
            results[(mode, phase)] = res.compact().to_pydict()
            runs[(mode, phase)] = res
            sources = (res.optimizer or {}).get("decision_sources") or {}
            emit_record(
                f"coplace_{name}", {"phase": phase}, res.wall_ms, n_rows,
                impl="plan_eager", optimizer="on",
                rules_fired=(res.optimizer or {}).get("rules_fired"),
                kernels=kernels_of(res),
                backend=jax.default_backend(),
                session="",                 # outside serving
                placement=mode,
                placement_overlap_ms=round(_overlap_ms(res), 3),
                placed_ops=_placed_ops(res),
                placement_decisions={k: v for k, v in sources.items()
                                     if k.endswith("/placement")})
            return res

    # separate stores per variant: the off runs must stay a pure
    # device-only baseline — observed walls from a placed run would
    # turn the "off" warm wall into a warm hybrid (docs/adaptive.md)
    for mode in ("off", "on"):
        # path="": must not inherit SPARK_RAPIDS_TPU_STATS_PATH state
        store = stats_mod.StatsStore(capacity=32, path="")
        one(mode, "cold", store)
        one(mode, "warm", store)

    assert (results[("on", "cold")] == results[("off", "cold")]
            == results[("on", "warm")] == results[("off", "warm")]), \
        f"{name}: placement changed the result"

    warm_on, warm_off = runs[("on", "warm")], runs[("off", "warm")]
    placed = _placed_ops(warm_on)
    if placed:
        import jax
        if jax.default_backend() != "cpu":
            # real device: host threads are different silicon — losing
            # wall-clock against the single-backend walk is a regression
            assert warm_on.wall_ms <= warm_off.wall_ms, \
                (f"{name}: warm placed wall {warm_on.wall_ms:.1f} ms "
                 f"exceeded warm device-only wall {warm_off.wall_ms:.1f} "
                 f"ms (placed={placed})")
        else:
            # CPU backend: host threads share the walk's own cores, so
            # only bound the overhead — a placed subtree that stops
            # overlapping (runs strictly after the walk) blows past this
            assert warm_on.wall_ms <= 1.5 * warm_off.wall_ms, \
                (f"{name}: warm placed wall {warm_on.wall_ms:.1f} ms is "
                 f">1.5x the warm device-only wall {warm_off.wall_ms:.1f}"
                 f" ms — the host subtree serialized (placed={placed})")
    # report-not-gate: the on/off warm wall ratio trajectory
    emit_record(f"coplace_{name}", {"phase": "ratio"},
                warm_on.wall_ms, n_rows,
                impl="plan_eager", optimizer="on",
                kernels=kernels_of(warm_on),
                backend=jax.default_backend(), session="",
                placement="on",
                placement_overlap_ms=round(_overlap_ms(warm_on), 3),
                placed_ops=placed,
                warm_wall_ratio=round(
                    warm_on.wall_ms / max(warm_off.wall_ms, 1e-9), 4))
    return warm_on


def main(argv=None):
    args = parse_args(argv)
    n = max(int(100_000 * args.scale), 10_000)

    from benchmarks.bench_nds_q5 import build_tables as bt5
    from benchmarks.bench_nds_q72 import build_tables as bt72

    # q5: the date dimension is DAG-shared across all three channel
    # semi-joins, so _host_placeable must DECLINE every candidate —
    # this query gates "shared subtrees never place" (zero placed ops,
    # results identical by construction of the decline).
    q5_in = q5_inputs(*bt5(n, seed=3))
    w5 = _run("q5", q5_plan(), q5_in,
              n_rows=sum(t.num_rows for t in q5_in.values()))
    assert not _placed_ops(w5), \
        f"q5: shared date dimension was placed ({_placed_ops(w5)})"

    # q72: the hd and dates build sides are exclusive scan+filter
    # subtrees whose certified output bounds fit the cold threshold —
    # the overlap gate lives here.
    q72_in = q72_inputs(*bt72(n, seed=5))
    w72 = _run("q72", q72_plan(), q72_in,
               n_rows=sum(t.num_rows for t in q72_in.values()))
    assert _placed_ops(w72), \
        (f"q72: no subtree placed (decisions="
         f"{(w72.optimizer or {}).get('decision_sources')})")
    assert _overlap_ms(w72) > 0, \
        (f"q72: placed {_placed_ops(w72)} but measured zero overlap — "
         "the host subtree serialized at the join")
    print("co-placement OK", file=sys.stderr)


if __name__ == "__main__":
    main()
