"""Physical-plan subsystem: the declarative operator layer between the
plugin-facing API and the `ops`/`parallel` kernel tiers.

The reference stack receives *plans* from Spark's Catalyst optimizer and
lowers them operator-by-operator onto libcudf ("Accelerating Presto with
GPUs" makes the same argument for a declarative operator layer above native
kernels; StreamBox-HBM uses per-operator pipelines as the unit of memory
arbitration — PAPERS.md). Before this subsystem every NDS query hand-wired
operator sequencing, cap management and retry; now a query is a `Plan` — a
DAG of typed operator nodes over `columnar.Table` — and the engine-side
concerns live in ONE executor:

- `nodes` / `expr`: the operator set (Scan, Filter, Project, HashJoin,
  HashAggregate, Window, Sort, Exchange, Limit, Union) and the expression
  mini-language predicates/projections are written in.
- `builder`: fluent, validating construction (`PlanBuilder`); schema and
  reference errors surface at build time as `PlanValidationError`.
- `optimizer`: Catalyst-style rule pipeline (column pruning, predicate/
  limit pushdown, constant folding, Filter+Project fusion into
  `FusedSelect`, Sort+Limit fusion into `TopK`, join build-side
  selection) run to fixpoint inside `execute()` before tier dispatch,
  plus the canonical `plan_fingerprint` the executor keys its compiled-
  program and caps memos by (docs/optimizer.md).
- `executor`: walks the DAG composing the public `ops` kernels (eager tier)
  or traces the whole plan into ONE capped XLA program (jit tier) with
  geometric cap escalation via `parallel.autoretry` at plan granularity;
  with a device mesh the eager walk runs full-plan SPMD over sharded
  relations (`distributed`, docs/distributed.md) — shuffle/broadcast
  joins, fused two-phase aggregates, sample-sort — crossing the ICI only
  at the `Exchange` boundaries the optimizer plans, and gathering to one
  device only at the sink;
  admission (`runtime.admission`), `faultinj` interception and
  `utils.tracing` spans apply per operator. Device failures resolve
  through the `runtime.health` degradation policy — backoff-paced retries
  for transient faults, circuit-breaker trip + degraded CPU-tier
  completion for sticky/fatal ones (docs/robustness.md).
- `metrics`: `explain()` (pre-run plan tree) and `profile()` (post-run
  per-operator rows/bytes/wall-time/retry counts).

Build-time validation, execute()'s bind-time re-resolution, and the
debug-mode pre-execution gate (`SPARK_RAPIDS_TPU_VERIFY_PLANS`) all
route through the static plan verifier (`spark_rapids_tpu.analysis`,
docs/analysis.md) — one error vocabulary of invariant codes naming the
offending operator, from the builder to the optimizer's fall-back
diagnostics.

See docs/plan.md for the operator contract and how a JVM/plugin front-end
targets this layer.
"""
from .expr import (col, lit, scalar_max, scalar_min, scalar_sum, is_null,
                   is_not_null, when, coalesce, Expr)
from .nodes import (Exchange, Filter, FusedSelect, HashAggregate, HashJoin,
                    Limit, PlanNode, Project, Scan, Sort, TopK, Union, Window)
from .builder import Plan, PlanBuilder, PlanValidationError
from .executor import PlanExecutor, PlanResult
from .metrics import OperatorMetrics
from .optimizer import (OptimizeReport, optimize, plan_fingerprint,
                        subtree_fingerprints)
from .stats import StatsStore, active_store, scoped_store

__all__ = [
    "col", "lit", "scalar_max", "scalar_min", "scalar_sum", "is_null",
    "is_not_null", "when", "coalesce", "Expr",
    "Scan", "Filter", "Project", "FusedSelect", "HashJoin",
    "HashAggregate", "Window", "Sort", "TopK", "Exchange", "Limit", "Union",
    "PlanNode",
    "Plan", "PlanBuilder", "PlanValidationError",
    "PlanExecutor", "PlanResult", "OperatorMetrics",
    "optimize", "plan_fingerprint", "subtree_fingerprints",
    "OptimizeReport",
    "StatsStore", "active_store", "scoped_store",
]
