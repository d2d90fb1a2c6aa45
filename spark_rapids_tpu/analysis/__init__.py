"""Static analysis over physical plans (docs/analysis.md).

The engine's value proposition is Spark-exact semantics, yet two of the
last PRs shipped soundness bugs only human review caught: a stale
partitioning claim that let `exchange_planning` elide a required shuffle
(silently duplicating/dropping groups), and a bound-method capture in a
process-global jitted-primitive cache that pinned dead executors. This
package turns those one-off review findings into a permanent machine
check that gates every optimizer rule, executor tier and plan:

- `verifier`: the static plan verifier — symbolic schema/dtype
  propagation, sharding/partitioning soundness (re-derived bottom-up with
  the SAME `transfer_part` transfer function the runtime uses), and
  rewrite-pair legality checks mirroring each optimizer rule's side
  conditions. Wired as the builder's validation backend, a debug-mode
  pre-execution gate (`SPARK_RAPIDS_TPU_VERIFY_PLANS`, on in tests), and
  the optimizer's per-rule fall-back diagnostic.
- `footprint`: the static resource certifier — an abstract interpreter
  propagating sound `[lo, hi]` row intervals and byte footprints
  (columnar widths, validity planes, join/aggregate working sets,
  exchange payloads) per operator, consumed by the executor's admission
  gate, the optimizer's broadcast byte-legality proof, and the capped
  tier's cold-run cap seeding. Its soundness inequality (certified hi >=
  observed, per op) is fuzz property 5 and a nightly NDS gate.
- `fuzz`: the property-based plan fuzzer — a seeded random DAG generator
  over all 11 operator kinds whose cases must verify, optimize cleanly,
  and (being small) execute with optimized-vs-unoptimized eager parity.
  A fixed corpus runs premerge; a deep seeded sweep runs nightly.

The AST-level sibling is `tools/lint_hazards.py`: the codebase linter for
the known JAX hazard patterns (self capture in jit closure caches,
host-sync on traced values, tracer branches, env reads outside config.py,
nondeterministic iteration feeding fingerprints, unlocked shared-state
mutation), and `tools/lint_concurrency.py` for the lock-order graph.
"""
from .footprint import (ResourceAdmissionError, ResourceCert, certify,
                        certify_nodes)
from .verifier import (PlanVerificationError, VerifyReport, Violation,
                       verify, verify_rewrite)

__all__ = ["PlanVerificationError", "VerifyReport", "Violation",
           "verify", "verify_rewrite", "ResourceAdmissionError",
           "ResourceCert", "certify", "certify_nodes"]
