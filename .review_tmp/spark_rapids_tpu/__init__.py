"""spark_rapids_tpu — TPU-native columnar acceleration layer for Apache Spark.

A from-scratch re-design of the capabilities of NVIDIA's spark-rapids-jni
(reference at /root/reference; structural analysis in SURVEY.md) on an
idiomatic JAX/XLA/Pallas/PJRT stack:

- `columnar`: HBM-resident Arrow-layout Column/Table substrate (pytrees).
- `ops`: Spark-exact kernels — casts, hashes, bloom filter, decimal128
  arithmetic, datetime rebase, timezones, zorder, parse_uri, JSON→map,
  histogram/percentile, row↔columnar conversion, groupby/join/sort.
- `runtime`: host-side C++ task/memory arbitration state machine (retry,
  split-and-retry, BUFN, deadlock watchdog, OOM injection, metrics) — the
  TPU equivalent of SparkResourceAdaptor (SURVEY.md §2.2).
- `parallel`: device-mesh sharding + ICI/DCN all-to-all partition exchange
  (the slot the GPU stack fills with UCX shuffle).
- `plan`: physical-plan subsystem — typed operator DAG (Scan/Filter/…/
  HashJoin/HashAggregate/Exchange) over Table, validating builder, and an
  executor with eager / capped-jit / distributed tiers, per-operator
  metrics (explain/profile) and plan-granularity cap escalation.
- `serving`: multi-tenant front door — fair-share session scheduler with
  certified per-session memory quotas, bounded-queue backpressure,
  breaker-aware degradation, and a fingerprint+digest plan-result cache.
- `io`: native parquet footer parse/prune/filter + chunked page reader.
- `interop`: Arrow C Data Interface export/import (JVM-facing surface).
- `faultinj`: config-driven fault injection over the device-call surface.

int64 is pervasive in Spark data (timestamps, longs, xxhash64), so this
package enables jax x64 mode on import; XLA:TPU emulates s64/u64 with 32-bit
pairs, which is correct (full wrap-around) and off the hot matmul path.
"""
import jax

jax.config.update("jax_enable_x64", True)

from . import dtypes                                    # noqa: E402
from .columnar import Column, Table                     # noqa: E402

from .version import __version__, version_info

__all__ = ["dtypes", "Column", "Table", "api", "__version__", "version_info"]


_LAZY_SUBMODULES = ("api", "ops", "parallel", "io", "runtime", "interop",
                    "columnar", "faultinj", "config", "plan", "serving")


def __getattr__(name):
    # Subpackages import modules whose module-level jnp constants initialize
    # the JAX backend — lazy (PEP 562) so a bare `import spark_rapids_tpu`
    # stays side-effect-free and callers can pin a platform first.
    if name in _LAZY_SUBMODULES:
        import importlib
        return importlib.import_module("." + name, __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

# Fault-injector auto-load (reference: libcufaultinj.so via
# CUDA_INJECTION64_PATH at cuInit — faultinj/README.md:20-24).
from . import faultinj as _faultinj                     # noqa: E402

_faultinj.maybe_install_from_env()
