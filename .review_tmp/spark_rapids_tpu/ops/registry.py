"""Per-backend kernel registry: (operator kind, backend, signature) → impl.

The engine grew backend-conditional kernels one ad-hoc dispatch at a time —
`ops/aggregate.py::_use_scan_kernel` (scan vs scatter groupby),
`ops/row_conversion.py::_use_word_kernel` (u32-word vs byte-concat row
images) — and the optimizer now produces fusion-shaped nodes (FusedSelect,
TopK) whose Pallas lowerings need the same choice. This module is the one
dispatch mechanism all of them share (docs/kernels.md):

- every operator kind registers exactly ONE `fallback=True` kernel: the
  universal lowering (jnp/XLA), eligible on every backend for every
  signature — selection can therefore never fail, only decline;
- non-fallback kernels register for specific backends (e.g. the Pallas TPU
  kernels register `backends=("tpu",)`) and may carry a `supports`
  predicate over the call-site `Signature` (dtype kinds, validity layout,
  operator parameters). An unsupported signature DECLINES cleanly to the
  next candidate at lookup time — strings/decimal128/nested inputs never
  error, they just run the fallback;
- `select()` consults the `SPARK_RAPIDS_TPU_KERNELS` override knob
  (config.py; e.g. `fused_select=xla,topk=pallas`). A forced kernel whose
  `supports` rejects the signature still declines to the fallback (a
  signature is data, not a typo), but an unknown op or kernel NAME raises —
  the same strict-typo policy as every other selector knob: a typo must
  not silently change which kernel an A/B capture measured.

The executor stamps the winning choice on `OperatorMetrics.kernel`
("pallas:fused_select", "scan:groupby", ...) and folds the override knob +
backend into the capped tier's jit-cache key, so compiled programs never
alias across kernel selections.

With the per-fingerprint stats store active (plan/stats.py,
docs/adaptive.md), `select()` additionally consults OBSERVED timings: a
candidate that has benched slower than its fallback on this exact
(op, backend, signature) shape loses the tie-break — declined with the
measured numbers, `stats_demoted` stamped on the choice. The capped
tier's jit-cache key folds in the store's `kernel_epoch` so compiled
programs never alias across demotion states.

Providers register lazily: importing this module imports nothing heavy;
the first `select(op)` imports the module listed in `_PROVIDERS`, whose
import-time registration fills the catalog.
"""
from __future__ import annotations

import dataclasses
import importlib
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

__all__ = ["Signature", "Kernel", "KernelChoice", "KernelRegistry",
           "REGISTRY", "select"]


@dataclasses.dataclass(frozen=True)
class Signature:
    """What a kernel is allowed to condition on: the dtype/validity layout
    of the columns crossing the operator plus op-specific static extras
    (tier, key count, limit, predicate compilability...). Hashable and
    cheap — built per dispatch, compared by `supports` predicates."""

    columns: Tuple[Tuple[str, bool], ...] = ()   # (Kind.value, has_validity)
    extras: Tuple[Tuple[str, object], ...] = ()

    @staticmethod
    def of(cols: Sequence = (), **extras) -> "Signature":
        col_sig = tuple((c.dtype.kind.value, c.validity is not None)
                        for c in cols)
        return Signature(columns=col_sig,
                         extras=tuple(sorted(extras.items())))

    def extra(self, key: str, default=None):
        for k, v in self.extras:
            if k == key:
                return v
        return default

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple(k for k, _ in self.columns)

    @property
    def any_validity(self) -> bool:
        return any(v for _, v in self.columns)


@dataclasses.dataclass(frozen=True)
class Kernel:
    op: str
    name: str                      # "pallas", "xla", "scan", "word", ...
    fn: Optional[Callable]         # op-specific entry point (None when the
    #                                caller owns the lowering and only asks
    #                                which one to run)
    backends: Tuple[str, ...]      # ("tpu",) / ("cpu",) / ("*",)
    supports: Optional[Callable]   # Signature -> bool; None = everything
    fallback: bool                 # the universal lowering


@dataclasses.dataclass(frozen=True)
class KernelChoice:
    """One resolved dispatch. `declined` records every better-ranked kernel
    that was passed over and why — observability for 'why did my Pallas
    kernel not run' without a debugger. `stats_demoted` marks a pick the
    stats store changed: a better-ranked kernel had benched slower than
    its fallback on this exact (op, backend, signature) shape and lost
    the tie-break (plan/stats.py, docs/adaptive.md) — the loss itself is
    in `declined` with the observed timings."""

    op: str
    name: str
    fn: Optional[Callable]
    fallback: bool
    declined: Tuple[Tuple[str, str], ...] = ()
    stats_demoted: bool = False

    @property
    def label(self) -> str:
        return f"{self.name}:{self.op}"


# op -> module whose import registers that op's kernels (lazy: nothing is
# imported until the first select()/kernels() touching the op)
_PROVIDERS = {
    "groupby": "spark_rapids_tpu.ops.aggregate",
    "row_conversion": "spark_rapids_tpu.ops.row_conversion",
    "fused_select": "spark_rapids_tpu.ops.select_pallas",
    "topk": "spark_rapids_tpu.ops.topk_pallas",
    "hash_join": "spark_rapids_tpu.ops.join_pallas",
}


class KernelRegistry:
    def __init__(self):
        self._ops: Dict[str, List[Kernel]] = {}
        # last successfully validated override set — select() is the hot
        # dispatch path, so the strict-typo scan (provider _ensure + name
        # lookup per entry) runs once per distinct knob value, not per call
        self._ov_validated: Optional[Tuple[Tuple[str, str], ...]] = None
        # the process-global REGISTRY is dispatched from every executor
        # thread; RLock because provider imports under _ensure re-enter
        # register() on the same thread. Mutations of the catalog and the
        # override memo hold it (machine-checked by the lint_hazards
        # lock-discipline rule); lock-free reads in select() see either
        # the pre- or post-registration list, both complete.
        self._lock = threading.RLock()

    # ---- registration (provider modules, at import time) -------------------
    def register(self, op: str, name: str, fn: Optional[Callable] = None, *,
                 backends: Sequence[str] = ("*",),
                 supports: Optional[Callable] = None,
                 fallback: bool = False) -> Kernel:
        with self._lock:
            ks = self._ops.setdefault(op, [])
            if any(k.name == name for k in ks):
                raise ValueError(
                    f"kernel {name!r} already registered for {op!r}")
            if fallback:
                if any(k.fallback for k in ks):
                    raise ValueError(f"{op!r} already has a fallback kernel")
                if supports is not None:
                    raise ValueError(
                        f"{op!r}/{name!r}: a fallback kernel must support "
                        "every signature (that is what makes decline safe)")
            k = Kernel(op=op, name=name, fn=fn, backends=tuple(backends),
                       supports=supports, fallback=fallback)
            ks.append(k)
            return k

    def _ensure(self, op: str) -> None:
        if op in self._ops:
            return
        with self._lock:
            if op in self._ops:
                return
            mod = _PROVIDERS.get(op)
            if mod is None:
                raise ValueError(
                    f"unknown kernel op {op!r} (known: "
                    f"{sorted(set(self._ops) | set(_PROVIDERS))})")
            importlib.import_module(mod)
            if op not in self._ops:
                raise RuntimeError(f"provider {mod} did not register {op!r}")

    def ops(self) -> Tuple[str, ...]:
        return tuple(sorted(set(self._ops) | set(_PROVIDERS)))

    def kernels(self, op: str) -> Tuple[Kernel, ...]:
        self._ensure(op)
        return tuple(self._ops[op])

    # ---- selection ---------------------------------------------------------
    def _overrides(self) -> Dict[str, str]:
        from .. import config
        ov = config.kernel_overrides()
        key = tuple(sorted(ov.items()))
        if key == self._ov_validated:
            return ov
        # strict-typo gate: every mentioned op and kernel name must exist
        for op, name in key:
            self._ensure(op)
            if not any(k.name == name for k in self._ops[op]):
                raise ValueError(
                    f"SPARK_RAPIDS_TPU_KERNELS: unknown kernel {name!r} for "
                    f"{op!r} (have "
                    f"{[k.name for k in self._ops[op]]})")
        with self._lock:
            self._ov_validated = key
        return ov

    @staticmethod
    def _stats_verdict(op: str, backend: str, name: str,
                       fallback_name: str, sig: Optional[Signature]):
        """Consult the stats store's observed kernel timings for this
        exact (op, backend, signature): a non-None (candidate, fallback)
        ms-per-1k-rows pair means the candidate has benched slower than
        its fallback past the hysteresis margin and must lose the
        tie-break (docs/adaptive.md). None — cold, store disabled, or
        the candidate holds up — leaves selection static."""
        if sig is None:
            return None
        from ..plan import stats as _stats
        store = _stats.active_store()
        if store is None:
            return None
        return store.kernel_slower(backend, op, sig, name, fallback_name)

    def select(self, op: str, sig: Optional[Signature] = None,
               backend: Optional[str] = None) -> KernelChoice:
        """Resolve `op` for `backend` (default: jax.default_backend()) and
        `sig`. Never raises on signatures — unsupported ones decline down
        the candidate list to the fallback; raises only on unknown op /
        override names (strict-typo policy). With the stats store active
        (plan/stats.py), a candidate that has benched slower than the
        fallback on this exact signature is DEMOTED — declined with the
        observed timings and `stats_demoted` stamped on the choice; a
        forced override outranks the demotion (an explicit pin is the
        operator saying 'measure it anyway')."""
        self._ensure(op)
        ks = self._ops[op]
        overrides = self._overrides()
        # an EXPLICIT backend is a caller pin (the degraded tier passes
        # "cpu" so nothing lands on the quarantined device) and outranks a
        # forced override; backend=None means "wherever we are", where a
        # force may deliberately cross the registration gate (interpret-
        # mode parity runs force the Pallas set on the CPU suite)
        pinned = backend is not None
        if backend is None:
            backend = jax.default_backend()
        fb = next((k for k in ks if k.fallback), None)
        if fb is None:
            raise RuntimeError(
                f"op {op!r} registered no fallback=True kernel — every "
                "provider must register exactly one universal fallback; "
                "that is what makes decline safe (docs/kernels.md)")
        declined: List[Tuple[str, str]] = []

        def ok(k: Kernel) -> bool:
            if k.supports is None:
                return True
            if sig is None:
                # a conditional kernel cannot be chosen blind
                declined.append((k.name, "no signature at call site"))
                return False
            if not k.supports(sig):
                declined.append((k.name, "unsupported signature"))
                return False
            return True

        forced = overrides.get(op)
        if forced is not None:
            k = next(k for k in ks if k.name == forced)
            if pinned and not (k.fallback or backend in k.backends
                               or "*" in k.backends):
                declined.append(
                    (k.name, f"not registered for pinned backend {backend}"))
                return KernelChoice(op, fb.name, fb.fn, True,
                                    tuple(declined))
            if ok(k):
                return KernelChoice(op, k.name, k.fn, k.fallback)
            return KernelChoice(op, fb.name, fb.fn, True, tuple(declined))
        # auto: backend-exact non-fallbacks first, then universal
        # non-fallbacks, then the fallback — registration order within a rank
        demoted = False
        for rank in (lambda k: not k.fallback and backend in k.backends,
                     lambda k: not k.fallback and "*" in k.backends):
            for k in ks:
                if rank(k) and ok(k):
                    verdict = self._stats_verdict(op, backend, k.name,
                                                  fb.name, sig)
                    if verdict is not None:
                        declined.append(
                            (k.name,
                             "stats: benched %.4g ms/1k rows vs fallback "
                             "%.4g on this signature" % verdict))
                        demoted = True
                        continue
                    return KernelChoice(op, k.name, k.fn, k.fallback,
                                        tuple(declined),
                                        stats_demoted=demoted)
        return KernelChoice(op, fb.name, fb.fn, True, tuple(declined),
                            stats_demoted=demoted)

    def summary(self, backend: Optional[str] = None) -> Dict[str, str]:
        """op -> signature-independent choice name for `backend` — the
        bench JSONL `kernels` stamp and explain()'s registry line.
        Conditional kernels that would need a signature fall through to
        their rank's next candidate, so the summary is the floor of what
        can run, never an overstatement."""
        return {op: self.select(op, None, backend=backend).name
                for op in self.ops()}


REGISTRY = KernelRegistry()


def select(op: str, sig: Optional[Signature] = None,
           backend: Optional[str] = None) -> KernelChoice:
    return REGISTRY.select(op, sig, backend=backend)
