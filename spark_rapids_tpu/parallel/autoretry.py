"""Driver-side SplitAndRetry for the distributed ops.

Every `distributed_*` op returns an overflow flag instead of corrupting
when a static capacity (key_cap / row_cap / slack) is exceeded — the mesh
analogue of the arbiter's SplitAndRetryOOM (SURVEY.md §5: "split its input
batch and retry"). Round 1 left acting on that flag to the caller; these
wrappers close the loop: run the op, and on overflow grow the capacities
and re-run. Capacities are static shapes, so each retry compiles a new SPMD
program — the retry cost is a compile, never wrong data, and the doubled
caps are remembered by jit's cache for the rest of the job (exactly how a
Spark task that hit SplitAndRetryOOM keeps its smaller batch size).

The growth is geometric (×2 per attempt, like halve_table's halving in
reverse); `max_attempts` bounds the escalation the way the arbiter's
retry limit bounds livelock (SparkResourceAdaptorJni.cpp:984-995).
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax.numpy as jnp

from ..utils.tracing import span
from .relational import (distributed_broadcast_join,
                         distributed_broadcast_join_keyed,
                         distributed_groupby, distributed_groupby_keyed,
                         distributed_inner_join, distributed_inner_join_keyed,
                         distributed_left_join, distributed_left_join_keyed,
                         distributed_sort)


class CapacityOverflowError(RuntimeError):
    """Retries exhausted with the overflow flag still set."""


def _grown(caps: Dict, grow: float) -> Dict:
    out = {}
    for k, v in caps.items():
        if isinstance(v, int):
            out[k] = max(v + 1, int(v * grow))
        else:
            out[k] = v * grow
    return out


def auto_retry_overflow(attempt: Callable[..., Tuple], caps: Dict,
                        max_attempts: int = 6, grow: float = 2.0,
                        ceil: Dict = None):
    """Run `attempt(**caps)` until its overflow flag (last element of the
    result tuple) clears, growing every capacity geometrically.

    `ceil` (per-capacity upper bounds — the resource certifier's sound
    hi-bounds, analysis/footprint.py) clamps the growth: escalating past
    a PROVEN bound is wasted memory, so a grown capacity stops at its
    ceiling. The ceiling is advisory, never load-bearing for progress: if
    an attempt that ran with a clamped capacity still overflows, the
    bound was wrong for this run (a certifier bug — soundness says this
    cannot happen) and the ceiling is dropped, restoring the pure
    geometric ladder rather than turning a recoverable overflow into a
    CapacityOverflowError.

    Returns (result_tuple, final_caps). The overflow check is a host sync —
    this is a driver-level loop by design, like the plugin's catch-retry."""
    ceil = dict(ceil or {})
    clamped_last = False
    for i in range(max_attempts):
        out = attempt(**caps)
        # the program runs to its end before its flag can be read: in the
        # capped tier this is where the host waits for the device
        with span("ops.host_sync", site="autoretry.overflow"):
            overflowed = bool(jnp.any(out[-1]))
        if not overflowed:
            return out, caps
        if clamped_last:
            ceil = {}           # distrust: a clamped attempt overflowed
            clamped_last = False
        if i + 1 < max_attempts:
            grown = _grown(caps, grow)
            if ceil:
                capped = {k: max(caps[k], min(v, ceil[k]))
                          if k in ceil and isinstance(v, int) else v
                          for k, v in grown.items()}
                if capped == caps:
                    # the ceiling blocks ALL growth: re-attempting
                    # byte-identical caps would deterministically
                    # overflow again, burning a ladder rung for nothing
                    # — drop the (evidently wrong) ceiling NOW and
                    # regrow, preserving the full geometric ladder
                    ceil = {}
                    caps = grown
                else:
                    clamped_last = capped != grown
                    caps = capped
            else:
                caps = grown
    raise CapacityOverflowError(
        f"overflow persisted after {max_attempts} attempts; final caps {caps}")


def distributed_groupby_auto(mesh, keys, vals, aggs, key_cap: int,
                             axis: str = "data", max_attempts: int = 6):
    """distributed_groupby that retries with a doubled key_cap on overflow
    (more distinct keys per shard than the static shape allowed)."""
    out, _ = auto_retry_overflow(
        lambda key_cap: distributed_groupby(mesh, keys, vals, aggs,
                                            key_cap=key_cap, axis=axis),
        {"key_cap": key_cap}, max_attempts)
    return out


def distributed_groupby_keyed_auto(mesh, key_words, key_specs, vals, aggs,
                                   key_cap: int, axis: str = "data",
                                   max_attempts: int = 6):
    out, _ = auto_retry_overflow(
        lambda key_cap: distributed_groupby_keyed(
            mesh, key_words, key_specs, vals, aggs, key_cap=key_cap,
            axis=axis),
        {"key_cap": key_cap}, max_attempts)
    return out


def distributed_inner_join_auto(mesh, lkeys, lvals, rkeys, rvals,
                                row_cap: int, slack: float = 2.0,
                                axis: str = "data", max_attempts: int = 6):
    """distributed_inner_join that grows BOTH capacities on overflow: the
    merged flag covers bucket spill during the shuffle (fix: slack) and
    join-output spill past row_cap (fix: row_cap); growing both converges
    on skew of either kind."""
    out, _ = auto_retry_overflow(
        lambda row_cap, slack: distributed_inner_join(
            mesh, lkeys, lvals, rkeys, rvals, row_cap=row_cap, slack=slack,
            axis=axis),
        {"row_cap": row_cap, "slack": slack}, max_attempts)
    return out


def distributed_inner_join_keyed_auto(mesh, l_words, lvals, r_words, rvals,
                                      key_specs, row_cap: int,
                                      slack: float = 2.0, axis: str = "data",
                                      max_attempts: int = 6):
    out, _ = auto_retry_overflow(
        lambda row_cap, slack: distributed_inner_join_keyed(
            mesh, l_words, lvals, r_words, rvals, key_specs,
            row_cap=row_cap, slack=slack, axis=axis),
        {"row_cap": row_cap, "slack": slack}, max_attempts)
    return out


def distributed_left_join_auto(mesh, lkeys, lvals, rkeys, rvals,
                               row_cap: int, slack: float = 2.0,
                               axis: str = "data", max_attempts: int = 6):
    out, _ = auto_retry_overflow(
        lambda row_cap, slack: distributed_left_join(
            mesh, lkeys, lvals, rkeys, rvals, row_cap=row_cap, slack=slack,
            axis=axis),
        {"row_cap": row_cap, "slack": slack}, max_attempts)
    return out


def distributed_left_join_keyed_auto(mesh, l_words, lvals, r_words, rvals,
                                     key_specs, row_cap: int,
                                     slack: float = 2.0, axis: str = "data",
                                     max_attempts: int = 6):
    out, _ = auto_retry_overflow(
        lambda row_cap, slack: distributed_left_join_keyed(
            mesh, l_words, lvals, r_words, rvals, key_specs,
            row_cap=row_cap, slack=slack, axis=axis),
        {"row_cap": row_cap, "slack": slack}, max_attempts)
    return out


def distributed_broadcast_join_auto(mesh, lkeys, lvals, rkeys, rvals,
                                    row_cap: int, axis: str = "data",
                                    max_attempts: int = 6):
    """Broadcast joins have no shuffle spill (the build side is replicated
    whole), so only row_cap grows on overflow."""
    out, _ = auto_retry_overflow(
        lambda row_cap: distributed_broadcast_join(
            mesh, lkeys, lvals, rkeys, rvals, row_cap=row_cap, axis=axis),
        {"row_cap": row_cap}, max_attempts)
    return out


def distributed_broadcast_join_keyed_auto(mesh, l_words, lvals, r_words,
                                          rvals, key_specs, row_cap: int,
                                          axis: str = "data",
                                          max_attempts: int = 6):
    out, _ = auto_retry_overflow(
        lambda row_cap: distributed_broadcast_join_keyed(
            mesh, l_words, lvals, r_words, rvals, key_specs,
            row_cap=row_cap, axis=axis),
        {"row_cap": row_cap}, max_attempts)
    return out


def distributed_sort_auto(mesh, keys, vals, slack: float = 2.0,
                          axis: str = "data", max_attempts: int = 6):
    """distributed_sort that grows slack on overflow (key skew past the
    sample-sort's balance estimate)."""
    out, _ = auto_retry_overflow(
        lambda slack: distributed_sort(mesh, keys, vals, slack=slack,
                                       axis=axis),
        {"slack": slack}, max_attempts)
    return out
