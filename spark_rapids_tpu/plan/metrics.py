"""Per-operator execution metrics — the plugin's GpuMetric slot.

The reference plugin hangs NVTX ranges and task metrics off every exec
node; here each executed operator records rows/bytes/wall-time and the two
recovery counters this engine's contracts produce: `retries` (faultinj /
device-assert recoveries, the RetryOOM analogue) and `escalations` (cap
growth attempts charged to the node whose capacity overflowed — the
SplitAndRetry analogue at plan granularity).

`profile()` on a PlanResult returns these rows. The eager tier additionally
brackets every operator with a `plan.op` span (`utils/tracing.py`; `op` =
`<toposort index>.<kind>`, the name the operator's scope carries inside a
capped program), recorded whenever a profiler session runs; to read a
device profile of the capped tier, `PlanExecutor.device_op_owners` maps
its instruction names to those operators (docs/plan.md "Reading a
profile").
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional


@dataclasses.dataclass
class OperatorMetrics:
    label: str                 # node label, e.g. HashJoin#3
    kind: str                  # node kind, e.g. HashJoin
    describe: str = ""         # the node's parameter summary
    rows_in: int = 0           # live input rows (sum over children)
    rows_out: int = 0          # live output rows
    bytes_out: int = 0         # output buffer bytes (padded size in capped)
    wall_ms: Optional[float] = None   # per-op wall (eager tier only)
    retries: int = 0           # operator re-runs after injected/device faults
    escalations: int = 0       # cap-growth retries charged to this node
    backoff_ms: float = 0.0    # time spent backing off before retries
    degraded: bool = False     # ran on the degraded CPU tier (breaker open)
    # serving-session stamp (serving/scheduler.py, docs/serving.md): the
    # tenant session this operator executed for, "" outside the serving
    # layer — per-tenant accounting must never be inferred from thread
    # identity (dispatcher workers are multiplexed across sessions)
    session: str = ""
    # fleet worker stamp (serving/fleet.py): which executor worker ran
    # this operator, "" outside a fleet — multi-worker soaks attribute
    # per-op numbers to the worker that produced them
    worker_id: str = ""
    # kernel-registry choice for operators with registered alternatives
    # (ops/registry.py, docs/kernels.md): "pallas:fused_select",
    # "scan:groupby", "xla:topk", ... — trajectory numbers must never
    # silently compare kernel backends (same rule as the bench `backend`
    # stamp). Empty for operators with no registry dispatch.
    kernel: str = ""
    # small rows x large rows an eager join compared on the small-side path
    # (ops/join.py; `kernel` then reads "<backend>:lookup"), else 0
    lookup_compares: int = 0
    # how an eager `Filter` / `FusedSelect` moved its rows
    # (ops/gather.py:compaction_path): "positions", "sort", "sort+gather",
    # "none" (every row stayed); "" where nothing is compacted (another
    # operator, the capped tier's mask)
    compact: str = ""
    # how an eager `left_outer` / `full_outer` join made each side's output
    # columns (ops/gather.py:outer_join_paths): the left side `as_is` or by
    # `take`; the right side `nulls`, `sparse`, `sort` (its rows rode one
    # sort to their slots: the matching left rows' keys are distinct) or
    # `take`, for a full join
    # then `/` and how the right rows without a match were compacted; and
    # the planes, and planes x slots, that still went through a frame-long
    # `take`. "" and 0 for another join or tier
    left_out: str = ""
    right_out: str = ""
    planes_gathered: int = 0
    slots_gathered: int = 0
    # left rows a `left_outer` or `full_outer` join put out null-extended
    # (no match, or a null key), else 0
    unmatched_rows: int = 0
    # right rows a `full_outer` join put out null-extended, else 0
    unmatched_right_rows: int = 0
    # a keyed `HashAggregate`: the key planes (a key column's data, its
    # validity) x slots its group-by gathered through the groups' first
    # rows (the groups in the eager tier, the key cap in the capped one); 0
    # where every key rode the compaction sort (ops/aggregate.py)
    key_slots_gathered: int = 0
    # an eager `Window`: its partitions, whether its kernel sorted (`sort`)
    # or took the child's order (`child`), and the sort's key: `packed`
    # (every key operand and the row number in one 64-bit word),
    # `operands` or `none`; 0 and "" elsewhere
    window_partitions: int = 0
    window_sorted: str = ""
    window_key: str = ""
    # streaming-scan IO metrics (Scan nodes bound to a parquet source;
    # docs/io.md). Decode wall is host-side bitstream decode; overlap is
    # the time decode of chunk N+1 ran concurrently with executing chunk N
    # (the prefetch pipeline's win — 0 with SPARK_RAPIDS_TPU_IO_PREFETCH=0).
    io_row_groups_total: int = 0
    io_row_groups_pruned: int = 0
    io_bytes_skipped: int = 0      # compressed chunk bytes never decoded
    io_decode_ms: float = 0.0
    io_overlap_ms: float = 0.0
    # distributed-tier metrics (docs/distributed.md). `sharding` is the
    # operator's OUTPUT distribution ("rows@4" row-sharded over 4 peers,
    # "hash[k]@4" hash-partitioned by k, "replicated@4", "local" gathered
    # to one device). `exchange_how` records the movement kind
    # (hash/broadcast/gather, plus "range" for the sample-sort's splitter
    # exchange inside Sort/TopK) — on Exchange nodes for planned
    # boundaries, on the operator itself for implicit movement (an
    # unplanned shuffle join's internal exchange, a Sort's range
    # partition). Byte accounting is per edge, each edge counted ONCE
    # (broadcast = payload x (n_peers-1)), live payload only — capacity
    # padding, slack, and exchange metadata (masks, bucket counts) are
    # excluded, matching the certifier's per-edge exchange model
    # (analysis/footprint.py): `exchange_bytes` is the WIRE form (packed
    # planes the edge actually ships; == logical with packing off) and
    # `exchange_bytes_logical` the unpacked per-column payload the edge
    # represents. `exchange_codecs` names the non-pass-through encodings
    # chosen (plan/transport.py); `exchange_overlap_ms` is the transfer
    # wall that ran concurrently with other plan work under async
    # dispatch (SPARK_RAPIDS_TPU_EXCHANGE_ASYNC).
    sharding: str = ""
    exchange_how: str = ""
    exchange_bytes: int = 0            # bytes on the wire (packed form)
    exchange_bytes_logical: int = 0    # unpacked payload bytes
    exchange_codecs: str = ""
    exchange_overlap_ms: float = 0.0
    n_peers: int = 0               # mesh size the operator ran over

    def to_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        # both byte counters under explicit names: a JSONL consumer must
        # never have to know that `exchange_bytes` means the wire form
        d["exchange_bytes_wire"] = self.exchange_bytes
        return d


def render_profile(rows: List[OperatorMetrics],
                   plan_wall_ms: Optional[float] = None,
                   attempts: int = 1,
                   caps: Optional[Dict] = None,
                   degraded: bool = False,
                   breaker: Optional[Dict] = None,
                   optimizer: Optional[Dict] = None,
                   jit_cache_hits: int = 0,
                   cert=None) -> str:
    """Human-readable profile table (the `profile()` text form)."""
    out = []
    if plan_wall_ms is not None:
        caps_s = f" caps={caps}" if caps else ""
        hits_s = f", {jit_cache_hits} jit cache hit(s)" if jit_cache_hits \
            else ""
        out.append(f"plan: {plan_wall_ms:.3f} ms, "
                   f"{attempts} attempt(s){caps_s}{hits_s}")
    if cert is not None:
        # static resource certifier (analysis/footprint.py): the sound
        # hi-bounds this execution was admitted and cap-seeded under
        peak = ("unbounded" if cert.peak_bytes_hi is None
                else f"{cert.peak_bytes_hi} B")
        root_rows = ("unbounded" if cert.root.rows_hi is None
                     else str(cert.root.rows_hi))
        ub = (f", {len(cert.unbounded)} op(s) unbounded"
              if cert.unbounded else "")
        out.append(f"footprint: peak resident <= {peak} certified, "
                   f"root rows <= {root_rows}{ub}")
    if optimizer is not None:
        fired = optimizer.get("rules_fired") or {}
        pruned = optimizer.get("pruned_columns", 0)
        out.append(f"optimizer: rules_fired={fired or 'none'}"
                   + (f", pruned {pruned} column(s) "
                      f"(~{optimizer.get('pruned_bytes_est', 0)} B est)"
                      if pruned else "")
                   + f", fingerprint={optimizer.get('fingerprint', '')}")
        # adaptive-execution provenance (plan/stats.py, docs/adaptive.md):
        # where each build-side/exchange decision's cardinalities came
        # from — a warm (observed-driven) profile must never read like a
        # cold one
        sources = optimizer.get("decision_sources") or {}
        if sources:
            tag = (" [STATS REVERTED]"
                   if optimizer.get("stats_reverted") else "")
            for key, src in sorted(sources.items()):
                out.append(f"  decision {key}: {src}{tag}")
    if degraded:
        reason = (breaker or {}).get("reason")
        state = (breaker or {}).get("state", "open")
        out.append(f"DEGRADED: breaker {state}"
                   f"{f' ({reason})' if reason else ''}; "
                   "plan completed on the CPU tier")
    hdr = (f"{'operator':<28} {'rows_in':>10} {'rows_out':>10} "
           f"{'bytes_out':>12} {'wall_ms':>9} {'retry':>5} {'escal':>5} "
           f"{'backoff':>8} {'deg':>4}")
    out.append(hdr)
    out.append("-" * len(hdr))
    for m in rows:
        wall = f"{m.wall_ms:.3f}" if m.wall_ms is not None else "-"
        out.append(f"{m.label:<28} {m.rows_in:>10} {m.rows_out:>10} "
                   f"{m.bytes_out:>12} {wall:>9} {m.retries:>5} "
                   f"{m.escalations:>5} {m.backoff_ms:>8.1f} "
                   f"{'yes' if m.degraded else '-':>4}")
        if m.kernel:
            out.append(f"  kernel: {m.kernel}")
        if m.io_row_groups_total:
            kept = m.io_row_groups_total - m.io_row_groups_pruned
            out.append(f"  io: row groups {kept}/{m.io_row_groups_total} "
                       f"({m.io_row_groups_pruned} pruned), "
                       f"{m.io_bytes_skipped} B skipped, "
                       f"decode {m.io_decode_ms:.3f} ms, "
                       f"overlap {m.io_overlap_ms:.3f} ms")
        if m.sharding or m.exchange_how:
            parts = []
            if m.sharding:
                parts.append(f"sharding {m.sharding}")
            if m.exchange_how:
                ex = (f"exchange {m.exchange_how} "
                      f"{m.exchange_bytes} B moved")
                if m.exchange_bytes_logical and \
                        m.exchange_bytes_logical != m.exchange_bytes:
                    ex += f" ({m.exchange_bytes_logical} B logical)"
                parts.append(ex)
            if m.exchange_codecs:
                parts.append(f"codecs {m.exchange_codecs}")
            if m.exchange_overlap_ms:
                parts.append(f"overlap {m.exchange_overlap_ms:.3f} ms")
            out.append(f"  dist: {', '.join(parts)}")
    return "\n".join(out)
