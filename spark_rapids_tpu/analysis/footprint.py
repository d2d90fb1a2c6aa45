"""Static resource certifier: abstract-interpretation bounds on cardinality,
memory footprint, and exchange bytes (docs/analysis.md).

The capped tier historically discovered footprints by OOM-escalation and
admission had no sizing at all — the arbitration story (PAPER.md §0:
many tasks share one device without deadlocking) needs to know *before*
admitting a plan whether it can possibly fit. This module walks the typed
plan DAG once, in toposort order, propagating a SOUND interval ``[lo, hi]``
on row count per operator plus derived byte footprints, and packages the
result as a :class:`ResourceCert`:

- **rows**: ``hi`` is an upper bound that holds for every execution over
  the bound inputs (filters collapse ``lo`` to 0, never ``hi``; an inner
  join's ``hi`` is the full cross product of its sides' ``hi`` — loose but
  sound, there are no key statistics to do better with statically);
- **bytes**: per-row widths come from the SAME dtype propagation the
  verifier's typing layer runs (`verifier.column_types`) — fixed-width
  columns certify ``itemsize + 1`` bytes/row (the +1 is a validity plane,
  assumed present because the certifier may not know nullability), while
  string/nested/unknown columns make the operator's byte bound UNBOUNDED
  (their buffer length is not a function of the row count);
- **working sets**: a join's build (right) table and a keyed aggregate's
  hash-table accumulators are resident while the operator runs, on top of
  its inputs and output — `resident_bytes_hi` sums them;
- **exchange bytes**: hash edges move each row at most once, broadcast
  replicates the relation onto every other peer, gather collects it —
  `exchange_bytes_hi` bounds the payload per planned Exchange edge
  (ROADMAP item 5's honest bytes-on-wire accounting, statically). The
  bound models the WIRE form the distributed tier actually ships
  (plan/transport.py): a hash edge's key columns ride their 64-bit
  order-preserving word encoding (8 B per word, plus a null-flag word
  when nullable) while value columns ship at most their unpacked
  column width; a hash edge whose sole consumer is a keyed aggregate
  fuses into the two-phase groupby and ships per-group int64 partials
  instead, so such edges bound by the larger of the two payload models.
  The runtime's observed `exchange_bytes` (wire) must stay at or under
  this bound on every edge — `check_observed` enforces the inequality.

Soundness contract (machine-checked): for every operator of every
executed plan, ``rows_lo <= observed rows_out <= rows_hi``, and on the
eager tier ``observed bytes_out <= out_bytes_hi`` (the capped tier pads
buffers to its caps, and the distributed tier's exchange buffers carry
slack, so their byte observations measure padding, not live data — rows
remain comparable everywhere). The fuzzer's property 5
(`analysis/fuzz.py`) asserts this on every seeded random DAG, cold and
warm, plus MONOTONICITY: an optimizer rewrite may only keep or tighten
the root's certified bound. tests/test_footprint.py asserts it on a
join-aggregate plan in both tiers; how tight the bounds are
(certified/observed) is reported nowhere yet.

Three consumers (docs/analysis.md#resource-certifier):

1. the executor's admission path (`PlanExecutor.execute`) rejects — or
   downgrades to the CPU tier — a plan whose certified hi-bound exceeds
   the configured device budget, BEFORE any compilation, raising a
   `ResourceAdmissionError` (PlanVerificationError family) that names the
   offending operator;
2. the optimizer consults certified row bounds where no observed stats
   or static estimates exist (decision source ``certified:<bound>``), and
   `exchange_planning` proves broadcast-join legality as a BYTE bound
   (`SPARK_RAPIDS_TPU_BROADCAST_BYTES`) instead of trusting the row
   heuristic alone;
3. the capped tier, on cold adaptive runs, tightens starting capacities
   to the certified hi (a sound bound can never overflow) and ceilings
   the escalation ladder at it — warm runs keep the observed high-water,
   which must always be <= the certified bound: that inequality IS the
   soundness check.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from .. import dtypes
from ..plan.nodes import (PAIRING_JOINS, Exchange, Filter, FusedSelect,
                          HashAggregate, HashJoin, Limit, PlanNode, Project,
                          Scan, Sort, TopK, Union, Window, nullable_sides)
from .verifier import (PlanVerificationError, Violation, _propagate_schemas,
                       column_types)

__all__ = ["OpBound", "ResourceCert", "ResourceAdmissionError",
           "certify", "certify_nodes", "table_metadata",
           "check_observed", "quota_charge"]

_VALIDITY_BYTES = 1        # one bool plane byte per row per column
_ACC_BYTES = 8             # aggregate accumulators widen to 64-bit


class ResourceAdmissionError(PlanVerificationError):
    """A plan's certified footprint exceeds the device budget — raised at
    admission, before any compilation, with the offending operator's label
    in the structured violations (same `Violation` vocabulary as every
    other static-analysis gate)."""


def _add(a: Optional[int], b: Optional[int]) -> Optional[int]:
    """None-propagating sum: an unbounded term poisons the bound."""
    if a is None or b is None:
        return None
    return a + b


def _mul(a: Optional[int], b: Optional[int]) -> Optional[int]:
    if a is None or b is None:
        return None
    return a * b


def _col_width(dt: Optional[dtypes.DType]) -> Optional[int]:
    """Certified bytes per row for one column's buffers, or None when the
    buffer length is not a function of the row count (strings/nested) or
    the dtype is unknown. DECIMAL128 is fixed-width (16 bytes of limbs)."""
    if dt is None or dt.is_string or dt.is_nested:
        return None
    return dt.itemsize() + _VALIDITY_BYTES


@dataclasses.dataclass(frozen=True)
class OpBound:
    """Certified bounds for one operator. `rows_hi`/byte fields are None
    when UNBOUNDED (an unknown input cardinality or a non-fixed-width
    column reached this operator) — the certifier is sound-but-incomplete
    and never guesses."""
    label: str
    kind: str
    index: int                        # toposort index (the capped tier's
    #                                   per-node cap-key space)
    rows_lo: int
    rows_hi: Optional[int]
    row_bytes: Optional[int]          # certified output bytes per row
    out_bytes_hi: Optional[int]       # rows_hi x row_bytes
    working_bytes_hi: Optional[int]   # join build table / agg hash table
    exchange_bytes_hi: Optional[int]  # planned movement (Exchange nodes)
    resident_bytes_hi: Optional[int]  # child outputs + working + output

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


class ResourceCert:
    """One plan's certified resource bounds, toposort-ordered. `by_label`
    and `by_index` address the same `OpBound`s; `peak_bytes_hi` is the
    largest certified per-operator residency (the admission comparand);
    `unbounded` lists operators the certifier could not bound (they pass
    admission — rejecting them would reject every string plan — but are
    visible so the operator knows the cert is partial)."""

    def __init__(self, ops: List[OpBound], n_peers: int = 1):
        self.ops = list(ops)
        self.n_peers = n_peers
        self.by_label: Dict[str, OpBound] = {b.label: b for b in self.ops}
        self.by_index: Dict[int, OpBound] = {b.index: b for b in self.ops}
        self.unbounded: List[str] = [
            b.label for b in self.ops
            if b.rows_hi is None or b.out_bytes_hi is None]
        finite = [b.resident_bytes_hi for b in self.ops
                  if b.resident_bytes_hi is not None]
        self.peak_bytes_hi: Optional[int] = max(finite) if finite else None
        ex = [b.exchange_bytes_hi for b in self.ops
              if b.exchange_bytes_hi is not None]
        self.exchange_bytes_hi: Optional[int] = sum(ex) if ex else 0

    @property
    def root(self) -> OpBound:
        return self.ops[-1]

    def over_budget(self, budget_bytes: int) -> List[Violation]:
        """Operators whose certified residency provably exceeds
        `budget_bytes` — DEFINITE findings only: an unbounded operator is
        reported on the cert, not rejected (sound-but-incomplete, same
        philosophy as the verifier)."""
        out = []
        for b in self.ops:
            if b.resident_bytes_hi is not None and \
                    b.resident_bytes_hi > budget_bytes:
                out.append(Violation(
                    "footprint.over-budget", b.label,
                    f"{b.label}: certified residency hi-bound "
                    f"{b.resident_bytes_hi} B (rows<= "
                    f"{b.rows_hi}, output<={b.out_bytes_hi} B, working<="
                    f"{b.working_bytes_hi or 0} B) exceeds the device "
                    f"budget of {budget_bytes} B — the plan cannot be "
                    "proven to fit"))
        return out

    def to_dict(self) -> Dict:
        return {"peak_bytes_hi": self.peak_bytes_hi,
                "exchange_bytes_hi": self.exchange_bytes_hi,
                "root_rows_hi": self.root.rows_hi,
                "root_bytes_hi": self.root.out_bytes_hi,
                "unbounded": list(self.unbounded),
                "ops": [b.to_dict() for b in self.ops]}

    def render(self) -> str:
        """explain()-style block: one line per operator."""
        def fmt(v, unit=""):
            return "unbounded" if v is None else f"{v}{unit}"
        lines = ["resource cert (certified hi-bounds, "
                 f"peak {fmt(self.peak_bytes_hi, ' B')} resident, "
                 f"exchange {fmt(self.exchange_bytes_hi, ' B')}):"]
        for b in self.ops:
            parts = [f"rows [{b.rows_lo}, {fmt(b.rows_hi)}]",
                     f"out<={fmt(b.out_bytes_hi, ' B')}"]
            if b.working_bytes_hi:
                parts.append(f"working<={b.working_bytes_hi} B")
            if b.exchange_bytes_hi:
                parts.append(f"exchange<={b.exchange_bytes_hi} B")
            lines.append(f"  {b.label}: " + ", ".join(parts))
        return "\n".join(lines)

    def __repr__(self):
        return (f"ResourceCert({len(self.ops)} ops, peak="
                f"{self.peak_bytes_hi}, unbounded={len(self.unbounded)})")

    def peak_op_label(self) -> str:
        """Label of the operator that set `peak_bytes_hi` ("" when every
        operator is unbounded) — the name an over-quota serving
        diagnostic carries (docs/serving.md)."""
        for b in self.ops:
            if b.resident_bytes_hi is not None and \
                    b.resident_bytes_hi == self.peak_bytes_hi:
                return b.label
        return ""


def quota_charge(cert: Optional["ResourceCert"],
                 default_bytes: int) -> Tuple[int, str, str]:
    """Bytes one plan admission charges against a serving session's
    memory quota (serving/scheduler.py, docs/serving.md).

    The certified `peak_bytes_hi` is the charge when the certifier
    bounded the plan — it is SOUND (the plan provably stays inside that
    many resident bytes), so quota accounting inherits the same
    no-guessing contract as the admission gate. A plan the certifier
    could not bound (strings/nested columns, unbound scans, an internal
    certifier decline) charges the flat `default_bytes` instead
    (`SPARK_RAPIDS_TPU_SERVING_DEFAULT_CHARGE_BYTES`): unbounded plans
    neither ride the quota for free nor get rejected outright.

    Returns ``(bytes, source, op_label)``: source is ``"certified"`` or
    ``"default"``; op_label names the operator that set the certified
    peak ("" under the default) — the label an over-quota diagnostic
    should carry."""
    if cert is None or cert.peak_bytes_hi is None:
        return int(default_bytes), "default", ""
    return int(cert.peak_bytes_hi), "certified", cert.peak_op_label()


# ---- the abstract interpreter ----------------------------------------------

def _scan_rows(node: Scan, bound_rows) -> Optional[int]:
    """Source cardinality: the bound table/source's row count wins; a scan
    carrying its own parquet binding knows its footer count; otherwise
    unbounded (est_rows is a HINT, never a sound bound)."""
    v = (bound_rows or {}).get(node.source)
    if v is not None:
        return int(v)
    if node.parquet is not None:
        try:
            return int(node.parquet.num_rows)
        except (AttributeError, TypeError):
            return None
    return None


def _rows_interval(node: PlanNode, kids: List[Tuple[int, Optional[int]]],
                   bound_rows, nullable_keys: bool
                   ) -> Tuple[int, Optional[int]]:
    """The transfer function: [lo, hi] of this operator's output rows from
    its children's intervals. Sound for every tier: filters/semijoins
    collapse lo to 0 and never raise hi; a left outer join holds its left
    side's lo, a full outer join the longer side's; inner joins bound by
    the cross
    product; keyed aggregates by their input (distinct groups <= rows)."""
    if isinstance(node, Scan):
        n = _scan_rows(node, bound_rows)
        if n is None:
            return 0, None
        # a pruning predicate may skip row groups: lo collapses, hi holds
        return (0 if node.predicate is not None else n), n
    los = [lo for lo, _ in kids]
    his = [hi for _, hi in kids]
    if isinstance(node, (Filter, FusedSelect)):
        return 0, his[0]
    if isinstance(node, (Project, Sort, Exchange, Window)):
        return los[0], his[0]           # a row in, a row out
    if isinstance(node, (Limit, TopK)):
        return (min(node.n, los[0]),
                None if his[0] is None else min(node.n, his[0]))
    if isinstance(node, Union):
        hi = 0
        for h in his:
            hi = _add(hi, h)
        return sum(los), hi
    if isinstance(node, HashJoin):
        if node.how == "inner":
            return 0, _mul(his[0], his[1])
        if node.how == "left_outer":
            # every left row comes out once at least (null-extended where
            # nothing matches) and once a match at most
            return los[0], _mul(his[0], None if his[1] is None
                                else max(his[1], 1))
        if node.how == "full_outer":
            # every row of either side comes out once at least: the longer
            # side's rows at least, and at most every row null-extended
            # beside every pair
            return max(los), _add(_add(his[0], his[1]),
                                  _mul(his[0], his[1]))
        return 0, his[0]                     # semi/anti: left-row subset
    if isinstance(node, HashAggregate):
        if not node.keys:
            return 1, 1                      # one row, even over empty input
        # distinct groups <= input rows; at least one group when the input
        # provably has a row AND no key column can be null (a null-keyed
        # row's grouping is kernel policy the certifier must not assume)
        lo = 1 if (los[0] > 0 and not nullable_keys) else 0
        return lo, his[0]
    return los[0] if los else 0, his[0] if his else None


def _key_words(dt: Optional[dtypes.DType], nullable: bool) -> Optional[int]:
    """64-bit words one key column rides through a hash exchange
    (parallel/keys.py encoding: decimal128 = 2 data words, every other
    fixed-width kind = 1, plus a null-flag word when nullable); None for
    kinds with no distributed key encoding (strings/nested/unknown)."""
    if dt is None or dt.is_string or dt.is_nested:
        return None
    words = 2 if dt.kind == dtypes.Kind.DECIMAL128 else 1
    return words + (1 if nullable else 0)


def _hash_edge_row_bytes(node: Exchange, schema, ctypes,
                         cnull) -> Optional[int]:
    """Wire bytes per row of a standalone hash exchange: key columns as
    8-byte order-preserving words, every other column at most its
    unpacked width. The transport may FOR-narrow the shipped key planes
    (transport.narrow_words) and widen them back for the partition hash
    — a strict shrink, so pricing keys at full width stays a sound
    upper bound."""
    total = 0
    keyset = set(node.keys)
    for k in node.keys:
        w = _key_words(ctypes.get(k), cnull.get(k, True))
        if w is None:
            return None
        total += 8 * w
    for cname in (schema or ()):
        if cname in keyset:
            continue
        w = _col_width(ctypes.get(cname))
        if w is None:
            return None
        total += w
    return total


def _partial_row_bytes(agg: HashAggregate, ctypes, cnull) -> Optional[int]:
    """Wire bytes per shipped GROUP of a fused aggregate exchange: the
    two-phase program's all-to-all moves one int64 per key word and per
    agg partial (groups <= input rows, so rows_hi x this width is a
    sound payload bound)."""
    total_words = 0
    for k in agg.keys:
        w = _key_words(ctypes.get(k), cnull.get(k, True))
        if w is None:
            return None
        total_words += w
    return 8 * (total_words + len(agg.aggs))


def _agg_widths(node: HashAggregate, child_types) -> Optional[int]:
    """Output bytes/row of a HashAggregate: group keys keep their column
    widths; aggregate outputs certify at the 64-bit accumulator width
    (sums/counts/means accumulate in 64-bit regardless of the input
    column's width — certifying the typed width would under-bound). A
    decimal sum or mean is charged its planes: one 64-bit accumulator per
    32 bits of the input (the kernels sum a decimal plane by plane) or
    the 16 bytes of limbs it comes out as, whichever is more."""
    total = 0
    for k in node.keys:
        w = _col_width(child_types.get(k))
        if w is None:
            return None
        total += w
    for c, op, _ in node.aggs:
        dt = child_types.get(c) if op in ("sum", "mean") else None
        acc = _ACC_BYTES
        if dt is not None and dt.is_decimal:
            acc = max(16, _ACC_BYTES * (dt.itemsize() // 4))
        total += acc + _VALIDITY_BYTES
    return total


def certify_nodes(nodes: List[PlanNode], *, bound=None, bound_rows=None,
                  input_dtypes=None, input_nullable=None,
                  n_peers: int = 1) -> Dict[int, OpBound]:
    """Core walk over an already-toposorted node list; returns node-id ->
    OpBound. `bound` maps scan source -> column names (schema resolution
    falls back to declared schemas), `bound_rows` -> row counts,
    `input_dtypes` -> {column: DType} (enables byte bounds),
    `input_nullable` -> {column: bool} (tightens keyed-aggregate lo;
    unknown columns are assumed nullable). `n_peers` sizes exchange
    payloads (1 = single chip, exchanges move nothing)."""
    schemas, _ = _propagate_schemas(nodes, bound, strict=False)
    types = column_types(nodes, schemas, input_dtypes or {})
    parents: Dict[int, List[PlanNode]] = {}
    for nd in nodes:
        for ch in nd.children:
            parents.setdefault(id(ch), []).append(nd)
    # nullability walk, conservative: unknown -> True (nullable)
    nullable: Dict[int, Dict[str, bool]] = {}
    for node in nodes:
        kids_n = [nullable.get(id(c), {}) for c in node.children]
        if isinstance(node, Scan):
            src = dict((input_nullable or {}).get(node.source) or {})
            nullable[id(node)] = {
                c: src.get(c, True) for c in schemas.get(id(node), ())}
        elif isinstance(node, Filter):
            # `col IS NOT NULL` as a conjunct: not null in the rows kept
            from ..plan.expr import not_null_columns
            out = dict(kids_n[0])
            out.update(dict.fromkeys(
                not_null_columns(node.predicate) & set(out), False))
            nullable[id(node)] = out
        elif isinstance(node, (Project, FusedSelect)):
            from ..plan.expr import nullable as expr_nullable
            kid_types = types.get(id(node.children[0])) or {}
            nullable[id(node)] = {
                n: expr_nullable(e, lambda c: kids_n[0].get(c, True),
                                 kid_types.get)
                for n, e in node.exprs}
        elif isinstance(node, HashJoin):
            out = dict(kids_n[0])
            if node.how in PAIRING_JOINS:
                # a row without a match is null in every column of the
                # other side
                null_left, null_right = nullable_sides(node.how)
                if null_left:
                    out = dict.fromkeys(out, True)
                out.update(dict.fromkeys(kids_n[1], True) if null_right
                           else kids_n[1])
            nullable[id(node)] = out
        elif isinstance(node, HashAggregate):
            out = {k: kids_n[0].get(k, True) for k in node.keys}
            out.update({n: True for _, _, n in node.aggs})
            nullable[id(node)] = out
        elif isinstance(node, Window):
            # a running sum / min / max is NULL until the partition's
            # first value, so nullable where its input is; a count never
            out = dict(kids_n[0])
            out.update({n: o != "count" and kids_n[0].get(c, True)
                        for n, o, c in node.functions})
            nullable[id(node)] = out
        elif isinstance(node, Union):
            merged = {}
            for c in schemas.get(id(node), ()):
                merged[c] = any(k.get(c, True) for k in kids_n)
            nullable[id(node)] = merged
        else:
            nullable[id(node)] = dict(kids_n[0]) if kids_n else {}

    out: Dict[int, OpBound] = {}
    for i, node in enumerate(nodes):
        kid_bounds = [out[id(c)] for c in node.children]
        kid_rows = [(b.rows_lo, b.rows_hi) for b in kid_bounds]
        keys_nullable = True
        if isinstance(node, HashAggregate) and node.keys and kid_bounds:
            cn = nullable.get(id(node.children[0]), {})
            keys_nullable = any(cn.get(k, True) for k in node.keys)
        lo, hi = _rows_interval(node, kid_rows, bound_rows, keys_nullable)

        # output bytes/row from the typed schema
        schema = schemas.get(id(node))
        ntypes = types.get(id(node)) or {}
        row_bytes: Optional[int] = None
        if schema is not None:
            if isinstance(node, HashAggregate):
                ctypes = (types.get(id(node.children[0])) or {}
                          if node.children else {})
                row_bytes = _agg_widths(node, ctypes)
            else:
                total = 0
                for c in schema:
                    w = _col_width(ntypes.get(c))
                    if w is None:
                        total = None
                        break
                    total += w
                row_bytes = total
        out_bytes = _mul(hi, row_bytes)

        # operator working sets beyond inputs + output
        working: Optional[int] = 0
        if isinstance(node, HashJoin):
            # the build (right) table is resident while probing — even for
            # semi/anti, where it never reaches the output
            working = kid_bounds[1].out_bytes_hi
        elif isinstance(node, HashAggregate) and node.keys:
            ctypes = types.get(id(node.children[0])) or {}
            w = _agg_widths(node, ctypes)
            working = _mul(kid_bounds[0].rows_hi, w)
        elif isinstance(node, Window):
            # the sort's operands: the child's columns and a row number
            working = _add(kid_bounds[0].out_bytes_hi,
                           _mul(kid_bounds[0].rows_hi, 4))

        # exchange payload per planned edge (docs/distributed.md): hash
        # moves each row at most once; broadcast lands one extra copy on
        # every other peer; gather collects the whole relation. The
        # model is the WIRE form (module docstring): hash edges price
        # key columns as their 8-byte word encoding, and a hash edge
        # fused into the keyed aggregate above it ships per-group int64
        # partials — bound by the larger payload model, covering both
        # runtime paths.
        exchange: Optional[int] = 0
        if isinstance(node, Exchange) and n_peers > 1:
            child_out = kid_bounds[0].out_bytes_hi
            if node.how == "hash":
                cid = id(node.children[0])
                ctypes = types.get(cid) or {}
                cnull = nullable.get(cid, {})
                width = _hash_edge_row_bytes(node, schemas.get(id(node)),
                                             ctypes, cnull)
                par = parents.get(id(node), [])
                if width is not None and len(par) == 1 and \
                        isinstance(par[0], HashAggregate) and par[0].keys:
                    pw = _partial_row_bytes(par[0], ctypes, cnull)
                    width = None if pw is None else max(width, pw)
                exchange = _mul(hi, width)
            elif node.how == "gather":
                exchange = child_out
            elif node.how == "broadcast":
                exchange = _mul(child_out, n_peers - 1)

        resident = out_bytes
        for b in kid_bounds:
            resident = _add(resident, b.out_bytes_hi)
        resident = _add(resident, working)
        out[id(node)] = OpBound(
            label=node.label, kind=node.kind, index=i, rows_lo=lo,
            rows_hi=hi, row_bytes=row_bytes, out_bytes_hi=out_bytes,
            working_bytes_hi=working, exchange_bytes_hi=exchange,
            resident_bytes_hi=resident)
    return out


def table_metadata(inputs) -> Tuple[Dict, Dict]:
    """(input_dtypes, input_nullable) for the Table bindings of an
    execute()-style `inputs` dict — THE extraction every certify caller
    (executor, fuzzer, nightly gate) shares, so the metadata the bounds
    are proven over can never drift between them. Non-Table bindings
    (streaming sources) contribute nothing: their dtypes stay unknown
    and their columns conservatively nullable."""
    from ..columnar.table import Table
    dts = {name: {cn: c.dtype for cn, c in zip(t.names, t.columns)}
           for name, t in inputs.items() if isinstance(t, Table)}
    nul = {name: {cn: c.validity is not None
                  for cn, c in zip(t.names, t.columns)}
           for name, t in inputs.items() if isinstance(t, Table)}
    return dts, nul


def check_observed(cert: ResourceCert, result) -> Optional[str]:
    """THE soundness inequality, single-sourced: every executed
    operator's observed rows inside the certified ``[lo, hi]`` (all
    tiers), observed bytes at or under the certified byte bound on the
    eager tier for non-degraded ops (capped buffers pad to caps;
    degraded ops re-ran on a different tier than the cert sized).
    On a distributed run, every planned Exchange edge's observed WIRE
    bytes (the packed payload the edge shipped, plan/transport.py) must
    also sit at or under the certified per-edge payload bound — the
    `wire <= certified hi` inequality the transport layer is audited
    against (the cert must have been built with the run's n_peers, as
    `PlanExecutor.execute` does for the cert it stamps on the result).
    Returns the first violation as a string, None when sound — fuzz
    property 5 and the exchange-transport tests
    (tests/test_plan_distributed.py) call this."""
    for lbl, m in result.metrics.items():
        b = cert.by_label.get(lbl)
        if b is None:
            return f"{lbl}: executed op has no cert entry"
        if m.rows_out < b.rows_lo or (
                b.rows_hi is not None and m.rows_out > b.rows_hi):
            return (f"{lbl}: observed rows {m.rows_out} outside "
                    f"certified [{b.rows_lo}, {b.rows_hi}]")
        # mesh-resident ops (n_peers stamped) pad buffers to the mesh
        # width and exchange slack, so their bytes_out measures padding,
        # not live data (module docstring) — rows and WIRE bytes remain
        # comparable there
        if result.mode == "eager" and not m.degraded and not m.n_peers \
                and b.out_bytes_hi is not None \
                and m.bytes_out > b.out_bytes_hi:
            return (f"{lbl}: observed bytes {m.bytes_out} > certified "
                    f"{b.out_bytes_hi}")
        if m.kind == "Exchange" and not m.degraded \
                and m.exchange_bytes \
                and b.exchange_bytes_hi is not None \
                and m.exchange_bytes > b.exchange_bytes_hi:
            return (f"{lbl}: observed wire bytes {m.exchange_bytes} > "
                    f"certified exchange bound {b.exchange_bytes_hi}")
    return None


def certify(plan, *, bound=None, bound_rows=None, input_dtypes=None,
            input_nullable=None, n_peers: int = 1) -> ResourceCert:
    """Certify one Plan; see `certify_nodes` for the parameter contract.
    The returned cert's ops are in the plan's toposort order, so
    `by_index` keys line up with the capped tier's per-node cap-key
    space and the stats store's per-op records."""
    by_id = certify_nodes(plan.nodes, bound=bound, bound_rows=bound_rows,
                          input_dtypes=input_dtypes,
                          input_nullable=input_nullable, n_peers=n_peers)
    return ResourceCert([by_id[id(n)] for n in plan.nodes],
                        n_peers=n_peers)
