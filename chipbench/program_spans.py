"""The program's own spans and operator names, read back from a run's trace.

The engine opens `jax.profiler.TraceAnnotation` spans (`serving.*`,
`plan.*`, `ops.*`, each with `request=` and a few attributes) which the
profiler writes into the same `.xplane.pb` as the device's ops, on one
clock, one `/host:` line per thread; and it runs every operator of a capped
program under the scope `<toposort index>.<kind>`, which
`PlanExecutor.device_op_owners` reads back per HLO instruction. `of(run)`
reduces one traced run, once, to what the per-layer readers ask for:

- `request_ms(name)`: per request, the summed duration of its `name` spans
  (0 where it has none); only requests whose every span lies inside the
  window (the two `chipbench_sync` marks) count;
- `spans[row]` (a row is a span name; `plan.op` has one per operator):
  count, total, self (minus what children on the thread cover) and idle
  seconds (device idle time under the span while it was the deepest one
  open on its thread; union over threads), and the attributes' values;
- `owner_s`: device self time per plan operator, the remainder under
  `UNOWNED` — capped tier through `device_op_owners`, eager tier by the
  `plan.op` span that holds the op's interval;
- `kernel(prefix)`: device seconds and bytes (operands and results, each
  buffer once, from the shapes in the op's own HLO text) of the custom
  calls whose instruction name starts with `prefix`.

The device's line is first moved onto the host's clock (`device_skew`: on
the chip it lay 1.4 ms early, PERF.md PR 26). A trace of a program that
opens no spans (the parent of the PR that added them) gives `None` from
`of`: every reader then reports nothing. The tables that `breakdown` cannot
hold are printed through `harness.log`.
"""
import bisect
import glob
import os
import re
import statistics

from chipbench import trace
from chipbench.spans import SYNC_NAME

PREFIXES = ("serving.", "plan.", "ops.")
CAPPED_MODULE = "jit_capped_plan"
UNOWNED = "(no operator)"
TOP_OPS = 14
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
               "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_OPERAND = re.compile(r"([a-z]+\d*\[[\d,]*\])(?:\{[^}]*\})? (%[\w.\-]+)")


# ---- reading the file -----------------------------------------------------------

def find_trace(trace_dir: str) -> str:
    """The run's `.xplane.pb`, by the glob of `trace.stop`."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """-> spans (dicts: name, thread, t0, t1 in ns, attrs), the sync marks
    and, per device, the ops as (module, instruction, opcode, t0, t1, hlo
    text), the device's times moved onto the host's clock by `skew_ns`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, marks, planes, thread = [], [], [], 0
    launched, enqueued, completed = {}, {}, {}      # run_id -> ns
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if trace.OP_LINE not in lines:
                continue
            mods = []
            for e in lines[trace.MODULE_LINE].events \
                    if trace.MODULE_LINE in lines else ():
                t0 = int(e.start_ns)
                mods.append((t0, t0 + int(e.duration_ns),
                             re.sub(r"\(\d+\)$", "", e.name)))
                run = dict(e.stats).get("run_id")
                if run is not None:
                    launched[run] = mods[-1][:2]
            planes.append((sorted(mods), lines[trace.OP_LINE]))
        elif plane.name.startswith("/host:"):
            # one line per thread; lines carry no id and their names repeat
            for line in plane.lines:
                thread += 1
                for e in line.events:
                    if e.name == SYNC_NAME:
                        marks.append(int(e.start_ns))
                    elif e.name.startswith(PREFIXES):
                        t0 = int(e.start_ns)
                        spans.append({"name": e.name, "thread": thread,
                                      "t0": t0,
                                      "t1": t0 + int(e.duration_ns),
                                      "attrs": dict(e.stats)})
                    elif e.name.startswith(("DoEnqueueProgram",
                                            "CompleteCallbacks")):
                        run = dict(e.stats).get("run_id")
                        if run is not None:
                            (enqueued if e.name[0] == "D"
                             else completed)[run] = int(e.start_ns)
    skew = device_skew(launched, enqueued, completed)
    devices = []
    for mods, op_line in planes:
        starts = [m[0] for m in mods]
        ops = []
        for e in op_line.events:
            s = int(e.start_ns)
            i = bisect.bisect_right(starts, s) - 1
            module = mods[i][2] if i >= 0 and s < mods[i][1] else ""
            short = trace.op_name(e.name)
            ops.append((module, short.rsplit(":", 1)[0], trace.opcode(short),
                        s + skew["ns"], s + int(e.duration_ns) + skew["ns"],
                        e.name))
        devices.append(ops)
    return {"spans": spans, "marks": sorted(marks), "devices": devices,
            "skew": skew}


def device_skew(launched: dict, enqueued: dict, completed: dict) -> dict:
    """How far the device's line lies from the host's clock, from causality
    per program run (`run_id`): a program starts on the device after the
    host began to enqueue it, and the host's completion callback starts
    after it ended. -> {"ns": the shift to add to device times, "lo", "hi":
    the two bounds}; the least shift that puts no start before its enqueue,
    0 where the trace names no run."""
    lo = [enqueued[r] - t0 for r, (t0, _) in launched.items()
          if r in enqueued]
    hi = [completed[r] - t1 for r, (_, t1) in launched.items()
          if r in completed]
    lo, hi = (max(lo) if lo else None), (min(hi) if hi else None)
    if lo is None:
        ns = 0
    elif hi is not None and lo > hi:
        ns = (lo + hi) // 2          # the bounds disagree: between them
    else:
        ns = lo
    return {"ns": int(ns), "lo": lo, "hi": hi}


# ---- interval arithmetic -----------------------------------------------------------

def _total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _intersect(a, b):
    """Two sorted, merged interval lists -> their intersection."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _nest(spans):
    """Per thread, by nesting: sets `self_ns` (the span minus its direct
    children) on each span and returns the deepest-span segments
    [(t0, t1, row)] of every thread together (`row`: the span's line in
    the table, its name unless `_row` said otherwise)."""
    segments = []
    by_thread = {}
    for s in spans:
        by_thread.setdefault(s["thread"], []).append(s)
    for line in by_thread.values():
        line.sort(key=lambda s: (s["t0"], -s["t1"]))
        stack = []           # open spans; each with the cursor of its segment

        def close(upto):
            while stack and stack[-1][0]["t1"] <= upto:
                top, cursor = stack.pop()
                if top["t1"] > cursor:
                    segments.append((cursor, top["t1"], _row(top)))
                if stack:
                    stack[-1][1] = top["t1"]
        for s in line:
            close(s["t0"])
            s["self_ns"] = s["t1"] - s["t0"]
            if stack:
                parent, cursor = stack[-1]
                parent["self_ns"] -= min(s["t1"], parent["t1"]) - s["t0"]
                if s["t0"] > cursor:
                    segments.append((cursor, s["t0"], _row(parent)))
            stack.append([s, s["t0"]])
        close(float("inf"))
    return segments


def _row(span) -> str:
    """A span's line in the table: the eager tier's bracket by operator."""
    if span["name"] == "plan.op":
        return "plan.op " + str(span["attrs"].get("op", "?"))
    return span["name"]


# ---- bytes of a custom call, from its own HLO text -----------------------------------

def shape_bytes(shape: str) -> int:
    """'u32[88,1,128]' -> 45056."""
    m = _SHAPE.match(shape)
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    return n * DTYPE_BYTES[m.group(1)]


def hlo_bytes(text: str) -> int:
    """Bytes of the results and of the operands of one instruction as the
    trace prints it ('%n = (types) opcode(type %a, type %b), ...'): every
    result, and every operand buffer once however often it is passed."""
    head, _, rest = text.partition(" = ")
    cut = re.search(r" [\w-]+\(", rest)
    result, call = rest[:cut.start()], rest[cut.end():]
    depth, end = 1, 0
    for end, ch in enumerate(call):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            break
    operands = dict((name, shape)
                    for shape, name in _OPERAND.findall(call[:end]))
    return sum(shape_bytes(m.group(0)) for m in _SHAPE.finditer(result)) \
        + sum(shape_bytes(s) for s in operands.values())


# ---- the reduction -----------------------------------------------------------------

class Reduced:
    def __init__(self, loaded: dict, owners):
        marks = loaded["marks"]
        if len(marks) < 2:
            raise RuntimeError(f"{len(marks)} sync marks in the trace")
        self.w0, self.w1 = marks[0], marks[-1]
        self.skew = loaded["skew"]
        w0, w1 = self.w0, self.w1
        spans = [s for s in loaded["spans"] if s["t1"] > w0 and s["t0"] < w1]
        segments = _nest(spans)
        cut = {s["attrs"].get("request") for s in spans
               if s["t0"] < w0 or s["t1"] > w1}
        self.whole = [s for s in spans
                      if s["attrs"].get("request") not in cut]
        # a request counts under the root span of each side it has: the
        # submitter's serving.submit, the executing thread's plan.execute
        self.requests = {root: sorted({s["attrs"].get("request")
                                       for s in self.whole
                                       if s["name"] == root})
                         for root in ("serving.submit", "plan.execute")}
        # the device: busy intervals, idle gaps, self time per op event
        n_dev = max(1, len(loaded["devices"]))
        busy, idle_all = [], []
        self.kernels = {}        # custom call -> [self ns, bytes, calls]
        self.owner_s = {}        # plan operator -> device self seconds
        self.op_owner_s = {}     # (module/instruction, operator) -> seconds
        op_spans = sorted((s["t0"], s["t1"], s["attrs"].get("op", "?"))
                          for s in spans if s["name"] == "plan.op")
        op_starts = [s[0] for s in op_spans]
        self.has_owners = owners is not None or bool(op_spans)
        for ops in loaded["devices"]:
            inside = [(m, n, c, max(s, w0), min(e, w1), text)
                      for m, n, c, s, e, text in ops if e > w0 and s < w1]
            merged = [tuple(iv) for iv in trace._union(
                [(s, e) for _, _, _, s, e, _ in inside])]
            busy.append(_total(merged))
            edges = [w0] + [t for iv in merged for t in iv] + [w1]
            idle_all.append([(edges[i], edges[i + 1])
                             for i in range(0, len(edges), 2)
                             if edges[i + 1] > edges[i]])
            # trace._self_times takes (name, t0, t1); the event is its name
            for (m, n, c, s, e, text), own in trace._self_times(
                    [(ev, ev[3], ev[4]) for ev in inside]):
                if c == "custom-call":
                    k = self.kernels.setdefault(n, [0, 0, 0])
                    k[0] += own
                    k[1] += hlo_bytes(text)
                    k[2] += 1
                owner = UNOWNED
                if owners is not None:
                    if m == CAPPED_MODULE:
                        owner = owners.get(n, UNOWNED)
                elif op_spans:
                    # eager tier: the plan.op span of the one caller that
                    # holds the op's whole interval
                    i = bisect.bisect_right(op_starts, s) - 1
                    if i >= 0 and e <= op_spans[i][1]:
                        owner = op_spans[i][2]
                self.owner_s[owner] = self.owner_s.get(owner, 0.0) \
                    + own / n_dev / 1e9
                key = (f"{m}/{n}", owner)
                self.op_owner_s[key] = self.op_owner_s.get(key, 0.0) \
                    + own / n_dev / 1e9
        self.busy_s = sum(busy) / n_dev / 1e9
        self.idle_s = sum(_total(i) for i in idle_all) / n_dev / 1e9
        # spans by name
        by_name_segments = {}
        for t0, t1, name in segments:
            by_name_segments.setdefault(name, []).append(
                (max(t0, w0), min(t1, w1)))
        self.spans = {}
        for s in spans:
            row = self.spans.setdefault(_row(s), {
                "count": 0, "total_s": 0.0, "self_s": 0.0, "idle_s": 0.0,
                "attrs": {}})
            row["count"] += 1
            row["total_s"] += (min(s["t1"], w1) - max(s["t0"], w0)) / 1e9
            row["self_s"] += max(0, s["self_ns"]) / 1e9
            for k, v in s["attrs"].items():
                if k not in ("request", "op"):
                    row["attrs"].setdefault(k, []).append(v)
        for name, segs in by_name_segments.items():
            cover = [tuple(iv) for iv in trace._union(
                [iv for iv in segs if iv[1] > iv[0]])]
            self.spans[name]["idle_s"] = sum(
                _total(_intersect(cover, idle)) for idle in idle_all) \
                / n_dev / 1e9

    def request_ms(self, name: str) -> list:
        """Per whole request, the summed ms of its `name` spans."""
        root = "serving.submit" if name.startswith("serving.") \
            else "plan.execute"
        sums = dict.fromkeys(self.requests[root], 0.0)
        for s in self.whole:
            r = s["attrs"].get("request")
            if s["name"] == name and r in sums:
                sums[r] += (s["t1"] - s["t0"]) / 1e6
        return list(sums.values())

    def median_ms(self, name: str):
        values = self.request_ms(name)
        return statistics.median(values) if values else None

    def kind_share(self, kind: str):
        """% of device busy time owned by operators of one kind."""
        if not self.has_owners or not self.busy_s:
            return None
        return 100.0 * sum(s for o, s in self.owner_s.items()
                           if o.endswith("." + kind)) / self.busy_s

    def named_share(self):
        if not self.has_owners or not self.busy_s:
            return None
        return 100.0 * sum(s for o, s in self.owner_s.items()
                           if o != UNOWNED) / self.busy_s

    def kernel(self, prefix: str):
        """(device seconds, bytes) of the custom calls named `prefix*`."""
        rows = [k for n, k in self.kernels.items() if n.startswith(prefix)]
        return sum(k[0] for k in rows) / 1e9, sum(k[1] for k in rows)

    def tables(self) -> list:
        lines = ["device line moved by {ns} ns onto the host's clock (bounds "
                 "from the runs' enqueue and completion: {lo} .. {hi})"
                 .format(**self.skew),
                 "device seconds per plan operator (self time of its ops; "
                 f"busy {self.busy_s:.3f} s):"]
        for owner, s in sorted(self.owner_s.items(), key=lambda x: -x[1]):
            lines.append(f"  {owner:24s} {s:9.4f} s "
                         f"{100 * s / self.busy_s if self.busy_s else 0:5.1f}%")
        lines.append("largest device ops and their owners:")
        for (op, owner), sec in sorted(self.op_owner_s.items(),
                                       key=lambda x: -x[1])[:TOP_OPS]:
            lines.append(f"  {op:44s} {sec:9.4f} s  {owner}")
        lines.append("program spans in the window (idle: device idle time "
                     f"under the span, of {self.idle_s:.3f} s idle):")
        for name, row in sorted(self.spans.items(),
                                key=lambda x: -x[1]["idle_s"]):
            attrs = ", ".join(f"{k} {_summary(v)}"
                              for k, v in sorted(row["attrs"].items()))
            lines.append(
                f"  {name:26s} n {row['count']:5d}  total {row['total_s']:8.3f}"
                f" s  self {row['self_s']:8.3f} s  idle {row['idle_s']:7.3f} s"
                + (f"  [{attrs}]" if attrs else ""))
        return lines


def _summary(values) -> str:
    """An attribute over a span name's events: the median of numbers, the
    counts of texts."""
    if all(isinstance(v, (int, float)) for v in values):
        return f"{statistics.median(values):g}"
    counts = {}
    for v in values:
        counts[str(v)] = counts.get(str(v), 0) + 1
    shown = "/".join(f"{k} x{n}" for k, n in sorted(counts.items())[:4])
    return shown + (f"/.. ({len(counts)} values)" if len(counts) > 4 else "")


def capped_owners(run):
    """{instruction: '<idx>.<kind>'} of the capped program the window ran,
    or None: an eager cell, or a program without `device_op_owners`."""
    if run.cell.traffic["tier"] != "capped" \
            or not hasattr(run.executor, "device_op_owners"):
        return None
    return run.executor.device_op_owners(run.plan, run.make_inputs(0))


def of(run):
    """The run's `Reduced`, computed once and kept on the run; None where
    the run was not traced or its program opens no spans."""
    if not hasattr(run, "_program_spans"):
        run._program_spans = None
        if run.trace is not None:
            from chipbench import harness
            loaded = load(find_trace(run.trace_dir))
            if loaded["spans"]:
                red = run._program_spans = Reduced(loaded, capped_owners(run))
                for line in red.tables():
                    harness.log(line)
                low, compiled, secs = run.window_compiles
                harness.log(f"inside the traced window: {low} lowerings, "
                            f"{compiled} backend compiles taking {secs:.3f} s")
    return run._program_spans
