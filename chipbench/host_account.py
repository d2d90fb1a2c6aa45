"""A request's host time under names, read back from a traced run.

Since PR 38 a traced request of the program is a tree whose leaves tile it
(`utils/tracing.py`, docs/plan.md "Reading a profile"): brackets
(`serving.submit`, `serving.dispatch`, `plan.execute`, `plan.run`,
`plan.attempt`, whose own time names nothing), operator brackets whose own
time is the host's dispatch (`plan.op`, `plan.exchange`, `ops.groupby`),
leaves that say what the host was doing (`plan.bind`, `plan.caps`,
`plan.program`, `plan.launch`, `plan.readback`, `plan.result`,
`serving.consult`, `serving.complete` beside the older ones), and two
leaves for every wait on the device (`plan.wait`, `ops.host_sync`).
`of(run)` reduces that, once a run, to what the twelve readers beside
`layer_metrics/` ask for:

- `median_ms(names, own=False)`: per whole request the summed duration of
  its spans of those names (`own`: minus what their children cover), the
  median over the window's requests; None where the trace has none of them;
- `unnamed_ms()`: per request the own time of `plan.execute`, `plan.run`
  and `plan.attempt`: what no leaf covers (an operator bracket's own time
  is dispatch and is printed beside it);
- `attr_mean(name, attr)`: an attribute of a span over the whole requests;
- `idle_shares()`: of the device's idle time in the window, the part while
  some thread waits for the device and no thread is in another leaf, and
  the part while no thread is in any leaf at all (one device plane only).

It reuses `program_spans.of(run)` for the spans of whole requests, their
own times and the idle seconds under each name; the shares need the
device's busy intervals, which `Reduced` does not keep: one more pass over
the trace that reads intervals and span names only (no HLO text), kept on
the run. A trace of a program without the new spans (the parent of PR 38)
gives None from `of`: every reader then reports nothing. The request's
account is printed through `harness.log`.
"""
import statistics

from chipbench import program_spans, trace
from chipbench.spans import SYNC_NAME

# the spans PR 38 added: a trace with none of them is its parent's
NEW = ("plan.bind", "plan.caps", "plan.program", "plan.launch", "plan.wait",
       "plan.readback", "plan.result", "serving.consult", "serving.complete")
WAITS = ("plan.wait", "ops.host_sync")
BRACKETS = ("serving.submit", "serving.dispatch", "plan.execute", "plan.run",
            "plan.attempt")
DISPATCH = ("plan.op", "plan.exchange", "ops.groupby")
# what lies outside `PlanResult.wall_ms`, by the name the account gives it
PLAN_PATH = (("bind", "plan.bind"), ("optimize", "plan.optimize"),
             ("verify", "plan.verify"), ("certify", "plan.certify"),
             ("caps", "plan.caps"), ("readback", "plan.readback"),
             ("result", "plan.result"), ("stats", "plan.stats"))


def light_load(path: str, skew_ns: int) -> dict:
    """-> {"spans": the program's spans (name, thread, t0, t1 in ns),
    "busy": per device plane the merged intervals in which an op ran, on
    the host's clock}. Names and intervals only."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, busy, thread = [], [], 0
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == trace.OP_LINE:
                    busy.append([tuple(iv) for iv in trace._union(
                        (int(e.start_ns) + skew_ns,
                         int(e.start_ns) + int(e.duration_ns) + skew_ns)
                        for e in line.events)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                thread += 1
                for e in line.events:
                    name = e.name
                    if name.startswith(program_spans.PREFIXES) \
                            and name != SYNC_NAME:
                        t0 = int(e.start_ns)
                        spans.append({"name": name, "thread": thread,
                                      "t0": t0,
                                      "t1": t0 + int(e.duration_ns),
                                      "attrs": {}})
    return {"spans": spans, "busy": busy}


def idle_intervals(busy, w0: int, w1: int) -> list:
    """The gaps of one device's merged busy intervals inside the window."""
    inside = [(max(s, w0), min(e, w1)) for s, e in busy if e > w0 and s < w1]
    edges = [w0] + [t for iv in inside for t in iv] + [w1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _subtract(a, b):
    """Two sorted, merged interval lists -> a without b."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def host_states(spans) -> tuple:
    """-> (waiting, working): the merged intervals during which some
    thread's deepest open span is a wait on the device, and during which
    some thread's is any other leaf or an operator bracket (named work).
    Under brackets only, or under no span, a thread is in neither."""
    waiting, working = [], []
    for t0, t1, row in program_spans._nest([dict(s) for s in spans]):
        name = row.split(" ")[0]         # `plan.op 7.HashJoin` is plan.op
        if t1 <= t0 or name in BRACKETS:
            continue
        (waiting if name in WAITS else working).append((t0, t1))
    merge = lambda ivs: [tuple(iv) for iv in trace._union(ivs)]  # noqa: E731
    return merge(waiting), merge(working)


def idle_shares(spans, idle) -> dict:
    """Of the idle intervals `idle` (sorted, disjoint): {"wait": % while a
    thread waits for the device and none is in another leaf, "unnamed": %
    while no thread is in any leaf, "work": the rest, % while some thread
    does named work}; None where nothing idled."""
    total = program_spans._total(idle)
    if not total:
        return None
    waiting, working = host_states(spans)
    in_work = program_spans._total(program_spans._intersect(idle, working))
    in_wait = program_spans._total(program_spans._intersect(
        idle, _subtract(waiting, working)))
    return {"wait": 100.0 * in_wait / total,
            "work": 100.0 * in_work / total,
            "unnamed": 100.0 * (total - in_wait - in_work) / total}


class Account:
    """The reduction of one traced run. `red`: its `program_spans.Reduced`
    (spans carry `self_ns`); `light`: `light_load`'s dict, or a callable
    that gives it when the idle shares are first asked for."""

    def __init__(self, red, light=None):
        self.red = red
        self._light = light
        self.requests = red.requests["plan.execute"]
        self.capped = "plan.attempt" in red.spans
        self._shares = False

    # ---- per request ---------------------------------------------------------
    def request_ms(self, names, own=False, where=None) -> dict:
        """{request: summed ms of its spans named in `names`}, 0 where a
        request has none; `where(span)` narrows them."""
        sums = dict.fromkeys(self.requests, 0.0)
        for s in self.red.whole:
            r = s["attrs"].get("request")
            if s["name"] in names and r in sums \
                    and (where is None or where(s)):
                sums[r] += (max(0, s["self_ns"]) if own
                            else s["t1"] - s["t0"]) / 1e6
        return sums

    def median_ms(self, names, own=False):
        names = (names,) if isinstance(names, str) else tuple(names)
        if not self.requests or not any(n in self.red.spans for n in names):
            return None
        return statistics.median(self.request_ms(names, own).values())

    def unnamed_ms(self):
        """What a request's `plan.execute` holds beside its leaves."""
        return self.median_ms(("plan.execute", "plan.run", "plan.attempt"),
                              own=True)

    def attr_mean(self, name: str, attr: str):
        wanted = set(self.requests)
        values = [s["attrs"][attr] for s in self.red.whole
                  if s["name"] == name and attr in s["attrs"]
                  and s["attrs"].get("request") in wanted]
        return statistics.fmean(values) if values else None

    # ---- the device's idle time ------------------------------------------------
    def idle_shares(self):
        if self._shares is False:
            self._shares = None
            light = self._light() if callable(self._light) else self._light
            if light and len(light["busy"]) == 1:
                w0, w1 = self.red.w0, self.red.w1
                spans = [s for s in light["spans"]
                         if s["t1"] > w0 and s["t0"] < w1]
                self._shares = idle_shares(
                    spans, idle_intervals(light["busy"][0], w0, w1))
        return self._shares

    # ---- the printed account -----------------------------------------------------
    def plan_path(self) -> list:
        """[(part, median ms a request)] of what `PlanResult.wall_ms` does
        not cover, by span: the children of `plan.execute`, and in the
        capped tier those of `plan.run` that lie before and after the
        result's clock. `caps` is less its `plan.stats` child, which
        `stats` holds; the eager tiers' epilogue inside `plan.run` lies
        inside the wall time and is left out."""
        executes = sorted((s["thread"], s["t0"], s["t1"])
                          for s in self.red.whole
                          if s["name"] == "plan.execute")
        runs = [(s["thread"], s["t0"], s["t1"]) for s in self.red.whole
                if s["name"] == "plan.run"]

        def outside_wall(s):
            if not any(th == s["thread"] and a <= s["t0"] and s["t1"] <= b
                       for th, a, b in executes):
                return False         # admission's certify, on the submitter
            return self.capped or not any(
                th == s["thread"] and a <= s["t0"] and s["t1"] <= b
                for th, a, b in runs)
        parts = []
        for part, name in PLAN_PATH:
            sums = self.request_ms((name,), own=name == "plan.caps",
                                   where=outside_wall)
            parts.append((part, statistics.median(sums.values())
                          if sums else 0.0))
        return parts

    def operators(self) -> list:
        """The eager tiers, per operator: [(op, {"n", "total", "dispatch"
        (the bracket's own time), "wait", "host_sync", "inner" (the own
        time of `plan.exchange` and `ops.groupby` below it), "lowerings",
        "lowering_ms"})], medians over the requests of a request's sums,
        in the order the operators ran."""
        rows, order = {}, []
        by_thread = {}
        for s in self.red.whole:
            by_thread.setdefault(s["thread"], []).append(s)
        wanted = set(self.requests)
        for line in by_thread.values():
            line.sort(key=lambda s: (s["t0"], -s["t1"]))
            op = None
            for s in line:
                r = s["attrs"].get("request")
                if r not in wanted:
                    continue
                if s["name"] == "plan.op":
                    op = s
                    key = str(s["attrs"].get("op", "?"))
                    if key not in rows:
                        rows[key] = {}
                        order.append(key)
                    cell = rows[key].setdefault(r, dict.fromkeys(
                        ("n", "total", "dispatch", "wait", "host_sync",
                         "inner", "lowerings", "lowering_ms"), 0.0))
                    cell["n"] += 1
                    cell["total"] += (s["t1"] - s["t0"]) / 1e6
                    cell["dispatch"] += max(0, s["self_ns"]) / 1e6
                    cell["lowerings"] += s["attrs"].get("lowerings", 0)
                    cell["lowering_ms"] += s["attrs"].get("lowering_ms", 0)
                elif op is not None and s["t1"] <= op["t1"] \
                        and s["t0"] >= op["t0"]:
                    cell = rows[str(op["attrs"].get("op", "?"))][r]
                    if s["name"] == "plan.wait":
                        cell["wait"] += (s["t1"] - s["t0"]) / 1e6
                    elif s["name"] == "ops.host_sync":
                        cell["host_sync"] += (s["t1"] - s["t0"]) / 1e6
                    elif s["name"] in DISPATCH:
                        cell["inner"] += max(0, s["self_ns"]) / 1e6
        return [(key, {k: statistics.median(c[k] for c in rows[key].values())
                       for k in next(iter(rows[key].values()))})
                for key in order]

    def lines(self, host_plan_ms=None) -> list:
        red = self.red
        out = [f"a request's account ({len(self.requests)} whole requests; "
               "median ms a request: summed, and its own time less its "
               "children; device idle seconds while it was the deepest "
               f"span open, of {red.idle_s:.3f} s idle):"]
        names = sorted(n for n in red.spans if not n.startswith("plan.op "))
        for name in names:
            row = red.spans[name]
            total = self.median_ms(name) or 0.0
            own = self.median_ms(name, own=True) or 0.0
            kind = ("bracket" if name in BRACKETS else "dispatch"
                    if name in DISPATCH else "wait" if name in WAITS
                    else "leaf")
            out.append(f"  {name:18s} {kind:8s} n {row['count']:5d}  "
                       f"{total:10.3f} ms  own {own:10.3f} ms  idle "
                       f"{row['idle_s']:7.3f} s")
        ops = self.operators()
        if ops:
            out.append("by operator (median ms a request: the bracket, its "
                       "own time = the host's dispatch, plan.wait, "
                       "ops.host_sync, own time of plan.exchange and "
                       "ops.groupby below it; lowerings, lowering_ms; device "
                       "idle seconds under the bracket's own time):")
            for key, c in ops:
                idle = red.spans.get("plan.op " + key, {}).get("idle_s", 0.0)
                out.append(
                    f"  {key:22s} n {c['n']:3.0f}  {c['total']:9.3f}  "
                    f"dispatch {c['dispatch']:9.3f}  wait {c['wait']:9.3f}  "
                    f"host_sync {c['host_sync']:9.3f}  inner "
                    f"{c['inner']:8.3f}  lowerings {c['lowerings']:4.0f} "
                    f"{c['lowering_ms']:9.3f} ms  idle {idle:7.3f} s")
            dispatch = sum(c["dispatch"] + c["inner"] for _, c in ops)
            out.append(f"  dispatch over the operators: {dispatch:.3f} ms a "
                       f"request; execute_unnamed_ms "
                       f"{self.unnamed_ms() or 0.0:.3f}")
        parts = self.plan_path()
        if host_plan_ms is not None and parts:
            rest = host_plan_ms - sum(ms for _, ms in parts)
            out.append("host_plan_ms = " + " + ".join(p for p, _ in parts)
                       + " + remainder: " + f"{host_plan_ms:.3f} = "
                       + " + ".join(f"{ms:.3f}" for _, ms in parts)
                       + f" + {rest:.3f}")
        shares = self.idle_shares()
        if shares:
            out.append("device idle time by what the host was in: waiting "
                       f"for the device {shares['wait']:.1f}%, named work "
                       f"{shares['work']:.1f}%, brackets only or no span "
                       f"{shares['unnamed']:.1f}%")
        return out


def of(run):
    """The run's `Account`, computed once and kept on the run; None where
    the run was not traced or its program has none of the new spans."""
    if not hasattr(run, "_host_account"):
        run._host_account = None
        red = program_spans.of(run)
        if red is not None and any(n in red.spans for n in NEW):
            # the shares are one device's: `device_skew` is not to be
            # trusted over four planes (PERF.md section 7), so no pass
            acc = run._host_account = Account(
                red, lambda: light_load(
                    program_spans.find_trace(run.trace_dir), red.skew["ns"])
                if run.trace.get("devices") == 1 else None)
            from chipbench import harness
            host_plan = harness.read_layer_metric("host_plan_ms", run)
            for line in acc.lines(host_plan):
                harness.log(line)
    return run._host_account
