"""The general harness: one cell, one process.

    load_cell -> Run.set_up -> Run.window -> Run.check -> Run.result_line

Whatever belongs to one configuration, traffic mix, plan or per-layer
metric sits in a file of its own that is found by the name `BENCHMARK.json`
gives (`configs/`, `traffic/`, `plans/`, `layer_metrics/`); this file knows
two loops (closed-loop callers through a serving session, one caller
through `PlanExecutor.execute`) and nothing of any query.

From the program it takes the system under test alone: `PlanExecutor`,
`ServingScheduler`, the `Table` type and `place_compile_cache`. Spans are
recorded here, around the calls into each layer.
"""
import importlib
import importlib.util
import json
import os
import random
import statistics
import threading
import time

from chipbench import check as check_mod
from chipbench import spans as spans_mod
from chipbench import tpcds, trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WARM_STREAM = 1 << 30          # request numbers of warm-up batches
TABLE_STREAM = (1 << 31) - 1   # the stream of a resident cell's values
MAX_WARM_ROUNDS = 4


def log(msg: str) -> None:
    print(msg, flush=True)


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of BENCHMARK.json's `workloads`, with its files read."""

    def __init__(self, name: str, tiny: bool = False):
        bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"chipbench: no workload {name!r} in "
                             f"BENCHMARK.json (has {sorted(cells)})")
        w = cells[name]
        entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
        self.name, self.chips = name, int(w["chips"])
        self.config = read_json(os.path.join(ROOT, entry["file"]))
        self.traffic = read_json(os.path.join(
            HERE, "traffic", w["traffic"] + ".json"))
        self.plan = importlib.import_module(
            "chipbench.plans." + self.config["plan"])
        self.sizes = dict(self.config["sizes"])
        batches = self.config["batches"]
        if tiny:
            self.sizes.update(self.config["rehearsal"]["sizes"])
            batches = self.config["rehearsal"]["batches"]
        self.batch = batches[self.traffic["batch"]]
        own = read_json(os.path.join(HERE, "workloads", name + ".json"))
        if (own["config"], own["traffic"]) != (w["config"], w["traffic"]):
            raise SystemExit(f"chipbench: workloads/{name}.json and "
                             "BENCHMARK.json name different files")

        def mine(m):
            return "workloads" not in m or name in m["workloads"]
        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if mine(m) and m["moves"] in reported]


def require_devices(cell: Cell, platform: str):
    """The devices the cell asks for and their row of the peaks table, or
    exit non-zero before a table is built."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise SystemExit(f"chipbench: no {platform} device: jax.devices()[0] "
                         f"is {devs[0].platform}:{devs[0].device_kind}; "
                         "nothing was run")
    if len(devs) < cell.chips:
        raise SystemExit(f"chipbench: {cell.name} asks for {cell.chips} "
                         f"chip(s), jax sees {len(devs)}; nothing was run")
    peaks = read_json(os.path.join(HERE, "peaks.json"))
    kind = devs[0].device_kind
    if kind not in peaks["devices"]:
        raise SystemExit(f"chipbench: device kind {kind!r} is not in "
                         "chipbench/peaks.json; nothing was run")
    return devs[:cell.chips], peaks["devices"][kind]


def batch_keys(cell: Cell, seed: int, request: int):
    """(keys_key, values_key) of one batch. A resident cell's join keys are
    the configuration's fixed draw; everything else is --seed's."""
    import jax
    values = tpcds.run_key(seed, request)
    if cell.traffic["data"] == "resident":
        return tpcds.run_key(cell.sizes["dsdgen_seed"], 0), values
    return jax.random.fold_in(values, 1), values


class Run:
    def __init__(self, cell: Cell, seed: int, seconds: float, traced: bool,
                 platform: str, t_process: float):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.traced, self.platform, self.t_process = traced, platform, t_process
        self.rec = spans_mod.Recorder()
        self.requests = []       # one dict per attempted request
        self.executes = []       # one dict per PlanExecutor.execute call
        self.warm_result = None  # a resident cell's warm-up answer
        self._lock = threading.Lock()
        self.trace = None
        self.failed_why = []

    # ---- set-up -----------------------------------------------------------
    def set_up(self):
        import jax
        self.devs, self.peaks = require_devices(self.cell, self.platform)
        from spark_rapids_tpu.config import place_compile_cache
        cache_dir = place_compile_cache()
        # every program goes to the persistent cache, the sub-second ones
        # of the eager tier too: a warm run must compile nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        self.counter = spans_mod.CompileCounter()
        cell, plan_mod = self.cell, self.cell.plan
        log(f"chipbench: {cell.name} seed {self.seed} on {len(self.devs)} x "
            f"{self.devs[0].platform}:{self.devs[0].device_kind}, jax "
            f"{jax.__version__}, compile cache at {cache_dir}")
        from spark_rapids_tpu.plan import PlanExecutor
        self.dims_np = plan_mod.dimensions(cell.sizes)
        self.dims = {n: tpcds.table(c) for n, c in self.dims_np.items()}
        self.gen = plan_mod.batch_generator(cell.sizes, cell.batch)
        self.plan = plan_mod.plan()
        tier = cell.traffic["tier"]
        kwargs = dict(cell.config.get("executor", {}))
        if tier == "capped":
            kwargs["caps"] = plan_mod.caps(cell.batch)
        self.executor = PlanExecutor(mode=tier, **kwargs)
        self._observe(self.executor)
        self.fact_rows = plan_mod.fact_rows(cell.batch)
        entry = cell.traffic["entry"]
        if entry == "serving":
            self._set_up_serving()
        elif entry == "executor":
            self._set_up_resident()
        else:
            raise SystemExit(f"chipbench: traffic entry {entry!r} is not "
                             "one the harness knows (serving, executor)")
        self.setup_compiles = self.counter.snapshot()
        log(f"set-up: {self.setup_compiles[0]} lowerings, "
            f"{self.setup_compiles[1]} backend compiles taking "
            f"{self.setup_compiles[2]:.3f} s")

    def _observe(self, executor):
        """An observer around PlanExecutor.execute: when it ran, and what
        the result says of itself. Nothing inside the program changes."""
        inner = executor.execute

        def execute(*a, **kw):
            t0 = time.perf_counter_ns()
            res = inner(*a, **kw)
            t1 = time.perf_counter_ns()
            row = {"t0": t0, "t1": t1, "wall_ms": float(res.wall_ms),
                   "mode": res.mode, "attempts": res.attempts}
            if res.mode == "eager":
                row["profile"] = [(m.kind, float(m.wall_ms))
                                  for m in res.metrics.values()]
            with self._lock:
                self.executes.append(row)
            return res
        executor.execute = execute

    def _keys(self, request: int):
        return batch_keys(self.cell, self.seed, request)

    def make_inputs(self, request: int):
        """The plan's inputs for one request: fresh fact tables drawn on
        the device, the resident dimension tables."""
        import jax
        with self.rec.span("generate", request):
            drawn = self.gen(*self._keys(request))
            inputs = dict(self.dims)
            for name, (cols, validity) in drawn.items():
                inputs[name] = tpcds.table(cols, validity,
                                           self.cell.plan.COLUMNS[name])
            jax.block_until_ready(drawn)
        return inputs

    def _set_up_serving(self):
        from spark_rapids_tpu.serving import ServingScheduler
        stats = self.devs[0].memory_stats() or {}
        # the session's quota is the chip's own limit (the 256 MiB default
        # is sized for many small tenants), as chip_smoke.phase_serving
        quota = int(stats.get("bytes_limit", 16 << 30))
        self.sched = ServingScheduler(self.executor)
        self.session = self.sched.open_session("chipbench", quota_bytes=quota)
        callers = int(self.cell.traffic["callers"])
        # the first execution goes to the executor itself, as
        # chip_smoke.py's did: a cold session is charged the certifier's
        # cross-product bound (hundreds of TB for a star join) and rejects
        # the plan; a plan that has run once is charged what it used
        import jax
        inputs = self.make_inputs(WARM_STREAM)
        jax.block_until_ready(check_mod.result_arrays(
            self.executor.execute(self.plan, inputs)))
        self._one_request(WARM_STREAM, inputs)
        for rnd in range(MAX_WARM_ROUNDS):
            before = self.counter.snapshot()
            base = WARM_STREAM + (rnd + 1) * callers
            threads = [threading.Thread(
                target=lambda c=c: self._one_request(
                    base + c, self.make_inputs(base + c)))
                for c in range(callers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if self.counter.snapshot()[:2] == before[:2]:
                break
        else:
            log("set-up: the last warm-up round still compiled")
        self._end_warm_up()

    def _set_up_resident(self):
        self.inputs = self.make_inputs(TABLE_STREAM)
        for _ in range(MAX_WARM_ROUNDS):
            before = self.counter.snapshot()
            self._one_request(WARM_STREAM, self.inputs)
            if self.counter.snapshot()[:2] == before[:2]:
                break
        else:
            log("set-up: the last warm-up pass still compiled")
        self.warm_result = self._end_warm_up()["result"]

    def _end_warm_up(self) -> dict:
        """-> the last warm-up request; none of them may have failed, and
        none counts in the window."""
        bad = [r for r in self.requests if not r["ok"]]
        if bad:
            raise RuntimeError(f"warm-up request failed: {bad[0]['why']}")
        last = self.requests[-1]
        self.requests.clear()
        return last

    # ---- one request --------------------------------------------------------
    def _one_request(self, request: int, inputs):
        """Submit or execute, wait until the result's arrays are ready on
        the device, and note what the client saw."""
        import jax
        row = {"request": request, "ok": False, "why": "", "queue_wait_ms": None}
        t0 = time.perf_counter_ns()
        try:
            if self.cell.traffic["entry"] == "serving":
                with self.rec.span("submit", request):
                    ticket = self.session.submit(self.plan, inputs)
                with self.rec.span("wait", request):
                    res = ticket.result(timeout=900)
                row["queue_wait_ms"] = float(ticket.queue_wait_ms)
                cached = bool(ticket.cached)
            else:
                res = self.executor.execute(self.plan, inputs)
                cached = bool(res.cached)
            arrays = check_mod.result_arrays(res)
            jax.block_until_ready(arrays)
            t1 = time.perf_counter_ns()
            why = check_mod.guarantees_broken(
                res, arrays, cached, self.devs, self.platform,
                fresh=self.cell.traffic["data"] == "fresh")
            # the task's driver takes the answer; outside the latency
            row["result"] = check_mod.to_host(res)
            row.update(ok=not why, why=why, t0=t0, t1=t1,
                       latency_ms=(t1 - t0) / 1e6)
        except Exception as e:                    # the request failed: count
            row.update(t0=t0, t1=time.perf_counter_ns(), ok=False,
                       why=f"{type(e).__name__}: {e}"[:300])   # it, go on
        with self._lock:
            self.requests.append(row)
        return row

    # ---- the measured window ------------------------------------------------
    def window(self):
        import jax
        cell = self.cell
        callers = int(cell.traffic["callers"])
        seconds = self.seconds
        if self.traced:
            seconds = min(seconds, float(cell.traffic["trace_seconds"]))
        fresh = cell.traffic["data"] == "fresh"
        first = [self.make_inputs(c) if fresh else self.inputs
                 for c in range(callers)]
        if self.traced:
            self.trace_dir = os.path.join(ROOT, ".chipbench_trace", cell.name)
            trace.start(self.trace_dir)
            self.rec.sync()
        c0, lowered0 = self.counter.snapshot(), dict(self.counter.lowered)
        self.t_window0 = time.perf_counter_ns()
        self.setup_s = time.perf_counter() - self.t_process
        deadline = self.t_window0 + int(seconds * 1e9)

        def caller(c):
            inputs, i = first[c], 0
            while time.perf_counter_ns() < deadline:
                self._one_request(c + i * callers, inputs)
                i += 1
                if fresh and time.perf_counter_ns() < deadline:
                    inputs = self.make_inputs(c + i * callers)
        threads = [threading.Thread(target=caller, args=(c,))
                   for c in range(callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        self.t_window1 = time.perf_counter_ns()
        self.window_compiles = tuple(
            b - a for a, b in zip(c0, self.counter.snapshot()))
        if self.traced:
            self.rec.sync()
            path = trace.stop(self.trace_dir)
        self.window_s = (self.t_window1 - self.t_window0) / 1e9
        self.peak_bytes = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in self.devs)
        self._derived_spans()
        if self.traced:
            self.trace = trace.reduce(path, self.rec.spans, self.rec.syncs)
        if cell.traffic["entry"] == "serving":
            self.session.close()
            self.sched.close()
        log(f"window: {self.window_s:.3f} s, {len(self.requests)} requests "
            f"attempted, {self.window_compiles[0]} lowerings and "
            f"{self.window_compiles[1]} backend compiles inside it")
        if self.window_compiles[0]:
            log("lowered inside the window: " + ", ".join(
                f"{n} x{k - lowered0.get(n, 0)}"
                for n, k in self.counter.lowered.items()
                if k > lowered0.get(n, 0)))

    def _derived_spans(self):
        """Spans the harness cannot bracket but can place: the host plan
        path is the part of PlanExecutor.execute before PlanResult.wall_ms
        starts, the queue the wait the ticket reports after submit."""
        for e in self.executes:
            split = e["t1"] - int(e["wall_ms"] * 1e6)
            self.rec.add("host_plan", -1, e["t0"], max(e["t0"], split))
            self.rec.add("execute", -1, max(e["t0"], split), e["t1"])
        submits = {r: t1 for n, r, _, _, t1 in self.rec.spans if n == "submit"}
        for r in self.requests:
            if r.get("queue_wait_ms") and r["request"] in submits:
                t = submits[r["request"]]
                self.rec.add("queue", r["request"], t,
                             t + int(r["queue_wait_ms"] * 1e6))

    # ---- the check, after the window ------------------------------------------
    def check(self):
        """Answers against the plain reference over the same arrays, once
        the window has closed. Fresh traffic: a sample of the finished
        requests drawn from the seed, the first and the last in it, each
        batch drawn again from (seed, request number). A resident batch:
        the warm-up's answer and every answer of the window. Every number
        compared is printed beside its limit."""
        import jax
        t0 = time.perf_counter()
        plan_mod = self.cell.plan
        fresh = self.cell.traffic["data"] == "fresh"
        done = sorted((r for r in self.requests if r["ok"]),
                      key=lambda r: r["request"])
        if fresh:
            k = int(self.cell.traffic["check_sample"])
            rng = random.Random(self.seed)
            picked = done[:1] + done[-1:] if len(done) > 1 else done
            rest = done[1:-1]
            picked = picked + rng.sample(rest, min(len(rest), max(0, k - 2)))
        else:
            picked = [{"request": "warm-up", "result": self.warm_result,
                       "ok": True}] + done
        self.checked, ref = 0, None
        with self.rec.span("check"):
            for row in picked:
                if fresh or ref is None:
                    stream = row["request"] if fresh else TABLE_STREAM
                    drawn = self.gen(*self._keys(stream))
                    tables = {n: (c, {}) for n, c in self.dims_np.items()}
                    tables.update(jax.device_get(drawn))
                    ref = plan_mod.reference(tables)
                    del drawn, tables
                n = check_mod.compare(row["result"], ref,
                                      plan_mod.RESULT_COLUMNS,
                                      plan_mod.ORDERED)
                self.checked += 1
                over = [k for k, lim in check_mod.LIMITS.items()
                        if n[k] > lim]
                if fresh or over or row["request"] == "warm-up" \
                        or row is picked[-1]:
                    log(f"check request {row['request']}: " + ", ".join(
                        f"{k} {n[k]} (limit {check_mod.LIMITS[k]})"
                        for k in check_mod.LIMITS)
                        + f"; reference rows {len(ref)}")
                if over:
                    why = f"request {row['request']} differs from the " \
                          f"reference: {n}"
                    if row["request"] == "warm-up":
                        self.failed_why.append(why)
                    else:
                        row.update(ok=False, why=why)
        self.result_rows = len(ref) if ref is not None else 0
        log(f"check: {self.checked} answers compared in "
            f"{time.perf_counter() - t0:.3f} s (outside setup_s and the "
            "window)")

    # ---- the line ---------------------------------------------------------------
    def result_line(self) -> dict:
        done = [r for r in self.requests if r["ok"]]
        failed = [r for r in self.requests if not r["ok"]]
        for r in failed[:5]:
            log(f"failed request {r['request']}: {r['why']}")
        for why in self.failed_why:
            log(f"failed: {why}")
        lat = sorted(r["latency_ms"] for r in done)
        log(f"query_ms samples: {len(lat)}")
        values = {"setup_s": self.setup_s,
                  "peak_hbm_gb": self.peak_bytes / 1e9,
                  "fact_rows_per_s":
                      len(done) * self.fact_rows / self.window_s}
        if lat:
            values["query_ms.p50"] = statistics.median(lat)
            values["query_ms.p95"] = percentile(lat, 0.95)
        correct = (not failed and not self.failed_why and self.checked > 0
                   and len(done) > 0)
        device = {"platform": self.devs[0].platform,
                  "kind": self.devs[0].device_kind, "count": len(self.devs),
                  "memory_peak_bytes": self.peak_bytes}
        line = {"correct": correct, "attempted": len(self.requests),
                "failed": len(failed) + len(self.failed_why)}
        if not self.traced:
            line["metrics"] = {m["name"]: {"value": values[m["name"]],
                                           "unit": m["unit"]}
                               for m in self.cell.end_to_end
                               if m["name"] in values}
        else:
            line["metrics"] = {}
            for m in self.cell.per_layer:
                value = read_layer_metric(m["name"], self)
                if value is not None:
                    line["metrics"][m["name"]] = {"value": value,
                                                  "unit": m["unit"]}
            device.update(busy_s=self.trace["busy_s"],
                          window_s=self.trace["window_s"])
            line["breakdown"] = {"device_ops": self.trace["device_ops"],
                                 "idle_gaps": self.trace["idle_gaps"]}
        line["device"] = device
        return line


def read_layer_metric(name: str, run: Run):
    """`layer_metrics/<name>.py` holds `read(run)`: spans, counters or the
    reduced trace in, one number (or None: nothing to read) out."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def median(values):
    values = list(values)
    return statistics.median(values) if values else None


def percentile(values, q: float):
    values = sorted(values)
    return values[min(len(values) - 1, int(q * len(values)))] if values else None
