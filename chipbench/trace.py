"""From a profiler trace (`.xplane.pb`) and the benchmark's spans to numbers.

`start`/`stop` record the window; `reduce` reads it back with
`jax.profiler.ProfileData` and gives

- `busy_s`: seconds in which an operation ran on a device (the union of the
  intervals on the device's op line), averaged over the devices used;
- `window_s`: the length of the traced window (first to last sync mark);
- `device_ops`: the operations that took most device time, by the names the
  trace prints; `op_seconds` holds all of them for the readers;
- `idle_gaps`: the longest gaps in which no operation ran, each named by
  the benchmark span that covered most of it on the host.

The same code reads the small recorded trace kept in `tests/data/`.
"""
import bisect
import glob
import os
import re
import shutil

from chipbench.spans import SYNC_NAME

OP_LINE = "XLA Ops"              # the line of a device plane that holds ops
MODULE_LINE = "XLA Modules"      # and the one that holds whole programs
DEVICE_PREFIX = "/device:TPU:"
TOP = 10


def start(out_dir: str) -> None:
    import jax
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # the spans are ours; python frames
    opts.host_tracer_level = 2         # only slow the callers down
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out_dir, profiler_options=opts)


def stop(out_dir: str) -> str:
    import jax
    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {out_dir}")
    return found[-1]


def op_name(text: str, module: str = "") -> str:
    """'<program>/<instruction>:<opcode>' from the HLO text the trace prints
    for an op ('%sort.6 = (s32[..]) sort(..)') and the program around it
    ('jit_step(123)')."""
    m = re.match(r"%(\S+) = .*?[\}\)\]] ([\w-]+)\(", text)
    short = f"{m.group(1)}:{m.group(2)}" if m else text[:60]
    module = re.sub(r"\(\d+\)$", "", module)
    return f"{module}/{short}" if module else short


def opcode(name: str) -> str:
    return name.rsplit(":", 1)[-1]


def _union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """[(name, own ns)]: an op's duration minus that of the ops nested in
    it (a while loop's body appears on the same line as the loop)."""
    out, stack = [], []          # stack of [name, end, own]
    for n, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([n, e, e - s])
    out += [(n, own) for n, _, own in stack]
    return out


def _overlap(a0, a1, b0, b1):
    return max(0, min(a1, b1) - max(a0, b0))


def reduce(path: str, spans: list, syncs: list) -> dict:
    """`spans` and `syncs` as `spans.Recorder` keeps them (host
    perf_counter_ns); the first and last sync bound the window."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    marks, devices = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if OP_LINE in lines:
                mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                               e.name) for e in lines[MODULE_LINE].events) \
                    if MODULE_LINE in lines else []
                starts = [m[0] for m in mods]
                ops = []
                for e in lines[OP_LINE].events:
                    s = int(e.start_ns)
                    i = bisect.bisect_right(starts, s) - 1
                    module = mods[i][2] if i >= 0 and s < mods[i][1] else ""
                    ops.append((op_name(e.name, module), s,
                                s + int(e.duration_ns)))
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                marks += [int(e.start_ns) for e in line.events
                          if e.name == SYNC_NAME]
    marks.sort()
    if len(marks) < 2 or len(marks) != len(syncs):
        raise RuntimeError(f"{len(marks)} sync marks in the trace, "
                           f"{len(syncs)} recorded: cannot place the window")
    if not devices:
        raise RuntimeError(f"no '{OP_LINE}' line on a '{DEVICE_PREFIX}*' "
                           "plane: no operation ran on the device")
    w0, w1 = marks[0], marks[-1]
    # host perf_counter -> trace clock (the mark starts just before the
    # host reading inside it)
    offset = sorted(m - s for m, s in zip(marks, sorted(syncs)))[len(marks) // 2]
    op_seconds, busy, gaps = {}, [], []
    for events in devices:
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in events
                  if e > w0 and s < w1]
        for n, self_ns in _self_times(inside):
            op_seconds[n] = op_seconds.get(n, 0.0) + self_ns / 1e9
        merged = _union([(s, e) for _, s, e in inside])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = len(devices)
    op_seconds = {n: s / n_dev for n, s in op_seconds.items()}
    placed = [(n, t0 + offset, t1 + offset) for n, _, _, t0, t1 in spans]
    named = {}
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        cover = {}
        for n, s0, s1 in placed:
            ov = _overlap(g0, g1, s0, s1)
            if ov:
                cover[n] = cover.get(n, 0) + ov
        name = max(cover, key=cover.get) if cover else "outside_spans"
        named.setdefault(name, []).append((g1 - g0) / 1e9)
    idle = sorted(((n, max(v)) for n, v in named.items()),
                  key=lambda x: -x[1])
    return {"busy_s": sum(busy) / n_dev, "window_s": (w1 - w0) / 1e9,
            "devices": n_dev, "op_seconds": op_seconds,
            "device_ops": [[n, s] for n, s in sorted(
                op_seconds.items(), key=lambda x: -x[1])[:TOP]],
            "idle_gaps": [[n, s] for n, s in idle[:TOP]]}
