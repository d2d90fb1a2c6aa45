"""Device seconds of the ops one kind of program span held, for the
`*_device_share`, `*_sort_share` and `*_bw_share` readers of an operator:
the ops whose interval lies inside a span of the given name whose
attributes hold the given values (the eager tier blocks on an operator's
result inside its `ops.*` span, so the span holds its device work).

The span's name and attributes are arguments: `groupby_spans.py`
(`ops.groupby`) and `join_spans.py` (`ops.join`, `how=`) are two copies of
this with theirs written in; the windows' readers (`ops.window`) are the
first to call it.

`program_spans.load` puts the device's line on the host's clock. A
program without the span (the parent of the PR that added it) gives
None, and the readers report nothing.
"""
import bisect

from chipbench import program_spans, trace


def seconds(run, span: str, **attrs):
    """{"inside": device self seconds of the ops inside the `span` spans
    whose attributes equal `attrs`, over the traced window, "sorts": the
    part of it in ops of opcode `sort`, "busy": the device's busy seconds,
    "spans": how many, "attrs": their attributes in time order}, or
    None."""
    cache = run.__dict__.setdefault("_op_spans", {})
    key = (span, tuple(sorted(attrs.items())))
    if key not in cache:
        cache[key] = _seconds(run, span, attrs)
    return cache[key]


def _seconds(run, span: str, attrs: dict):
    if run.trace is None:
        return None
    loaded = program_spans.load(program_spans.find_trace(run.trace_dir))
    marks = loaded["marks"]
    held = sorted(((s["t0"], s["t1"], s["attrs"]) for s in loaded["spans"]
                   if s["name"] == span and all(
                       s["attrs"].get(k) == v for k, v in attrs.items())),
                  key=lambda h: h[:2])
    if len(marks) < 2 or not held:
        return None
    w0, w1 = marks[0], marks[-1]
    starts = [h[0] for h in held]
    inside = sorts = 0
    by_op = {}
    for ops in loaded["devices"]:
        events = [(ev, max(ev[3], w0), min(ev[4], w1)) for ev in ops
                  if ev[4] > w0 and ev[3] < w1]
        for (module, name, code, s, e, _), own in trace._self_times(events):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or e > held[i][1]:
                continue
            inside += own
            sorts += own if code.startswith("sort") else 0
            key = f"{module}/{name}:{code}"
            by_op[key] = by_op.get(key, 0) + own
    n_dev = max(1, len(loaded["devices"]))
    out = {"inside": inside / n_dev / 1e9, "sorts": sorts / n_dev / 1e9,
           "busy": run.trace["busy_s"], "spans": len(held),
           "attrs": [dict(h[2]) for h in held]}
    said = "".join(f" of {k}={v}" for k, v in attrs.items())
    from chipbench import harness
    harness.log(f"device seconds inside {len(held)} {span} spans{said}: "
                f"{out['inside']:.4f} of {out['busy']:.4f} busy, sorts "
                f"{out['sorts']:.4f}; largest: " + ", ".join(
                    f"{k} {v / n_dev / 1e9:.4f}" for k, v in sorted(
                        by_op.items(), key=lambda x: -x[1])[:12]))
    return out
