"""Device seconds of the ops an eager join ran, for the three
`outer_join_*` readers: the ops whose interval lies inside an `ops.join`
span of the program with the wanted `how` (`plan/executor.py`: the join's
maps, the gathers of its output columns and the wait for them; the eager
tier blocks on the result inside the span, so the span holds its device
work).

`program_spans.load` puts the device's line on the host's clock. A
program without the span (the parent of the PR that added it) gives
None, and the readers report nothing.
"""
import bisect

from chipbench import program_spans, trace

SPAN = "ops.join"


def seconds(run, how: str = "left_outer"):
    """{"inside": device self seconds of the ops inside the `ops.join`
    spans of `how` over the traced window, "sorts": the part of it in ops
    of opcode `sort`, "busy": the device's busy seconds, "spans": how
    many, "matched" / "unmatched": what the last of them says it put
    out}, or None."""
    cache = run.__dict__.setdefault("_join_spans", {})
    if how not in cache:
        cache[how] = _seconds(run, how)
    return cache[how]


def _seconds(run, how: str):
    if run.trace is None:
        return None
    loaded = program_spans.load(program_spans.find_trace(run.trace_dir))
    marks = loaded["marks"]
    held = sorted((s["t0"], s["t1"], s["attrs"]) for s in loaded["spans"]
                  if s["name"] == SPAN and s["attrs"].get("how") == how)
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
    last = held[-1][2]
    out = {"inside": inside / n_dev / 1e9, "sorts": sorts / n_dev / 1e9,
           "busy": run.trace["busy_s"], "spans": len(held),
           "matched": last.get("matched"), "unmatched": last.get("unmatched")}
    from chipbench import harness
    harness.log(f"device seconds inside {len(held)} {SPAN} spans of how="
                f"{how}: {out['inside']:.4f} of {out['busy']:.4f} busy, "
                f"sorts {out['sorts']:.4f}; largest: " + ", ".join(
                    f"{k} {v / n_dev / 1e9:.4f}" for k, v in sorted(
                        by_op.items(), key=lambda x: -x[1])[:12]))
    return out
