"""The collective ops of a traced run, for the readers of the `exchange`
layer: device seconds (averaged over the chips) and the bytes that must
leave a chip, from the ops' own HLO text.

An op counts by its opcode as the trace prints it: `all-to-all`,
`all-gather`, `all-reduce`, `reduce-scatter`, `collective-permute`, and
their `-start` / `-done` halves. A collective holds no nested op, so its
event's duration is its self time. The bytes of an op are its operands'
shapes (a shard's shapes: the program is already partitioned) times what
its opcode sends of them to other chips (`leaving`: an all-to-all keeps
one bucket of `peers`, an all-gather sends its shard to every other chip,
an all-reduce is a reduce-scatter and an all-gather of the result); a
`-done` half moves nothing its `-start` has not counted. A
trace without a device plane, or a program that runs no collective (one
chip), gives zeros, and the readers report nothing.
"""
import re

from chipbench import program_spans, trace

OPCODES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
           "collective-permute")


def is_collective(opcode: str) -> bool:
    return opcode.startswith(OPCODES)


def leaving(opcode: str, peers: int) -> float:
    """Bytes that leave a chip per byte of the op's operands, over a mesh
    of `peers` chips (ring algorithms, which are the least any takes)."""
    if peers < 2:
        return 0.0
    kept = (peers - 1) / peers
    base = opcode[:-len("-start")] if opcode.endswith("-start") else opcode
    return {"all-to-all": kept, "reduce-scatter": kept,
            "all-gather": float(peers - 1), "all-reduce": 2 * kept,
            "collective-permute": 1.0}[base]


def operand_bytes(text: str) -> int:
    """Bytes of the operands of one instruction as the trace prints it
    ('%n = (types) opcode(type %a, type %b), ...'), each buffer once."""
    _, _, rest = text.partition(" = ")
    cut = re.search(r" [\w-]+\(", rest)
    if cut is None:
        return 0
    call = rest[cut.end():]
    depth, end = 1, 0
    for end, ch in enumerate(call):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            break
    operands = dict((name, shape) for shape, name
                    in program_spans._OPERAND.findall(call[:end]))
    return sum(program_spans.shape_bytes(s) for s in operands.values())


def of(run):
    """{"seconds", "bytes", "ops"} of the traced window, per chip, or None
    where the run was not traced; computed once and kept on the run."""
    if not hasattr(run, "_collectives"):
        run._collectives = None
        if run.trace is not None:
            run._collectives = reduce(
                program_spans.find_trace(run.trace_dir), run.cell.chips)
            from chipbench import harness
            c = run._collectives
            harness.log("collective ops in the traced window, a chip: "
                        f"{c['seconds']:.4f} s, {c['bytes']} bytes leaving; "
                        + ", ".join(f"{k} {v[0]:.4f} s x{v[1]}" for k, v
                                    in sorted(c["ops"].items())))
    return run._collectives


def reduce(path: str, peers: int) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    marks, planes = [], []
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if trace.OP_LINE in lines:
                planes.append(lines[trace.OP_LINE])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                marks += [int(e.start_ns) for e in line.events
                          if e.name == trace.SYNC_NAME]
    out = {"seconds": 0.0, "bytes": 0, "ops": {}}
    if len(marks) < 2 or not planes:
        return out
    w0, w1 = min(marks), max(marks)
    for line in planes:
        for e in line.events:
            code = trace.opcode(trace.op_name(e.name))
            if not is_collective(code):
                continue
            s = int(e.start_ns)
            t = max(0, min(s + int(e.duration_ns), w1) - max(s, w0)) / 1e9
            if not t:
                continue
            row = out["ops"].setdefault(code, [0.0, 0])
            row[0] += t / len(planes)
            row[1] += 1
            out["seconds"] += t / len(planes)
            if not code.endswith("-done"):
                out["bytes"] += operand_bytes(e.name) \
                    * leaving(code, peers) / len(planes)
    out["bytes"] = int(out["bytes"])
    return out
