"""Device seconds of a capped program's ops by the `decimal.<op>` scope a
decimal kernel ran them under (`ops/decimal_utils.py`: mul, add, sub,
rescale, div, sum), for the three `decimal_*` readers.

`PlanExecutor.device_op_owners(plan, inputs, nested=True)` gives, per HLO
instruction, `<idx>.<kind>/decimal.<op>` where such a scope lies below
the operator's; `program_spans.Reduced.op_owner_s` has each instruction's
device self time in the traced window. A program without the scopes (the
parent of the PR that added them: `device_op_owners` takes no `nested`)
gives None, and the readers report nothing.
"""
import inspect

from chipbench import program_spans


def seconds(run):
    """{('Project', 'mul'): s, ('HashAggregate', 'div'): s, ...}: device
    seconds by (the operator's kind, the decimal scope below it) over the
    traced window, or None."""
    if not hasattr(run, "_decimal_scopes"):
        run._decimal_scopes = _seconds(run)
    return run._decimal_scopes


def _seconds(run):
    red = program_spans.of(run)
    if not red or run.cell.traffic["tier"] != "capped":
        return None
    owners_of = getattr(run.executor, "device_op_owners", None)
    if owners_of is None \
            or "nested" not in inspect.signature(owners_of).parameters:
        return None             # a program from before the decimal scopes
    owners = owners_of(run.plan, run.make_inputs(0), nested=True)
    out = {}
    for (op, _), s in red.op_owner_s.items():
        module, _, instruction = op.partition("/")
        operator, _, scope = owners.get(instruction, "").partition("/decimal.")
        if module == program_spans.CAPPED_MODULE and scope:
            at = (operator.partition(".")[2], scope)    # "2.Project" -> kind
            out[at] = out.get(at, 0.0) + s
    from chipbench import harness
    harness.log("device seconds by operator and decimal scope: " + (", ".join(
        f"{kind}/{name} {s:.4f}"
        for (kind, name), s in sorted(out.items(), key=lambda x: -x[1]))
        or "none"))
    return out
