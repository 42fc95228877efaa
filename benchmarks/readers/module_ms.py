"""``module_ms``: device time of one program, per run, in milliseconds.

Parameters: ``module_pattern`` (regex on the XLA module's name) and,
optionally, ``contains_op`` (regex on an op's HLO text: only runs that
hold such an op count -- decode ticks and prefills share a module
name) and ``scope_pattern`` (regex on the same op's scope)."""
from ..trace import runs_matching


def read(trace, facts, params, peaks):
    if trace is None:
        return None
    runs = [m for d in trace.devices
            for m in runs_matching(d, params["module_pattern"],
                                   params.get("contains_op"),
                                   params.get("scope_pattern"))]
    if not runs:
        return None
    return 1e3 * sum(m.dur for m in runs) / len(runs)
