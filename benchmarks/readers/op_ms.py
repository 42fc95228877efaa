"""``op_ms``: device time of the ops matching ``op_pattern`` inside
the runs of one program, per run, in milliseconds.  Parameters:
``module_pattern`` and optional ``contains_op`` as ``module_ms``, plus
``op_pattern`` and, optionally, ``scope_pattern`` (regex on the op's
scope; an op counts when both match)."""
from ..trace import ops_in_runs, ops_matching, runs_matching


def matching_op_seconds(trace, params):
    """(seconds in matching ops, number of program runs) over the
    trace; (0, 0) when the program did not run."""
    seconds, n_runs = 0.0, 0
    for d in trace.devices:
        runs = runs_matching(d, params["module_pattern"],
                             params.get("contains_op"))
        n_runs += len(runs)
        wanted = ops_matching(d.ops, params.get("op_pattern"),
                              params.get("scope_pattern"))
        seconds += sum(op.dur for _, op in ops_in_runs(runs, wanted))
    return seconds, n_runs


def read(trace, facts, params, peaks):
    if trace is None:
        return None
    seconds, n_runs = matching_op_seconds(trace, params)
    if n_runs == 0 or seconds == 0.0:
        return None
    return 1e3 * seconds / n_runs
