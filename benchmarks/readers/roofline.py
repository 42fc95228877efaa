"""``roofline``: the least time the chip could take for a kernel's
work, over the time its ops took in the trace, in percent.

The least time is ``max(flops / peak flops, bytes / peak bytes per
second)`` with the peaks of ``peaks.json``; flops and bytes come from a
function under ``rooflines/`` (``counts``: ``module:function``) applied
to the run's shapes (``facts[shapes_fact]``).  ``per`` says what those
counts cover: ``run`` -- one program run, multiplied by the runs in the
trace -- or ``trace`` -- everything the host recorded while the
profiler was on.  ``facts['roofline_bound']`` receives which of the two
peaks bounds each metric.  Other parameters as ``op_ms``."""
import importlib

from .op_ms import matching_op_seconds


def read(trace, facts, params, peaks):
    shapes = facts.get(params["shapes_fact"])
    if trace is None or not shapes:
        return None
    seconds, n_runs = matching_op_seconds(trace, params)
    if n_runs == 0 or seconds == 0.0:
        return None
    module, function = params["counts"].split(":")
    flops, nbytes = getattr(importlib.import_module(module),
                            function)(**shapes)
    if params["per"] == "run":
        flops, nbytes = flops * n_runs, nbytes * n_runs
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    facts.setdefault("roofline_bound", {})[params["shapes_fact"]] = \
        "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds
