"""``step_mfu``: the share of the chip's peak that the whole step
reaches, in percent: the flops of one program run (``facts[flops_fact]``,
counted under ``benchmarks/``) over the period from one run's start to
the next's, on the device's clock, and ``peaks.json``'s bf16 peak times
the devices that ran the program.

The period holds the idle gap between two runs, so this is the share
of the rate a user sees and not of the busy time alone; it is taken
from the runs that started inside the traced window (the profiler's
late first step lies before it), so no stall of the host's around the
trace enters it.  Parameters: ``module_pattern``, ``flops_fact``.  None
when the run kept no such fact or the window holds fewer than two
runs."""
import re


def read(trace, facts, params, peaks):
    flops = facts.get(params["flops_fact"])
    if trace is None or not flops:
        return None
    lo, hi = trace.window
    periods, devices = [], 0
    for d in trace.devices:
        starts = [m.start for m in d.modules
                  if lo <= m.start <= hi
                  and re.search(params["module_pattern"], m.name)]
        if len(starts) >= 2:
            devices += 1
            periods.append((starts[-1] - starts[0]) / (len(starts) - 1))
    if not periods:
        return None
    period = sum(periods) / len(periods)
    return 100.0 * flops / (period * devices * peaks["bf16_flops_per_s"])
