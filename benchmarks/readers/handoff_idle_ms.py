"""``handoff_idle_ms``: milliseconds in which the device ran no op while
a program was being handed to it or its result back to the host, per
span matching ``per_pattern`` (default: the spans themselves), over the
traced window.  Each program run is linked to the host span that caused
it, so the runtime's latency is put down to its edge wherever the host
happens to be when it falls.

``edge: "launch"``: for each span matching ``span_pattern`` (the
dispatches), the run it launched is the first ``module_pattern`` run
(default ``^jit_step\\(``) that starts before the next matching span's
start and that no earlier span took; the idle time in ``[span start,
run start]`` counts.  A span with no such run counts nothing, and one
whose program waits behind an earlier program that keeps the device
busy reads 0: what is hidden counts nothing.  A run the trace puts up to
``CLOCK_SLACK_S`` before its span's start is still that span's (the
device's events are laid on the host's clock only so far: on one
Laguna trace from a TPU v5e, 302 of 395 runs sat up to 0.5 ms before
the dispatch that launched them) and reads 0; runs that start earlier
than that belong to earlier work.

``edge: "wake"``: for each span matching ``span_pattern`` (the
fetches), the run whose result it waits for is the last that started
before the span's end; the idle time in ``[max(span start, run end),
span end]`` counts.  A launch latency that lands inside a fetch lies
before its run's start: ``launch`` counts it and ``wake`` never does.

Idle time is the complement of the union of the op intervals of the
first device that ran any (``trace.busy_intervals``), as
``span_idle_ms`` has it; a span counts when it starts inside the window
and its interval is cut at the window's end.  None with no device ops,
no matching span or no ``per`` span."""
import bisect
import re

from ..trace import busy_intervals, runs_matching

CLOCK_SLACK_S = 1e-3


def read(trace, facts, params, peaks):
    if trace is None:
        return None
    device = next((d for d in trace.devices if d.ops), None)
    if device is None:
        return None
    lo, hi = trace.window
    pattern = params["span_pattern"]
    every = [s for s in trace.program_spans if re.search(pattern, s.name)]
    per = [s for s in trace.program_spans if lo <= s.start <= hi
           and re.search(params.get("per_pattern", pattern), s.name)]
    if not per or not any(lo <= s.start <= hi for s in every):
        return None
    runs = runs_matching(device, params.get("module_pattern",
                                            r"^jit_step\("))
    run_starts = [m.start for m in runs]
    busy = busy_intervals(device.ops, lo, hi)
    starts = [a for a, _ in busy]
    ends = [b for _, b in busy]
    before = [0.0]                    # busy seconds before interval i
    for a, b in busy:
        before.append(before[-1] + b - a)

    def idle_within(a, b):
        b = min(b, hi)
        if b <= a:
            return 0.0
        i = bisect.bisect_right(ends, a)      # first one ending after a
        j = bisect.bisect_left(starts, b)     # first one starting from b
        if i >= j:
            return b - a
        return max(0.0, (b - a) - (before[j] - before[i]
                                   - max(0.0, a - starts[i])
                                   - max(0.0, ends[j - 1] - b)))

    idle = 0.0
    r = 0                             # launch: the first run not taken
    for k, s in enumerate(every):
        counted = lo <= s.start <= hi
        if params["edge"] == "launch":
            nxt = every[k + 1].start if k + 1 < len(every) else float("inf")
            r = max(r, bisect.bisect_left(run_starts,
                                          s.start - CLOCK_SLACK_S))
            if r < len(runs) and runs[r].start < nxt:
                if counted:
                    idle += idle_within(s.start, runs[r].start)
                r += 1
        elif counted:
            w = bisect.bisect_left(run_starts, s.end) - 1
            if w >= 0:
                idle += idle_within(max(s.start, runs[w].end), s.end)
    return 1e3 * idle / len(per)
