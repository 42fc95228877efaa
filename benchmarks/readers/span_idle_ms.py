"""``span_idle_ms``: milliseconds in which the device ran no op while
the host was inside the program spans matching ``span_pattern``, per
span matching ``per_pattern`` (default: the same spans), over the
traced window.

It is the exposed host time of a phase, not the phase's length: under
``apex.serve.decode.fetch`` the device is busy but for the wake-up
after its last op, and a later change that overlaps a phase with the
device leaves the span as long as it was and brings this down.  Idle
time is the complement of the union of the op intervals of the first
device that ran any (``trace.busy_intervals``), as ``readers.idle``
has it, so the spans of one tick sum to that tick's share of the
device's idle time.  Spans that match ``span_pattern`` should not nest
in each other (idle time under both would count twice); a span counts
when it starts inside the window and is cut at the window's end.  None
with no device ops, no matching span or no ``per`` span."""
import bisect
import re

from ..trace import busy_intervals


def read(trace, facts, params, peaks):
    if trace is None:
        return None
    device = next((d for d in trace.devices if d.ops), None)
    if device is None:
        return None
    lo, hi = trace.window

    def matching(pattern):
        return [s for s in trace.program_spans
                if lo <= s.start <= hi and re.search(pattern, s.name)]

    spans = matching(params["span_pattern"])
    per = matching(params.get("per_pattern", params["span_pattern"]))
    if not spans or not per:
        return None
    busy = busy_intervals(device.ops, lo, hi)
    starts = [a for a, _ in busy]
    ends = [b for _, b in busy]
    before = [0.0]                    # busy seconds before interval i
    for a, b in busy:
        before.append(before[-1] + b - a)

    def busy_within(a, b):
        i = bisect.bisect_right(ends, a)      # first one ending after a
        j = bisect.bisect_left(starts, b)     # first one starting from b
        if i >= j:
            return 0.0
        return (before[j] - before[i] - max(0.0, a - starts[i])
                - max(0.0, ends[j - 1] - b))

    idle = sum((min(s.end, hi) - s.start)
               - busy_within(s.start, min(s.end, hi)) for s in spans)
    return 1e3 * idle / len(per)
