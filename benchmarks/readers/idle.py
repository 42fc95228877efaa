"""``idle``: the share of the traced window in which no op ran on the
device, in percent."""
from ..trace import busy_seconds


def read(trace, facts, params, peaks):
    if trace is None or trace.window_s <= 0 \
            or not any(d.ops for d in trace.devices):
        return None
    return 100.0 * (1.0 - busy_seconds(trace) / trace.window_s)
