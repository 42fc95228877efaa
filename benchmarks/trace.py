"""From a ``jax.profiler`` trace to plain event lists, and the two
reductions every traced run reports: device busy time and the
``breakdown``.

The ``.xplane.pb`` is read with ``jax.profiler.ProfileData`` alone.  On
this machine (see README.md) a device plane ``/device:TPU:<n>`` holds the
lines ``XLA Modules`` (one event per program run, named
``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event per device op,
named by its HLO text); the host plane ``/host:CPU`` holds one line per
thread, and ``jax.profiler.TraceAnnotation`` spans land on the line of
the thread that opened them.  All times are seconds from the trace's
own zero, host and device on one clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."        # the benchmark's own host annotations


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float              # seconds
    dur: float                # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class DeviceTrace:
    modules: List[Event]      # program runs, by start
    ops: List[Event]          # device ops, by start


@dataclasses.dataclass
class Trace:
    """What the readers get.  ``window`` is the traced steady slice:
    from the start of the first ``bench.*`` host span to the end of the
    last (the device's own extent when there is none)."""

    devices: List[DeviceTrace]
    spans: List[Event]        # bench.* host spans, by start
    window: Tuple[float, float]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def make_trace(devices: Sequence[DeviceTrace],
               spans: Iterable[Event]) -> Trace:
    """Sort, and set the window (also how the tests build a trace by
    hand)."""
    devices = [DeviceTrace(sorted(d.modules, key=lambda e: e.start),
                           sorted(d.ops, key=lambda e: e.start))
               for d in devices]
    spans = sorted(spans, key=lambda e: e.start)
    marks = spans or [e for d in devices for e in (d.modules or d.ops)]
    if not marks:
        return Trace(devices, spans, (0.0, 0.0))
    return Trace(devices, spans, (min(e.start for e in marks),
                                  max(e.end for e in marks)))


def load_trace(trace_dir: str) -> Optional[Trace]:
    """The newest ``.xplane.pb`` under ``trace_dir``, or None when the
    profiler wrote none."""
    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return None
    data = ProfileData.from_file(found[-1])
    devices: Dict[str, DeviceTrace] = {}
    spans: List[Event] = []
    for plane in data.planes:
        device = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if device is None and plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if device is not None and line.name in ("XLA Modules",
                                                    "XLA Ops"):
                events = [Event(e.name, e.start_ns * 1e-9,
                                e.duration_ns * 1e-9)
                          for e in line.events]
                dev = devices.setdefault(plane.name, DeviceTrace([], []))
                if line.name == "XLA Modules":
                    dev.modules.extend(events)
                else:
                    dev.ops.extend(events)
            elif device is None:
                spans.extend(
                    Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    return make_trace([devices[k] for k in sorted(devices)], spans)


# --- busy time ---------------------------------------------------------------

def busy_intervals(events: Sequence[Event], lo: float,
                   hi: float) -> List[Tuple[float, float]]:
    """The union of the events' intervals, clipped to [lo, hi], as
    disjoint sorted intervals.  ``events`` sorted by start."""
    out: List[Tuple[float, float]] = []
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_seconds(trace: Trace) -> float:
    """Seconds of the window in which an op ran on the device, averaged
    over the devices that ran any."""
    per_device = [sum(b - a for a, b in
                      busy_intervals(d.ops, *trace.window))
                  for d in trace.devices if d.ops]
    return sum(per_device) / len(per_device) if per_device else 0.0


# --- which program run an op belongs to ----------------------------------------

def ops_in_runs(runs: Sequence[Event], ops: Iterable[Event]):
    """Yield ``(index into runs, op)`` for each op that started inside
    one of ``runs`` (sorted by start, not overlapping)."""
    starts = [m.start for m in runs]
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start <= runs[i].end:
            yield i, op


def runs_matching(device: DeviceTrace, module_pattern: str,
                  contains_op: Optional[str] = None) -> List[Event]:
    """The device's program runs whose name matches ``module_pattern``
    and, with ``contains_op``, that hold at least one op matching it (a
    decode tick and a prefill are both ``jit_step``: the flash-decode
    custom call tells them apart)."""
    runs = [m for m in device.modules if re.search(module_pattern, m.name)]
    if contains_op is None:
        return runs
    wanted = (op for op in device.ops if re.search(contains_op, op.name))
    keep = {i for i, _ in ops_in_runs(runs, wanted)}
    return [m for i, m in enumerate(runs) if i in keep]


# --- breakdown ------------------------------------------------------------------

_HLO = re.compile(r"^%?(?P<name>[\w.\-]+) = \(?(?P<shape>\w+\[[\d,]*\])"
                  r".*?\s(?P<op>[\w\-]+)\(")


def short_op_name(hlo: str) -> str:
    """``'%self_attention.72 = bf16[8,1024,3072]{...} custom-call(...'``
    -> ``'self_attention custom-call bf16[8,1024,3072]'``: the flax
    scope (none for an op the compiler named after its kind, so the
    same fusion of every layer adds up under one name), the op kind and
    the first output shape."""
    m = _HLO.match(hlo)
    if m is None:
        return hlo[:80]
    name, op = m.group("name"), m.group("op")
    base = re.sub(r"\.\d+$", "", name)
    generic = base.replace("_", "-") == op or base.endswith("fusion") \
        or base.startswith(op)
    return f"{'' if generic else base + ' '}{op} {m.group('shape')}"


def breakdown(trace: Trace, top_ops: int = 10, top_gaps: int = 5) -> dict:
    """The ``top_ops`` device operations by total time in the window
    and the ``top_gaps`` longest idle gaps of the first device, each
    gap named by the ``bench.*`` span the host was in at its middle."""
    totals: Dict[str, float] = {}
    lo, hi = trace.window
    for d in trace.devices:
        for e in d.ops:
            if lo <= e.start <= hi:
                key = short_op_name(e.name)
                totals[key] = totals.get(key, 0.0) + e.dur
    n_dev = max(1, sum(1 for d in trace.devices if d.ops))
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top_ops]
    gaps: List[Tuple[str, float]] = []
    if trace.devices and trace.devices[0].ops:
        busy = busy_intervals(trace.devices[0].ops, lo, hi)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_span_at(trace.spans, (a + b) / 2), b - a))
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[k, v / n_dev] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps[:top_gaps]]}


def _span_at(spans: Sequence[Event], t: float) -> str:
    """The innermost (latest-started) bench span covering ``t``."""
    inside = [s for s in spans if s.start <= t <= s.end]
    return inside[-1].name if inside else "outside-bench-spans"
