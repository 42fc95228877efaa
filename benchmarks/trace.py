"""From a ``jax.profiler`` trace to plain event lists, and the two
reductions every traced run reports: device busy time and the
``breakdown``.

The ``.xplane.pb`` is read with ``jax.profiler.ProfileData``.  On
this machine (see README.md) a device plane ``/device:TPU:<n>`` holds the
lines ``XLA Modules`` (one event per program run, named
``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event per device op,
named by its HLO text); the host plane ``/host:CPU`` holds one line per
thread, and ``jax.profiler.TraceAnnotation`` spans land on the line of
the thread that opened them.  All times are seconds from the trace's
own zero, host and device on one clock.

An op's framework name (``jit(f)/named_scope/flax module/primitive``,
what XLA keeps in ``metadata={op_name=...}``) is the stat ``tf_op`` of
the op's *event metadata*, which ``ProfileData`` does not hand out (it
gives an event's own stats: offsets and durations).  ``op_scopes``
therefore reads that one map from the file's protobuf wire format
itself; nothing else is taken from there.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."        # the benchmark's own host annotations
PROGRAM_PREFIX = "apex."      # the program's own (README.md)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float              # seconds
    dur: float                # seconds
    scope: str = ""           # XLA Ops only: the framework op name
    #                           (jit(f)/named_scope/flax module/primitive)

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
    program_spans: List[Event] = dataclasses.field(
        default_factory=list)     # apex.* host spans, by start

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def make_trace(devices: Sequence[DeviceTrace], spans: Iterable[Event],
               program_spans: Iterable[Event] = ()) -> Trace:
    """Sort, and set the window (also how the tests build a trace by
    hand).  The program's spans never move the window."""
    devices = [DeviceTrace(sorted(d.modules, key=lambda e: e.start),
                           sorted(d.ops, key=lambda e: e.start))
               for d in devices]
    spans = sorted(spans, key=lambda e: e.start)
    program_spans = sorted(program_spans, key=lambda e: e.start)
    marks = spans or [e for d in devices for e in (d.modules or d.ops)]
    window = (min(e.start for e in marks),
              max(e.end for e in marks)) if marks else (0.0, 0.0)
    return Trace(devices, spans, window, program_spans)


_DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, the bytes for a length-delimited or fixed field."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        value = shift = 0
        while True:
            byte = buf[i]
            i += 1
            value |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                return value

    while i < n:
        key = varint()
        wire = key & 7
        if wire == 0:
            yield key >> 3, varint()
            continue
        if wire not in (1, 2, 5):
            raise ValueError(f"protobuf wire type {wire}")
        size = varint() if wire == 2 else 8 if wire == 1 else 4
        yield key >> 3, buf[i:i + size]
        i += size


def op_scopes(path: str) -> Dict[str, str]:
    """HLO text -> framework op name, for every op of the device planes
    of an ``.xplane.pb`` that carries one.  By ``xplane.proto``: XSpace
    .planes = 1; XPlane .name = 2, .event_metadata = 4 and
    .stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata .name
    = 2, .stats = 5; XStatMetadata .id = 1, .name = 2; XStat
    .metadata_id = 1, .str_value = 5.  The value is ``<op name>:<op
    type>`` and jax leaves the type empty.  The map is keyed by the HLO
    text, as the events are: where two programs hold the same text, the
    later plane entry's scope stands for both.  A map entry with no
    value is passed over.  (To be replaced by ``ProfileData`` the day
    it hands out event metadata.)"""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    scopes: Dict[str, str] = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        parts = list(_fields(plane))
        name = next((bytes(v).decode() for k, v in parts if k == 2), "")
        if not _DEVICE_PLANE.fullmatch(name):
            continue
        tf_op = None
        for k, entry in parts:
            if k == 5:
                meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
                if bytes(meta.get(2, b"")) == b"tf_op":
                    tf_op = meta.get(1)
        if tf_op is None:
            continue
        for k, entry in parts:
            if k != 4:
                continue
            hlo = None
            for mk, mv in _fields(dict(_fields(entry)).get(2, b"")):
                if mk == 2:
                    hlo = bytes(mv).decode()
                elif mk == 5:
                    stat = dict(_fields(mv))
                    if stat.get(1) == tf_op and 5 in stat and hlo:
                        value = bytes(stat[5]).decode()
                        scopes[hlo] = value.rpartition(":")[0] or value
    return scopes


def load_trace(trace_dir: str) -> Optional[Trace]:
    """The newest ``.xplane.pb`` under ``trace_dir``, or None when the
    profiler wrote none."""
    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return None
    data = ProfileData.from_file(found[-1])
    scopes = op_scopes(found[-1])
    devices: Dict[str, DeviceTrace] = {}
    spans: List[Event] = []
    program_spans: List[Event] = []
    for plane in data.planes:
        device = _DEVICE_PLANE.fullmatch(plane.name)
        if device is None and plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if device is not None and line.name in ("XLA Modules",
                                                    "XLA Ops"):
                events = [Event(e.name, e.start_ns * 1e-9,
                                e.duration_ns * 1e-9,
                                scopes.get(e.name, ""))
                          for e in line.events]
                dev = devices.setdefault(plane.name, DeviceTrace([], []))
                if line.name == "XLA Modules":
                    dev.modules.extend(events)
                else:
                    dev.ops.extend(events)
            elif device is None:
                for e in line.events:
                    for prefix, into in ((SPAN_PREFIX, spans),
                                         (PROGRAM_PREFIX, program_spans)):
                        if e.name.startswith(prefix):
                            into.append(Event(e.name, e.start_ns * 1e-9,
                                              e.duration_ns * 1e-9))
    return make_trace([devices[k] for k in sorted(devices)], spans,
                      program_spans)


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


def ops_matching(ops: Iterable[Event], op_pattern: Optional[str],
                 scope_pattern: Optional[str] = None) -> Iterable[Event]:
    """The ops whose HLO text matches ``op_pattern`` and whose scope
    matches ``scope_pattern``; an absent pattern matches every op."""
    return (op for op in ops
            if re.search(op_pattern or "", op.name)
            and re.search(scope_pattern or "", op.scope))


def runs_matching(device: DeviceTrace, module_pattern: str,
                  contains_op: Optional[str] = None,
                  contains_scope: Optional[str] = None) -> List[Event]:
    """The device's program runs whose name matches ``module_pattern``
    and, with ``contains_op`` or ``contains_scope``, that hold at least
    one op matching both (a decode tick and a prefill are both
    ``jit_step``: the flash-decode custom call tells them apart)."""
    runs = [m for m in device.modules if re.search(module_pattern, m.name)]
    if contains_op is None and contains_scope is None:
        return runs
    wanted = ops_matching(device.ops, contains_op, contains_scope)
    keep = {i for i, _ in ops_in_runs(runs, wanted)}
    return [m for i, m in enumerate(runs) if i in keep]


# --- breakdown ------------------------------------------------------------------

_HLO = re.compile(r"^%?(?P<name>[\w.\-]+) = \(?(?P<shape>\w+\[[\d,]*\])"
                  r".*?\s(?P<op>[\w\-]+)\(")


@functools.lru_cache(maxsize=None)     # a trace names ~2,000 ops 50,000 times
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
    gap named by the innermost ``bench.*`` or ``apex.*`` span the host
    was in at its middle."""
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
    spans = sorted(trace.spans + trace.program_spans,
                   key=lambda e: (e.start, -e.dur))
    if trace.devices and trace.devices[0].ops:
        busy = busy_intervals(trace.devices[0].ops, lo, hi)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_span_at(spans, (a + b) / 2), b - a))
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[k, v / n_dev] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps[:top_gaps]]}


@functools.lru_cache(maxsize=None)
def short_scope(scope: str) -> str:
    """``'jit(_step)/jvp(GPTModel)/GPTModel.hidden_states/transformer/
    layer_7/mlp/dense_h_to_4h/dot_general'`` -> ``'jvp(GPTModel)/
    GPTModel.hidden_states/transformer/layer_N/mlp/dense_h_to_4h'``:
    without the jitted function at its head and the primitive at its
    tail, a module's index as ``N``, so that every layer's share adds up
    under one name.  An op straight under the jitted function keeps its
    primitive, and one with no scope reads ``(none)``."""
    parts = scope.split("/")
    while parts and re.fullmatch(r"(jit|pjit)\(.*\)", parts[0]):
        parts = parts[1:]
    parts = parts[:-1] or parts
    return re.sub(r"_\d+(?=/|$)", "_N", "/".join(parts)) or "(none)"


def scope_ms(trace: Trace, top: int = 8,
             op: Optional[str] = None) -> List[Tuple[str, float]]:
    """The ``top`` scopes (``short_scope``) by device time in the window,
    each in ms per run of the program that was busiest there; with
    ``op``, of the ops alone that ``short_op_name`` gives that name."""
    lo, hi = trace.window
    totals: Dict[str, float] = {}
    programs: Dict[str, List[float]] = {}
    for d in trace.devices:
        for m in d.modules:
            if lo <= m.start <= hi:
                programs.setdefault(m.name, []).append(m.dur)
        for e in d.ops:
            if lo <= e.start <= hi and (op is None
                                        or short_op_name(e.name) == op):
                key = short_scope(e.scope)
                totals[key] = totals.get(key, 0.0) + e.dur
    runs = len(max(programs.values(), key=sum, default=[])) or 1
    best = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [(k, 1e3 * v / runs) for k, v in best]


def _span_at(spans: Sequence[Event], t: float) -> str:
    """The innermost (latest-started) of ``spans`` (sorted by start)
    covering ``t``."""
    inside = [s for s in spans if s.start <= t <= s.end]
    return inside[-1].name if inside else "outside-bench-spans"
